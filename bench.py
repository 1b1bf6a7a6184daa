"""ResNet-50 synthetic-data training throughput on one chip (the
BASELINE.md workload: images/sec/chip; MXNet ResNet-50 on 1xV100 ~= 375
img/s fp32).

The whole train step (forward, backward, grad reduce, SGD update, BatchNorm
stat update) is ONE jitted XLA program with donated buffers via
parallel.SPMDTrainer over a single-device mesh; compute in bfloat16 for the
MXU.

One process, one measurement, one JSON row on stdout:
  {"metric": ..., "value": N, "unit": "img/s", "vs_baseline": N,
   "platform": ..., "device_kind": ..., "n_devices": N}
The process that runs this takes the chip (one process per chip: do not
start it from a parent that has touched jax).  Without an accelerator it
exits 2 and prints no row; a failed measurement is a traceback and a
non-zero exit.  The default run measures the op-granular step only;
`MXNET_FUSED_CONVBN=1 python bench.py` measures the fused Conv+BN variant
and its row says how many units ran the Pallas kernel and how many XLA.
`--cpu-smoke` is the CI self-test: tiny shapes on the CPU backend, and the
row says platform "cpu" — it is not a speed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

V100_BASELINE_IMG_S = 375.0  # BASELINE.md: MXNet ResNet-50 fp32 on 1xV100

METRIC = "resnet50_v1_train_throughput_per_chip"


def device_fields() -> dict:
    """The device as JAX reports it; every row any benchmark prints
    carries these, so a CPU number can never pass for a chip number."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def start(cpu_smoke: bool) -> None:
    """What every measurement process does before its first compile: pin
    the CPU for --cpu-smoke, place JAX's compile cache, and refuse to
    measure when the default platform is the CPU."""
    import jax

    if cpu_smoke:
        jax.config.update("jax_platforms", "cpu")
        return
    from mxnet_tpu.compile_cache import jax_cache

    jax_cache.configure()
    fields = device_fields()
    if fields["platform"] == "cpu":
        print(f"no accelerator: jax.devices() reports {fields}; a "
              "benchmark row is only measured on a chip (--cpu-smoke is "
              "the CPU self-test)", file=sys.stderr)
        sys.exit(2)


def resnet_trainer(*, model="resnet50_v1", classes=1000, layout="NHWC",
                   dtype="bfloat16", n_dev=1):
    """The repo's main training path, as examples/imagenet_train.py builds
    it: a model-zoo ResNet initialised on cpu(), cast, and handed to
    SPMDTrainer (SGD momentum 0.9, wd 1e-4), which places it on a dp mesh
    of the first `n_dev` devices.  chip_smoke.py drives the same
    construction."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    net = getattr(vision, model)(classes=classes, layout=layout)
    net.initialize(mx.initializer.Xavier(magnitude=2.0), ctx=mx.cpu())
    with mx.autograd.pause():   # resolve deferred shapes (cheap spatial dims)
        shape = ((1, 3, 32, 32) if layout == "NCHW" else (1, 32, 32, 3))
        net(mx.nd.zeros(shape, ctx=mx.cpu()))
    if dtype != "float32":
        net.cast(dtype)
    return parallel.SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        mesh=parallel.make_mesh(dp=n_dev))


def measure(args) -> dict:
    """One row: the step as the environment selects it (op-granular by
    default, fused Conv+BN under MXNET_FUSED_CONVBN=1)."""
    if args.cpu_smoke:
        args.batch_size, args.image_size = 8, 64
        args.steps, args.warmup = 3, 1

    import numpy as np

    from mxnet_tpu.util import env

    layout = args.layout
    rng = np.random.RandomState(0)
    ishape = ((args.batch_size, 3, args.image_size, args.image_size)
              if layout == "NCHW"
              else (args.batch_size, args.image_size, args.image_size, 3))
    images = rng.rand(*ishape).astype(args.dtype)
    labels = rng.randint(0, 1000, size=(args.batch_size,)).astype(np.int32)

    trainer = resnet_trainer(layout=layout, dtype=args.dtype)
    # synthetic-data convention (ref: image-classification --benchmark 1):
    # the batch lives on device; we measure the train step, not the
    # host link
    images = trainer._place(images, None)
    labels = trainer._place(labels, None)

    # at least one unmeasured step: compilation stays out of the window
    for _ in range(max(args.warmup, 1)):
        loss = trainer.step(images, labels)
    loss.asnumpy()

    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss = trainer.step(images, labels)
    lval = float(loss.asnumpy())  # blocks: full async chain done
    dt = time.perf_counter() - t0

    img_s = args.batch_size * args.steps / dt
    if not np.isfinite(lval):
        raise FloatingPointError(f"non-finite loss {lval}")
    row = {
        "metric": METRIC,
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / V100_BASELINE_IMG_S, 3),
        **device_fields(),
    }
    if env.get_bool("MXNET_FUSED_CONVBN"):
        from mxnet_tpu.ops import pallas_convbn

        row["variant"] = "fused_convbn"
        row["fused_units"] = pallas_convbn.unit_counts()
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--layout", default="NHWC", choices=["NCHW", "NHWC"])
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="tiny shapes on the CPU backend (CI self-test)")
    args = ap.parse_args()
    start(args.cpu_smoke)
    print(json.dumps(measure(args)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
