"""Capture + analyze an xplane trace of the ResNet-50 bench train step.

Writes a per-op-category device-time breakdown (the MFU analysis VERDICT
round 2 asked for).  Usage:
    python tools/profile_bench.py [--batch-size 256] [--steps 5] [--out DIR]
The fused paths profile through the same command via their env knobs:
    MXNET_FUSED_CONVBN=1 [MXNET_FUSED_CONVBN_BWD=1] python tools/profile_bench.py
Parses the xplane.pb with tensorflow's proto (no tensorboard needed).

The capture window runs through ``telemetry.mxtriage`` (the one
deep-capture path every surface shares), so the run is admission-gated,
indexed, and leaves an ``mxprof.json`` aggregate + ``meta.json``
beside the xplane files.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def capture(args) -> str:
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.telemetry import mxtriage

    net = vision.resnet50_v1(classes=1000, layout=args.layout)
    net.initialize(mx.initializer.Xavier(magnitude=2.0), ctx=mx.cpu())
    with mx.autograd.pause():
        shape = ((1, 3, 32, 32) if args.layout == "NCHW" else (1, 32, 32, 3))
        net(mx.nd.zeros(shape, ctx=mx.cpu()))
    if args.dtype != "float32":
        net.cast(args.dtype)

    rng = np.random.RandomState(0)
    ishape = ((args.batch_size, 3, args.image_size, args.image_size)
              if args.layout == "NCHW"
              else (args.batch_size, args.image_size, args.image_size, 3))
    images = rng.rand(*ishape).astype(args.dtype)
    labels = rng.randint(0, 1000, size=(args.batch_size,)).astype(np.int32)

    mesh = parallel.make_mesh(dp=1)
    with mesh:
        trainer = parallel.SPMDTrainer(
            net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
        images = trainer._place(images, None)
        labels = trainer._place(labels, None)
        for _ in range(3):
            loss = trainer.step(images, labels)
        loss.asnumpy()

        t0 = time.perf_counter()
        for _ in range(args.steps):
            loss = trainer.step(images, labels)
        loss.asnumpy()
        dt = time.perf_counter() - t0
        print(f"throughput: {args.batch_size*args.steps/dt:.1f} img/s "
              f"({dt/args.steps*1e3:.1f} ms/step)")

        os.makedirs(args.out, exist_ok=True)
        # the one deep-capture path (admission-gated + indexed):
        # manual bracket around exactly the measured steps
        mxtriage.start_manual(args.out)
        try:
            for _ in range(args.steps):
                loss = trainer.step(images, labels)
            loss.asnumpy()
        finally:
            mxtriage.stop_manual()
    return args.out


# categorize by the op's own label (lhs of " = "), NOT by substring over
# the full event name — operand text would misattribute (e.g. "convert"
# matching "conv", fusions quoting %copy-done operands)
LABEL_CATEGORIES = [
    ("conv+fusion (convs, BN-bwd dx)", re.compile(r"^fusion$")),
    ("wgrad+update (add_convert)", re.compile(r"^add_convert_fusion$")),
    ("BN stat reduces (convert_reduce)", re.compile(r"^convert_reduce_fusion$")),
    ("relu/residual (maximum_add)", re.compile(r"^maximum_add_fusion$")),
    ("pool", re.compile(r"^(select_and_scatter|reduce-window)")),
    ("copies/slices", re.compile(r"^(copy|slice|bitcast)")),
    ("other fusions", re.compile(r"fusion$")),
]


def _label(name: str) -> str:
    lhs = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", lhs)


def analyze(logdir: str, steps: int):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    pbs = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not pbs:
        print("no xplane.pb found under", logdir)
        return
    pb = max(pbs, key=os.path.getmtime)
    xs = xplane_pb2.XSpace()
    with open(pb, "rb") as f:
        xs.ParseFromString(f.read())

    for plane in xs.planes:
        if "TPU" not in plane.name:
            continue
        ev_meta = plane.event_metadata
        for line in plane.lines:
            if line.name != "XLA Ops":  # the non-overlapped device timeline
                continue
            op_time = defaultdict(int)
            total = 0
            for ev in line.events:
                lab = _label(ev_meta[ev.metadata_id].name)
                op_time[lab] += ev.duration_ps
                total += ev.duration_ps
            print(f"\n=== {plane.name} 'XLA Ops': "
                  f"{total/1e12*1e3/steps:.1f} ms/step ===")
            cat_time = defaultdict(int)
            for lab, t in op_time.items():
                for cat, pat in LABEL_CATEGORIES:
                    if pat.search(lab):
                        cat_time[cat] += t
                        break
                else:
                    cat_time["other"] += t
            for cat, t in sorted(cat_time.items(), key=lambda kv: -kv[1]):
                print(f"  {cat:36s} {t/1e12*1e3/steps:8.2f} ms/step  "
                      f"{100*t/total:5.1f}%")
            print("  top 15 op labels:")
            for lab, t in sorted(op_time.items(), key=lambda kv: -kv[1])[:15]:
                print(f"    {t/1e12*1e3/steps:8.3f} ms/step  {lab}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--layout", default="NHWC")
    ap.add_argument("--out", default="/tmp/xprof_bench")
    ap.add_argument("--analyze-only", action="store_true")
    args = ap.parse_args()
    if not args.analyze_only:
        capture(args)
    analyze(args.out, args.steps)


if __name__ == "__main__":
    sys.exit(main())
