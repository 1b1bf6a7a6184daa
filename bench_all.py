"""All five BASELINE.md configurations, one row each, measured on one chip.

MNIST MLP, ResNet-50, BERT-base pretrain, SSD-300-ResNet50, Transformer
NMT - each as ONE jitted train step (forward+backward+update) via
parallel.SPMDTrainer - plus two head-to-head variants: ResNet-50 with
fused Conv+BN (MXNET_FUSED_CONVBN=1) and BERT with the Pallas attention
kernel disabled (MXNET_USE_PALLAS=0).

One process per chip: the parent never imports jax and runs one child per
(configuration, variant) in turn, so each child takes the chip alone and
its environment variant stays its own.  Each child prints one JSON row
that names the platform, device_kind and device count it ran on; a child
that finds no accelerator exits 2 and the run stops there; a child that
fails prints its traceback and the run ends non-zero after the others.
Nothing is retried and nothing is substituted for a missing row.

Usage:
    python bench_all.py                  # the chip, all configurations
    python bench_all.py --config bert_base --variant no_pallas
    python bench_all.py --cpu-smoke      # tiny shapes, CPU, CI self-test
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


# ---------------------------------------------------------------------------
# measurement children (run in their own process; may import jax)
# ---------------------------------------------------------------------------

def _measure_loop(step_fn, unit_count, steps, warmup):
    """Time `steps` calls of step_fn after warmup; step_fn returns the
    loss NDArray whose .asnumpy() is the only sync point."""
    import numpy as np

    # at least one unmeasured call: compilation must stay out of the
    # timed window (and `loss` must be bound even for --warmup 0)
    for _ in range(max(warmup, 1)):
        loss = step_fn()
    loss.asnumpy()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step_fn()
    lval = float(loss.asnumpy())
    dt = time.perf_counter() - t0
    assert np.isfinite(lval), f"non-finite loss {lval}"
    return unit_count * steps / dt, lval


class _Identity:
    def __call__(self, out, *labels):
        return out


def _spmd_trainer(net, optimizer, opt_params):
    from mxnet_tpu import parallel

    return parallel.SPMDTrainer(net, _Identity(), optimizer, opt_params,
                                mesh=parallel.make_mesh(dp=1), n_labels=0)


def bench_mnist_mlp(args):
    """BASELINE config 1 — examples/gluon/mnist.py MLP, synthetic data."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import HybridBlock

    bs = 64 if args.cpu_smoke else 512

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.net = nn.HybridSequential(prefix="")
                self.net.add(nn.Dense(128, activation="relu"))
                self.net.add(nn.Dense(64, activation="relu"))
                self.net.add(nn.Dense(10))

        def hybrid_forward(self, F, x, y):
            import jax
            import jax.numpy as jnp

            logits = self.net(x)
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(
                lsm, y[:, None].astype(jnp.int32), -1)[:, 0]
            return nll.mean()

    step_blk = Step()
    step_blk.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    rng = np.random.RandomState(0)
    x = rng.rand(bs, 784).astype(np.float32)
    y = rng.randint(0, 10, (bs,)).astype(np.int32)
    # deferred shapes resolve through the inner net: the Step wrapper's
    # jnp loss math is traced-only
    with mx.autograd.pause():
        # warm inputs pinned to the init ctx (see bench_bert_base)
        step_blk.net(mx.nd.array(x, ctx=mx.cpu()))
    trainer = _spmd_trainer(step_blk, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    xd, yd = trainer._place(x, None), trainer._place(y, None)
    tp, lval = _measure_loop(lambda: trainer.step(xd, yd), bs,
                             args.steps, args.warmup)
    return {"metric": "mnist_mlp_train_throughput", "value": round(tp, 1),
            "unit": "samples/s", "loss": round(lval, 4)}


def bench_resnet50(args):
    """BASELINE config 2 — delegated to bench.py's exact measurement
    (variant `fused_convbn` = MXNET_FUSED_CONVBN=1, set by the parent)."""
    import bench

    class A:
        cpu_smoke = args.cpu_smoke
        batch_size, image_size = 256, 224
        steps, warmup = args.steps, args.warmup
        dtype, layout = "bfloat16", "NHWC"

    return bench.measure(A())


def bench_bert_base(args):
    """BASELINE config 3 — MLM+NSP pretrain step, seq 128 (GluonNLP
    run_pretraining.py counterpart; variant `no_pallas` = XLA attention)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.bert import get_bert_model

    if args.cpu_smoke:
        bs, seq, vocab = 2, 32, 1000
        kw = dict(num_layers=2, units=64, hidden_size=128, num_heads=4,
                  max_length=seq)
    else:
        bs, seq, vocab = 32, 128, 30522
        kw = dict(max_length=512)

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.bert = get_bert_model("bert_12_768_12",
                                           vocab_size=vocab, **kw)

        def hybrid_forward(self, F, tokens, segments, vlen, mlm_labels,
                           mlm_weight, nsp_labels):
            import jax
            import jax.numpy as jnp

            seq_out, pooled = self.bert(tokens, segments, vlen)
            mlm_scores = self.bert.decode_mlm(seq_out)
            nsp_scores = self.bert.classify_nsp(pooled)
            lsm = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(
                lsm, mlm_labels[..., None].astype(jnp.int32), -1)[..., 0]
            mlm_l = ((nll * mlm_weight).sum()
                     / jnp.maximum(mlm_weight.sum(), 1.0))
            nsp_lsm = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), -1)
            nsp_l = -jnp.take_along_axis(
                nsp_lsm, nsp_labels[:, None].astype(jnp.int32), -1)[:, 0]
            return mlm_l + nsp_l.mean()

    step_blk = Step()
    step_blk.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
    rng = np.random.RandomState(0)
    tokens = rng.randint(5, vocab, (bs, seq)).astype(np.int32)
    segments = np.zeros((bs, seq), np.int32)
    vlen = np.full((bs,), seq, np.float32)
    mlm_labels = rng.randint(5, vocab, (bs, seq)).astype(np.int32)
    mlm_weight = (rng.rand(bs, seq) < 0.15).astype(np.float32)
    nsp_labels = rng.randint(0, 2, (bs,)).astype(np.int32)
    with mx.autograd.pause():
        # warm inputs pinned to the init ctx: on a TPU host the default
        # context is tpu(0), and cpu-initialized params must not meet
        # tpu-resident inputs in the eager warm pass
        seq_out, pooled = step_blk.bert(
            mx.nd.array(tokens, ctx=mx.cpu()),
            mx.nd.array(segments, ctx=mx.cpu()),
            mx.nd.array(vlen, ctx=mx.cpu()))
        step_blk.bert.decode_mlm(seq_out)
        step_blk.bert.classify_nsp(pooled)
    if not args.cpu_smoke:
        step_blk.cast("bfloat16")
    trainer = _spmd_trainer(step_blk, "adam", {"learning_rate": 1e-4})
    placed = [trainer._place(a, None) for a in
              (tokens, segments, vlen, mlm_labels, mlm_weight, nsp_labels)]
    tp, lval = _measure_loop(lambda: trainer.step(*placed), bs,
                             args.steps, args.warmup)
    return {"metric": "bert_base_pretrain_throughput",
            "value": round(tp, 1), "unit": "samples/s",
            "seq_len": seq, "loss": round(lval, 4)}


def bench_ssd_resnet50(args):
    """BASELINE config 4 — SSD-300-ResNet50 train step with the GluonCV
    SSDMultiBoxLoss (targets precomputed host-side, as GluonCV's default
    training loop does with its label batchify)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.detection import (SSDMultiBoxLoss,
                                                     ssd_300_resnet50_v1)

    bs = 1 if args.cpu_smoke else 32
    size = 300  # the anchor spec is keyed to the 300x300 input

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.ssd = ssd_300_resnet50_v1(classes=20)
                self.loss = SSDMultiBoxLoss()

        def hybrid_forward(self, F, x, cls_t, box_t):
            cls_p, box_p, _anchors = self.ssd(x)
            return self.loss(cls_p, box_p, cls_t, box_t)

    step_blk = Step()
    step_blk.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    rng = np.random.RandomState(0)
    x = rng.rand(bs, 3, size, size).astype(np.float32)
    with mx.autograd.pause():
        n_anchors = int(step_blk.ssd(
            mx.nd.array(x[:1], ctx=mx.cpu()))[0].shape[1])
    cls_t = rng.randint(-1, 21, (bs, n_anchors)).astype(np.float32)
    box_t = (rng.randn(bs, n_anchors, 4) * 0.1).astype(np.float32)
    if not args.cpu_smoke:
        step_blk.cast("bfloat16")
        x = x.astype("bfloat16")   # the image meets bf16 conv weights
    trainer = _spmd_trainer(step_blk, "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9,
                             "wd": 5e-4})
    placed = [trainer._place(a, None) for a in (x, cls_t, box_t)]
    tp, lval = _measure_loop(lambda: trainer.step(*placed), bs,
                             args.steps, args.warmup)
    return {"metric": "ssd300_resnet50_train_throughput",
            "value": round(tp, 1), "unit": "img/s",
            "anchors": n_anchors, "loss": round(lval, 4)}


def bench_transformer_nmt(args):
    """BASELINE config 5 — transformer-base en-de train step (Sockeye /
    GluonNLP counterpart), label-smoothed CE, one (64,64) bucket."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.transformer import get_transformer_model

    if args.cpu_smoke:
        bs, slen, vocab = 2, 16, 1000
        kw = dict(num_layers=2, units=64, hidden_size=128, num_heads=4)
    else:
        bs, slen, vocab = 64, 64, 32000
        kw = {}

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.net = get_transformer_model(
                    "transformer_base", src_vocab_size=vocab,
                    tgt_vocab_size=vocab, **kw)

        def hybrid_forward(self, F, src, tgt_in, src_valid, tgt_valid,
                           tgt_out):
            import jax
            import jax.numpy as jnp

            logits = self.net(src, tgt_in, src_valid, tgt_valid)
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            eps = 0.1
            nll = -jnp.take_along_axis(
                lsm, tgt_out[..., None].astype(jnp.int32), -1)[..., 0]
            smooth = -lsm.mean(-1)
            steps_ = jax.lax.broadcasted_iota(
                jnp.int32, nll.shape, 1).astype(jnp.float32)
            mask = (steps_ < tgt_valid[:, None].astype(jnp.float32))
            per_tok = ((1 - eps) * nll + eps * smooth) * mask
            return per_tok.sum() / jnp.maximum(mask.sum(), 1.0)

    step_blk = Step()
    step_blk.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    rng = np.random.RandomState(0)
    src = rng.randint(4, vocab, (bs, slen)).astype(np.int32)
    tgt_in = rng.randint(4, vocab, (bs, slen)).astype(np.int32)
    tgt_out = rng.randint(4, vocab, (bs, slen)).astype(np.int32)
    sv = np.full((bs,), slen, np.float32)
    tv = np.full((bs,), slen, np.float32)
    with mx.autograd.pause():
        step_blk.net(mx.nd.array(src, ctx=mx.cpu()),
                     mx.nd.array(tgt_in, ctx=mx.cpu()),
                     mx.nd.array(sv, ctx=mx.cpu()),
                     mx.nd.array(tv, ctx=mx.cpu()))
    if not args.cpu_smoke:
        step_blk.cast("bfloat16")
    trainer = _spmd_trainer(step_blk, "adam", {"learning_rate": 3e-4})
    placed = [trainer._place(a, None) for a in (src, tgt_in, sv, tv,
                                                tgt_out)]
    tp, lval = _measure_loop(lambda: trainer.step(*placed), bs * slen,
                             args.steps, args.warmup)
    return {"metric": "transformer_nmt_train_throughput",
            "value": round(tp, 1), "unit": "tokens/s",
            "bucket": [slen, slen], "loss": round(lval, 4)}


CONFIGS = {
    "mnist_mlp": bench_mnist_mlp,
    "resnet50": bench_resnet50,
    "bert_base": bench_bert_base,
    "ssd_resnet50": bench_ssd_resnet50,
    "transformer_nmt": bench_transformer_nmt,
}

# (config, variant-name, extra env) — variants isolate env flags per child
RUNS = [
    ("mnist_mlp", "default", {}),
    ("resnet50", "default", {}),
    ("resnet50", "fused_convbn", {"MXNET_FUSED_CONVBN": "1"}),
    ("bert_base", "default", {}),
    ("bert_base", "no_pallas", {"MXNET_USE_PALLAS": "0"}),
    ("ssd_resnet50", "default", {}),
    ("transformer_nmt", "default", {}),
]


def _measure(args, variant):
    """One row, in this process: the configuration's numbers plus the
    variant and the device it ran on."""
    import bench

    row = CONFIGS[args.config](args)
    row["variant"] = variant
    row.update(bench.device_fields())
    print(json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=sorted(CONFIGS), default=None)
    ap.add_argument("--variant", default="default")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--cpu-smoke", action="store_true")
    ap.add_argument("--_child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args._child or args.cpu_smoke:
        # a measurement process: it may import jax, and it spawns nothing
        import bench

        bench.start(args.cpu_smoke)
        if args.cpu_smoke:
            args.steps, args.warmup = 3, 1
        for name in [args.config] if args.config else sorted(CONFIGS):
            args.config = name
            _measure(args, "cpu_smoke" if args.cpu_smoke else args.variant)
        return 0

    # ---- parent: stays off jax; one child per (config, variant) ----
    if args.variant != "default" and args.config is None:
        ap.error("--variant requires --config")
    runs = [r for r in RUNS if args.config in (None, r[0])
            and (args.config is None or args.variant in ("default", r[1]))]
    failed = []
    for name, variant, env in runs:
        cmd = [sys.executable, os.path.abspath(__file__), "--_child",
               "--config", name, "--variant", variant,
               "--steps", str(args.steps), "--warmup", str(args.warmup)]
        rc = subprocess.run(cmd, env={**os.environ, **env}).returncode
        if rc == 2:
            return 2  # no accelerator: the child has said so
        if rc != 0:
            failed.append(f"{name}/{variant} (exit {rc})")
    if failed:
        print("failed: " + ", ".join(failed), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
