"""Record the small pair that test_instruction_time.py reads: a few steps
of one BERT encoder layer (two heads of 64 over 128 positions, the shape
the fused attention kernels take) and a dense head under Adam with remat
on, through the harness's own window and spans, and the `step_programs()`
of the process that ran them, `instructions` included.  One step of it
holds all four kinds of instruction_time (products, weight gradients with
Adam's update behind them, Mosaic calls, vector work) and a recomputed
pass.  Run on the chip (one is enough), once, when instruction_time
changes what it reads:

    python benchmark/tests/record_instruction_fixture.py chiprun_out/instruction_fixture

It writes `instruction_small.xplane.pb.gz` and
`instruction_small.programs.json.gz` there: copy both to
benchmark/tests/data/.  (`record_scope_fixture.py` records the older
pair, whose table has no `instructions`.)
"""
from __future__ import annotations

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)),
                os.path.dirname(HERE)]

STEPS, LEAD_IN = 3, 2


def main(out: str) -> int:
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from harness import trace_reduce, window
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.bert import BERTEncoderCell
    from mxnet_tpu.parallel.spmd import step_programs

    class OneLayer(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.layer0 = BERTEncoderCell(128, 512, 2, dropout=0.1,
                                              prefix="layer0_")
                self.head = nn.Dense(16, flatten=False, prefix="head_")

        def hybrid_forward(self, F, x, mask):
            return self.head(self.layer0(x, mask))

    np.random.seed(0)
    mx.random.seed(0)
    net = OneLayer(prefix="tiny_")
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    with mx.autograd.pause():
        net(mx.nd.zeros((1, 128, 128), ctx=mx.cpu()),
            mx.nd.ones((1, 128), ctx=mx.cpu()))
    net.cast("bfloat16")
    trainer = parallel.SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=parallel.make_mesh(dp=1), remat=True)
    rng = np.random.RandomState(0)
    n = 16
    put = lambda a: jax.device_put(a, parallel.shard_batch(    # noqa: E731
        trainer.mesh, extra_dims=a.ndim - 1))
    x = put(rng.rand(n, 128, 128).astype("bfloat16"))
    mask = put(np.ones((n, 128), "bfloat16"))
    y = put(rng.randint(0, 16, (n, 128)).astype(np.int32))
    for _ in range(3):
        trainer.step(x, mask, y).asnumpy()
    jax.profiler.start_trace(out)
    win = window.run(lambda: trainer.step(x, mask, y),
                     steps=LEAD_IN + STEPS,
                     span=jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    path = trace_reduce.newest_xplane(out)
    with open(path, "rb") as f, gzip.open(
            os.path.join(out, "instruction_small.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    programs = step_programs()
    with gzip.open(os.path.join(out, "instruction_small.programs.json.gz"),
                   "wt") as f:
        json.dump(programs, f, indent=0, sort_keys=True)
    print(path, os.path.getsize(path), "bytes;", win.completed, "steps on",
          jax.devices()[0].device_kind, "; programs:",
          [(p["module"], p["origin"], p["scoped"], len(p["instructions"]),
            sorted({r["kernel"] for r in p["instructions"].values()
                    if r["kernel"]}),
            sorted({r["pass"] for r in p["instructions"].values()}))
           for p in programs])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
