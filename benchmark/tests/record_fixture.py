"""Record the small trace that test_trace_reduce.py reads: a few steps of
a tiny conv + BatchNorm + dense net under SPMDTrainer over every chip of
the host, through the harness's own window and spans.  Run on the chip
(four chips for the collectives), once, when the reduction changes what
it reads:

    python benchmark/tests/record_fixture.py chiprun_out/fixture

and copy the .xplane.pb it names, gzipped, to benchmark/tests/data/.
"""
from __future__ import annotations

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

    chips = len(jax.devices())
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(64, 3, padding=1, layout="NHWC"),
            nn.BatchNorm(axis=3), nn.Activation("relu"),
            nn.GlobalAvgPool2D(layout="NHWC"), nn.Dense(16))
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    with mx.autograd.pause():
        net(mx.nd.zeros((1, 8, 8, 3), ctx=mx.cpu()))
    net.cast("bfloat16")
    trainer = parallel.SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=parallel.make_mesh(dp=chips))
    rng = np.random.RandomState(0)
    n = 64 * chips
    put = lambda a: jax.device_put(a, parallel.shard_batch(    # noqa: E731
        trainer.mesh, extra_dims=a.ndim - 1))
    x = put(rng.rand(n, 64, 64, 3).astype("bfloat16"))
    y = put(rng.randint(0, 16, n).astype(np.int32))
    for _ in range(3):
        trainer.step(x, y).asnumpy()
    jax.profiler.start_trace(out)
    win = window.run(lambda: trainer.step(x, y), steps=LEAD_IN + STEPS,
                     span=jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    path = trace_reduce.newest_xplane(out)
    print(path, os.path.getsize(path), "bytes;", win.completed, "steps on",
          chips, jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
