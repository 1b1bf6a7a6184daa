"""Record the small pair that test_scope_time.py reads: a few steps of
record_fixture.py's tiny conv + BatchNorm + dense net under SPMDTrainer,
through the harness's own window and spans, and the `step_programs()` of
the process that ran them.  A trace alone is no fixture here: the scopes
live in the program's table, so the two are recorded together.  Run on the
chip (one is enough), once, when scope_time changes what it reads:

    python benchmark/tests/record_scope_fixture.py chiprun_out/scope_fixture

It writes `scope_small.xplane.pb.gz` and `scope_small.programs.json`
there: copy both to benchmark/tests/data/.
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
    from mxnet_tpu.parallel.spmd import step_programs

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
    with open(path, "rb") as f, gzip.open(
            os.path.join(out, "scope_small.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    programs = step_programs()
    with open(os.path.join(out, "scope_small.programs.json"), "w") as f:
        json.dump(programs, f, indent=0, sort_keys=True)
    print(path, os.path.getsize(path), "bytes;", win.completed, "steps on",
          chips, jax.devices()[0].device_kind, "; programs:",
          [(p["module"], p["origin"], p["scoped"], len(p["ops"]))
           for p in programs])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
