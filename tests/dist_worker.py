"""Multi-process worker used by test_dist.py (not itself a test module).

Modeled on the reference's tests/nightly/dist_sync_kvstore.py: launched N
times (by tools/launch.py or the test harness) with the DMLC_* env
contract; each worker asserts dist_sync semantics and prints DIST_OK.
"""
import os
import sys

# force the CPU backend before any jax backend touch (N workers cannot
# share one chip).
# The hybrid lane gives each process FOUR virtual devices (a 2-host pod
# slice in miniature); other modes keep 2.  Script-mode only: pytest
# IMPORTS this module (for hybrid_loss_and_data), and mutating the
# parent's XLA_FLAGS there would shrink its conftest-pinned 8-device
# backend.
_IS_SCRIPT = __name__ == "__main__"
_N_LOCAL = 4 if (_IS_SCRIPT and len(sys.argv) > 1
                 and sys.argv[1] == "hybrid") else 2
if _IS_SCRIPT:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_N_LOCAL}")
import jax  # noqa: E402

if _IS_SCRIPT:
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402
from mxnet_tpu.parallel import dist  # noqa: E402


def mode_kvstore():
    """dist_sync push/pull/pushpull/row_sparse_pull across workers."""
    dist.init()
    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == int(os.environ["DMLC_NUM_WORKER"]), (nw, os.environ)

    # push/pull: store ends at sum over workers (no updater => overwrite
    # with the DCN-allreduced value)
    kv.init("a", nd.zeros((4, 3)))
    kv.push("a", nd.ones((4, 3)) * (rank + 1))
    out = nd.zeros((4, 3))
    kv.pull("a", out=out)
    expect = sum(r + 1 for r in range(nw))
    np.testing.assert_allclose(out.asnumpy(), expect * np.ones((4, 3)),
                               rtol=1e-6)

    # updater path: SGD lr=1 => weight -= sum(grads); every worker applies
    # the same allreduced grad so stores stay consistent
    kv2 = mx.kv.create("dist_sync")
    kv2.set_optimizer(mx.optimizer.SGD(learning_rate=1.0))
    kv2.init(0, nd.zeros((2, 2)))
    kv2.push(0, nd.ones((2, 2)) * (rank + 1))
    w = nd.zeros((2, 2))
    kv2.pull(0, out=w)
    np.testing.assert_allclose(w.asnumpy(), -expect * np.ones((2, 2)),
                               rtol=1e-6)

    # row_sparse grads across workers
    from mxnet_tpu.ndarray import sparse
    kv.init("rs", nd.zeros((6, 2)))
    g = sparse.row_sparse_array(
        (np.ones((1, 2), np.float32), [rank % 6]), shape=(6, 2))
    kv.push("rs", g)
    rs_out = sparse.zeros("row_sparse", (6, 2))
    kv.row_sparse_pull("rs", out=rs_out,
                       row_ids=nd.array([rank % 6], dtype="int32"))
    np.testing.assert_allclose(rs_out.todense().asnumpy()[rank % 6], [1, 1])

    kv.barrier()
    print(f"DIST_OK rank={rank}/{nw}", flush=True)


def mode_train():
    """2-process data-parallel MLP convergence via Trainer(dist_sync)."""
    dist.init()
    from mxnet_tpu.gluon import nn, loss as gloss, Trainer

    rank, nw = dist.rank(), dist.num_workers()
    np.random.seed(0)
    mx.random.seed(0)
    # same init on every worker (same seed), different data shards
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu", in_units=4))
    net.add(nn.Dense(2, in_units=16))
    net.initialize(mx.initializer.Xavier())

    rng = np.random.RandomState(42)
    X = rng.randn(256, 4).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.int32)
    shard = slice(rank * 128 // nw * 2, (rank + 1) * 128 // nw * 2)
    Xs, ys = X[shard], y[shard]

    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1}, kvstore="dist_sync")
    lfn = gloss.SoftmaxCrossEntropyLoss()
    first = last = None
    for epoch in range(30):
        with mx.autograd.record():
            out = net(nd.array(Xs))
            loss = lfn(out, nd.array(ys))
        loss.backward()
        trainer.step(len(Xs) * nw)
        last = float(loss.mean().asnumpy())
        if first is None:
            first = last
    assert last < first * 0.5, (first, last)

    # weights must be bit-identical across workers after sync training
    w = net[0].weight.data().asnumpy()
    gathered = dist.allgather_np(w)
    for r in range(1, gathered.shape[0]):
        np.testing.assert_allclose(gathered[r], gathered[0], rtol=0, atol=0)
    print(f"DIST_OK rank={rank}/{nw} loss {first:.4f}->{last:.4f}",
          flush=True)


def mode_spmd():
    """Unified SPMD step across processes (ISSUE 9): ONE mesh program
    spanning every worker's devices, optimizer states ZeRO-sharded
    job-wide, the executable warm-started from the shared persistent
    compile cache.  Prints per-rank compile accounting for the parent
    to assert the cold/warm contract."""
    import hashlib
    import json

    dist.init()
    from mxnet_tpu.gluon.parameter import Parameter
    from mxnet_tpu.gluon.trainer import Trainer
    from mxnet_tpu.optimizer import spmd as spmd_mod

    rank, nw = dist.rank(), dist.num_workers()
    ctx = [mx.cpu(i) for i in range(_N_LOCAL)]
    shapes = [(32, 8), (64,), (16, 4)]
    init_rng = np.random.RandomState(7)  # same init on every worker
    params = []
    for i, shp in enumerate(shapes):
        p = Parameter(f"w{i}", shape=shp)
        p.initialize(ctx=ctx)
        p.set_data(nd.array(init_rng.randn(*shp).astype("f4")))
        params.append(p)
    tr = Trainer(params, "sgd",
                 {"learning_rate": 0.05, "momentum": 0.9},
                 kvstore="dist_sync", update_on_kvstore=False, spmd=True)
    for step in range(3):
        grng = np.random.RandomState(100 + step)
        for p in params:
            g = grng.randn(*p.shape).astype("f4")
            for r, gnd in enumerate(p.list_grad()):
                # distinct per GLOBAL replica: the in-graph reduce must
                # sum all of them identically on every shard
                scale = rank * _N_LOCAL + r + 1
                gnd._data = nd.array(g * scale, ctx=gnd.ctx).data
        tr.step(1)
    assert tr._spmd_active, "SPMD path disengaged on the dist job"
    u = tr._spmd_updater
    assert u.shard_factor() == nw * _N_LOCAL, u.shard_factor()

    # replicas bit-identical across the whole job
    h = hashlib.sha256()
    for p in params:
        for d in p.list_data():
            arr = np.ascontiguousarray(d.asnumpy())
            h.update(arr.tobytes())
    for p in params:
        r0 = p.list_data()[0].asnumpy()
        for d in p.list_data()[1:]:
            np.testing.assert_allclose(d.asnumpy(), r0, rtol=0, atol=0)
        gathered = dist.allgather_np(r0)
        for r in range(1, gathered.shape[0]):
            np.testing.assert_allclose(gathered[r], gathered[0],
                                       rtol=0, atol=0)

    stats = spmd_mod.compile_stats()
    print("SPMD_STATS " + json.dumps(
        {"rank": rank, "compiles": stats["count"],
         "cache_loads": stats["cache_loads"],
         "params_sha": h.hexdigest()}), flush=True)
    print(f"DIST_OK rank={rank}/{nw}", flush=True)


def mode_peerloss():
    """Failure detection: a worker whose peer died must abort loudly, not
    hang (ref role: ps-lite Van heartbeat timeout -> SURVEY.md §5)."""
    dist.init()
    rank = dist.rank()
    if rank == 1:
        # die without ever reaching the barrier
        print("DIST_OK rank=1 (exiting early, simulating peer death)",
              flush=True)
        os._exit(0)
    import time

    t0 = time.time()
    try:
        dist.barrier("peerloss", timeout=8)
    except mx.MXNetError as e:
        took = time.time() - t0
        assert "timed out" in str(e) and "unreachable" in str(e), e
        assert took < 60, took  # aborted promptly, did not deadlock
        print(f"DIST_OK rank=0 peer-loss detected in {took:.1f}s",
              flush=True)
        # normal exit would hang ~100s in the coordination service's
        # shutdown barrier (the peer can never arrive) -> fast abort
        dist.abort(code=0)
    raise AssertionError("barrier with a dead peer did not abort")


def hybrid_loss_and_data():
    """Shared fixture for the hybrid DCN+ICI lane: a deterministic tiny
    MLP (pure-jax params) + global batch, used by both the workers and
    the single-process oracle in test_dist.py."""
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    params = {
        "w1": jnp.asarray(rng.randn(4, 8).astype(np.float32) * 0.5),
        "b1": jnp.asarray(np.zeros(8, np.float32)),
        "w2": jnp.asarray(rng.randn(8, 3).astype(np.float32) * 0.5),
        "b2": jnp.asarray(np.zeros(3, np.float32)),
    }
    X = rng.randn(16, 4).astype(np.float32)
    y = rng.randint(0, 3, (16,)).astype(np.int32)

    def loss(p, xb, yb):
        h = jnp.tanh(xb @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(logp[jnp.arange(xb.shape[0]), yb])

    return params, X, y, loss


def mode_hybrid():
    """The pod topology in miniature (2 hosts x 4 chips): inside each
    process the gradient's batch reduction is an IN-GRAPH psum over a
    4-device dp mesh (the ICI stand-in, inserted by GSPMD); across the
    2 processes the per-process gradients ride the dist_sync KVStore
    (gloo = the DCN stand-in).  Rank 0 prints the final gradient so the
    parent test can assert equality with its single-process 8-device
    oracle."""
    import json

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import parallel

    dist.init()
    rank, nw = dist.rank(), dist.num_workers()
    params, X, y, loss = hybrid_loss_and_data()
    shard = X.shape[0] // nw
    Xs, ys = X[rank * shard:(rank + 1) * shard], \
        y[rank * shard:(rank + 1) * shard]

    # the ICI mesh must be built over THIS process's addressable chips
    # (jax.devices() is global after jax.distributed init — rank>0 would
    # otherwise get rank 0's devices and produce non-addressable grads)
    with parallel.make_mesh(dp=_N_LOCAL,
                            devices=jax.local_devices()) as mesh:
        xd = jax.device_put(jnp.asarray(Xs),
                            NamedSharding(mesh.mesh, P("dp")))
        yd = jax.device_put(jnp.asarray(ys),
                            NamedSharding(mesh.mesh, P("dp")))
        grads = jax.jit(jax.grad(loss))(params, xd, yd)

    # DCN hop: push per-process grads through dist_sync (sum across
    # workers), then renormalize the two half-batch means to the global
    # mean: sum_r mean_r / nw == mean over the global batch
    kv = mx.kv.create("dist_sync")
    out = {}
    for i, name in enumerate(sorted(grads)):
        g = mx.nd.array(np.asarray(grads[name]))
        kv.init(i, mx.nd.zeros(g.shape))
        kv.push(i, g)
        pulled = mx.nd.zeros(g.shape)
        kv.pull(i, out=pulled)
        out[name] = (pulled.asnumpy() / nw).tolist()

    # every worker must end with the identical global gradient
    flat = np.concatenate([np.asarray(v, np.float32).ravel()
                           for _, v in sorted(out.items())])
    gathered = dist.allgather_np(flat)
    for r in range(1, gathered.shape[0]):
        np.testing.assert_allclose(gathered[r], gathered[0],
                                   rtol=0, atol=0)
    if rank == 0:
        print("HYBRID_GRADS " + json.dumps(out), flush=True)
    print(f"DIST_OK rank={rank}/{nw}", flush=True)


if __name__ == "__main__":
    {"kvstore": mode_kvstore, "train": mode_train, "spmd": mode_spmd,
     "peerloss": mode_peerloss, "hybrid": mode_hybrid}[sys.argv[1]]()
