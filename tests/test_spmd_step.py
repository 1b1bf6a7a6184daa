"""Unified SPMD training step (ISSUE 9): one donated jit program over
the replica mesh — gradient reduce + ZeRO-sharded optimizer apply.

The SPMD path is a pure optimization over the per-replica fused path:
every test here pins it against that path (which PR 3 already pinned
against the eager loop), across every registered optimizer, plus the
ISSUE-9 acceptance assertions: per-device optimizer-state memory
shrinks ~1/N, exactly ONE executable per (mesh, layout), states
round-trip through save/load including onto a different mesh shape,
and the documented fallbacks hand states off losslessly.

The conftest pins an 8-virtual-device CPU backend, so the >=2-device
harness runs in-process.  MXNET_ZERO_MIN_SIZE is dropped to 1 in most
tests: the suite's parameters are tiny and would otherwise (correctly)
stay replicated.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import optimizer
from mxnet_tpu.gluon.parameter import Parameter
from mxnet_tpu.gluon.trainer import Trainer
from mxnet_tpu.ndarray.ndarray import NDArray, array as nd_array
from mxnet_tpu.optimizer import spmd as spmd_mod
from mxnet_tpu.telemetry import instruments as _ins

SHAPES = [(4, 3), (7,), (2, 3, 2), (1,)]

CASES = [
    ("sgd", {"momentum": 0.9, "wd": 0.01}),
    ("sgd", {}),
    ("nag", {"momentum": 0.9}),
    ("adam", {}),
    ("adagrad", {}),
    ("adadelta", {}),
    ("adamax", {}),
    ("nadam", {}),
    ("rmsprop", {}),
    ("rmsprop", {"centered": True}),
    ("ftrl", {}),
    ("signum", {"momentum": 0.9}),
    ("signsgd", {}),
    ("lamb", {}),
    ("test", {}),
]


@pytest.fixture(autouse=True)
def _small_zero_min(monkeypatch):
    """Test params are tiny; shard them anyway so the ZeRO layout is
    what every test exercises."""
    monkeypatch.setenv("MXNET_ZERO_MIN_SIZE", "1")


def _make_params(ctx=None, dtype="float32", seed=0, shapes=SHAPES):
    rng = np.random.RandomState(seed)
    params = []
    for i, shp in enumerate(shapes):
        p = Parameter(f"w{i}", shape=shp, dtype=dtype)
        p.initialize(ctx=ctx or [mx.cpu()])
        p.set_data(nd_array(rng.randn(*shp).astype("float32")))
        params.append(p)
    return params


def _set_grads(params, step, replica_scale=True):
    rng = np.random.RandomState(1000 + step)
    for p in params:
        g = rng.randn(*p.shape).astype("float32")
        for r, gnd in enumerate(p.list_grad()):
            scaled = g * (r + 1) if replica_scale else g
            gnd._data = nd_array(scaled, ctx=gnd.ctx,
                                 dtype=str(gnd.data.dtype)).data


def _assert_state_close(a, b, **tol):
    if a is None:
        assert b is None
        return
    if isinstance(a, (NDArray, np.ndarray)):
        an = a.asnumpy() if isinstance(a, NDArray) else a
        bn = b.asnumpy() if isinstance(b, NDArray) else b
        np.testing.assert_allclose(np.asarray(an, "f8"),
                                   np.asarray(bn, "f8"), **tol)
        return
    assert len(a) == len(b)
    for x, y in zip(a, b):
        _assert_state_close(x, y, **tol)


def _run_pair(name, kwargs, steps=3, ctx=None, shapes=SHAPES):
    """Two identical trainers, SPMD vs per-replica fused, fed identical
    per-replica gradients."""
    ctx = ctx or [mx.cpu(0), mx.cpu(1)]
    ps = _make_params(ctx=ctx, shapes=shapes)
    pf = _make_params(ctx=ctx, shapes=shapes)
    ts = Trainer(ps, name, dict(kwargs), kvstore="device", spmd=True)
    tf = Trainer(pf, name, dict(kwargs), kvstore="device",
                 fuse_step=True)
    for step in range(steps):
        _set_grads(ps, step)
        _set_grads(pf, step)
        ts.step(2)
        tf.step(2)
    return ts, tf, ps, pf


def test_every_registered_optimizer_has_a_spmd_case():
    from mxnet_tpu.optimizer.optimizer import _REG

    assert {n for n, _ in CASES} >= set(_REG.list())


@pytest.mark.parametrize("name,kwargs", CASES,
                         ids=[f"{n}-{i}" for i, (n, _)
                              in enumerate(CASES)])
def test_spmd_matches_per_replica_fused(name, kwargs):
    """Params AND states match the per-replica path's replica 0 (the
    documented trajectory for t-optimizers; exact for the rest), and
    the SPMD replicas stay bit-identical to each other."""
    ts, tf, ps, pf = _run_pair(name, kwargs)
    assert ts._spmd_active and ts._spmd_updater is not None
    for p_s, p_f in zip(ps, pf):
        np.testing.assert_allclose(p_s.list_data()[0].asnumpy(),
                                   p_f.list_data()[0].asnumpy(),
                                   rtol=2e-5, atol=1e-6)
        r0, r1 = (d.asnumpy() for d in p_s.list_data())
        np.testing.assert_allclose(r0, r1, rtol=0, atol=0)
    import pickle

    spmd_states = pickle.loads(
        ts._spmd_updater.get_states(dump_optimizer=False))
    for k, s_f in tf._updaters[0].states.items():
        _assert_state_close(spmd_states[k], s_f, rtol=2e-5, atol=1e-6)


def test_states_shard_one_over_n_per_device():
    """ISSUE 9 acceptance: optimizer-state memory per device shrinks
    ~1/N vs replicated."""
    n = 4
    ctx = [mx.cpu(i) for i in range(n)]
    shapes = [(64, 8), (128,), (16, 16)]
    ts, _, ps, _ = _run_pair("adam", {}, ctx=ctx, shapes=shapes)
    u = ts._spmd_updater
    assert u.shard_factor() == n
    total = per_dev = 0
    leaves = []

    def walk(t):
        if t is None:
            return
        if isinstance(t, tuple):
            for x in t:
                walk(x)
            return
        leaves.append(t)

    for tree in list(u._bstate.values()) + list(u._pstate.values()):
        walk(tree)
    assert leaves
    for leaf in leaves:
        total += leaf.size
        shard = leaf.sharding.shard_shape(leaf.shape)
        per_dev += int(np.prod(shard))
    assert per_dev == total // n  # exactly 1/N (padding already inside)


def test_one_executable_per_mesh_layout():
    """ISSUE 9 acceptance: two trainers with the same (mesh, layout)
    share ONE compiled step; a different layout compiles a second."""
    c0 = spmd_mod.compile_stats()["count"]
    _run_pair("sgd", {"momentum": 0.5}, steps=2)
    built = spmd_mod.compile_stats()["count"] - c0
    assert built == 1
    _run_pair("sgd", {"momentum": 0.5}, steps=2)  # same layout: cached
    assert spmd_mod.compile_stats()["count"] - c0 == built
    _run_pair("sgd", {"momentum": 0.5}, steps=2,
              ctx=[mx.cpu(i) for i in range(4)])  # new mesh: one more
    assert spmd_mod.compile_stats()["count"] - c0 == built + 1


def test_no_recompile_on_lr_change():
    ctx = [mx.cpu(0), mx.cpu(1)]
    ps = _make_params(ctx=ctx)
    ts = Trainer(ps, "sgd", {"momentum": 0.9}, kvstore="device",
                 spmd=True)
    _set_grads(ps, 0)
    ts.step(2)
    c0 = spmd_mod.compile_stats()["count"]
    before = ps[0].list_data()[0].asnumpy().copy()
    ts.set_learning_rate(0.5)
    _set_grads(ps, 1)
    ts.step(2)
    assert spmd_mod.compile_stats()["count"] == c0
    assert not np.allclose(before, ps[0].list_data()[0].asnumpy())


def test_save_load_roundtrip_onto_different_mesh(tmp_path):
    """Gather-on-save / reshard-on-load: resume a 4-replica SPMD run
    onto a 2-replica mesh and onto the per-replica fused path — both
    continue exactly."""
    ctx4 = [mx.cpu(i) for i in range(4)]
    ps = _make_params(ctx=ctx4)
    ts = Trainer(ps, "sgd", {"momentum": 0.9, "learning_rate": 0.1},
                 kvstore="device", spmd=True)
    for step in range(2):
        _set_grads(ps, step)
        ts.step(2)
    fname = str(tmp_path / "spmd.states")
    ts.save_states(fname)

    # resume on a 2-replica SPMD mesh
    ctx2 = [mx.cpu(0), mx.cpu(1)]
    p2 = _make_params(ctx=ctx2)
    for pa, pb in zip(p2, ps):
        pa.set_data(pb.list_data()[0])
    t2 = Trainer(p2, "sgd", {"momentum": 0.9, "learning_rate": 0.1},
                 kvstore="device", spmd=True)
    t2.load_states(fname)
    # resume on the per-replica fused path
    p3 = _make_params(ctx=ctx2)
    for pa, pb in zip(p3, ps):
        pa.set_data(pb.list_data()[0])
    t3 = Trainer(p3, "sgd", {"momentum": 0.9, "learning_rate": 0.1},
                 kvstore="device", fuse_step=True)
    t3.load_states(fname)

    for tr, pp in ((t2, p2), (t3, p3)):
        _set_grads(pp, 9)
        tr.step(2)
    for pa, pb in zip(p2, p3):
        np.testing.assert_allclose(pa.list_data()[0].asnumpy(),
                                   pb.list_data()[0].asnumpy(),
                                   rtol=2e-5, atol=1e-6)


def test_sparse_grad_disengages_and_hands_states_off():
    """A sparse gradient after the mesh engaged disengages the SPMD
    path permanently, handing the accumulated (sharded) momentum off
    to the per-replica updaters — the whole run matches a pure
    per-replica twin."""
    from mxnet_tpu.ndarray import sparse as sp

    results = {}
    for use_spmd in (True, False):
        params = _make_params(seed=3)
        emb = Parameter("emb", shape=(6, 3))
        emb.initialize(ctx=[mx.cpu()])
        emb.set_data(nd_array(
            np.random.RandomState(5).randn(6, 3).astype("f4")))
        trainer = Trainer(params + [emb], "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          kvstore=None, spmd=use_spmd,
                          fuse_step=not use_spmd)
        for step in range(4):
            _set_grads(params, step)
            if step == 2:  # sparse grad after 2 SPMD steps
                emb.data()._ag_grad = sp.row_sparse_array(
                    (np.ones((2, 3), "f4"), [1, 4]), shape=(6, 3))
            else:
                emb.data()._ag_grad = nd_array(
                    np.zeros((6, 3), "f4"))
            trainer.step(2)
        if use_spmd:
            assert trainer._spmd_active is False  # disengaged
            assert trainer._spmd_updater is None
            assert trainer._updaters[0].states  # states handed off
        results[use_spmd] = [p.data().asnumpy()
                             for p in params + [emb]]
    for ws, we in zip(results[True], results[False]):
        np.testing.assert_allclose(ws, we, rtol=2e-5, atol=1e-6)


def test_manual_update_flow_hands_states_off():
    """The documented manual flow — allreduce_grads() + update() —
    after the mesh engaged must NOT run the per-replica updaters on
    fresh zero states: update() disengages first, handing the sharded
    momentum off, so the whole run matches a per-replica twin."""
    results = {}
    for use_spmd in (True, False):
        ps = _make_params(ctx=[mx.cpu(0), mx.cpu(1)], seed=4)
        t = Trainer(ps, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
                    kvstore="device", spmd=use_spmd,
                    fuse_step=not use_spmd)
        for step in range(2):
            _set_grads(ps, step)
            t.step(2)
        if use_spmd:
            assert t._spmd_updater is not None  # engaged, states live
        _set_grads(ps, 2)
        t.allreduce_grads()
        t.update(2)
        if use_spmd:
            assert t._spmd_active is False
            assert t._spmd_updater is None
            assert t._updaters[0].states  # momentum handed off
        results[use_spmd] = [p.list_data()[0].asnumpy() for p in ps]
    for ws, we in zip(results[True], results[False]):
        np.testing.assert_allclose(ws, we, rtol=2e-5, atol=1e-6)


def test_kvstore_spmd_reduces_off_device_buffer(monkeypatch):
    """A gradient buffer that drifted off its ctx device reduces fine
    under MXNET_SPMD=1 (same device_put normalization as the classic
    bucket path) instead of crashing the mesh-array assembly."""
    import jax as _jax

    from mxnet_tpu import kvstore as kvs

    rng = np.random.RandomState(9)
    raw = [rng.randn(4, 3).astype("f4") for _ in range(2)]
    expected = raw[0] + raw[1]
    monkeypatch.setenv("MXNET_SPMD", "1")
    kv = kvs.create("device")
    reps = [nd_array(v, ctx=mx.cpu(r)) for r, v in enumerate(raw)]
    kv.init(0, reps[0])
    # simulate drift: replica 0's buffer lives on replica 1's device
    reps[0]._data = _jax.device_put(reps[0].data,
                                    mx.cpu(1).jax_device)
    kv.pushpull_fused([0], [reps], out=[reps])
    for r in reps:
        np.testing.assert_allclose(r.asnumpy(), expected, rtol=1e-6)


def test_spmd_false_env_off_keeps_per_replica_path(monkeypatch):
    monkeypatch.delenv("MXNET_SPMD", raising=False)
    ctx = [mx.cpu(0), mx.cpu(1)]
    ps = _make_params(ctx=ctx)
    t = Trainer(ps, "sgd", {}, kvstore="device")
    _set_grads(ps, 0)
    t.step(2)
    assert t._spmd_active is False
    assert t._spmd_updater is None


def test_spmd_env_engages(monkeypatch):
    monkeypatch.setenv("MXNET_SPMD", "1")
    ctx = [mx.cpu(0), mx.cpu(1)]
    ps = _make_params(ctx=ctx)
    t = Trainer(ps, "sgd", {}, kvstore="device")
    _set_grads(ps, 0)
    t.step(2)
    assert t._spmd_active is True
    assert t._spmd_updater is not None


def test_spmd_true_with_compression_warns_and_falls_back():
    ctx = [mx.cpu(0), mx.cpu(1)]
    ps = _make_params(ctx=ctx)
    with pytest.warns(UserWarning, match="spmd=True"):
        t = Trainer(ps, "sgd", {}, kvstore="device", spmd=True,
                    compression_params={"type": "2bit"})
        _set_grads(ps, 0)
        t.step(2)
    assert t._spmd_active is False


def test_zero_states_off_keeps_states_replicated(monkeypatch):
    monkeypatch.setenv("MXNET_ZERO_STATES", "0")
    ts, tf, ps, pf = _run_pair("sgd", {"momentum": 0.9})
    u = ts._spmd_updater
    assert u.shard_factor() == 1
    for p_s, p_f in zip(ps, pf):
        np.testing.assert_allclose(p_s.list_data()[0].asnumpy(),
                                   p_f.list_data()[0].asnumpy(),
                                   rtol=2e-5, atol=1e-6)


def test_zero_min_size_keeps_small_params_replicated(monkeypatch):
    """Params below MXNET_ZERO_MIN_SIZE skip the flat-shard layout
    (collective latency would beat the memory win) — the plan puts
    them in the small group."""
    monkeypatch.setenv("MXNET_ZERO_MIN_SIZE", "64")
    shapes = [(64, 8), (7,)]  # 512 sharded, 7 replicated
    ts, tf, ps, pf = _run_pair("sgd", {"momentum": 0.9}, shapes=shapes)
    plan = ts._spmd_updater._plan
    assert len(plan.buckets) == 1 and plan.buckets[0].pos == (0,)
    assert plan.smalls and plan.smalls[0].pos == (1,)
    for p_s, p_f in zip(ps, pf):
        np.testing.assert_allclose(p_s.list_data()[0].asnumpy(),
                                   p_f.list_data()[0].asnumpy(),
                                   rtol=2e-5, atol=1e-6)


def test_lamb_takes_per_param_singles():
    """Norm-based optimizers cannot concatenate (per-tensor trust
    ratio) — the plan routes them through singles, still sharded."""
    ts, tf, ps, pf = _run_pair("lamb", {})
    plan = ts._spmd_updater._plan
    assert not plan.buckets and len(plan.singles) == len(SHAPES)
    for p_s, p_f in zip(ps, pf):
        np.testing.assert_allclose(p_s.list_data()[0].asnumpy(),
                                   p_f.list_data()[0].asnumpy(),
                                   rtol=2e-5, atol=1e-6)


def test_half_precision_t_hyper_disengages_cleanly():
    """Adamax (t-hyper) on bf16 weights without multi_precision cannot
    take the mesh program — the trainer falls back without touching
    state."""
    ctx = [mx.cpu(0), mx.cpu(1)]
    ps = _make_params(ctx=ctx, dtype="bfloat16")
    pf = _make_params(ctx=ctx, dtype="bfloat16")
    ts = Trainer(ps, "adamax", {}, kvstore="device", spmd=True)
    tf = Trainer(pf, "adamax", {}, kvstore="device", fuse_step=False)
    for step in range(2):
        _set_grads(ps, step)
        _set_grads(pf, step)
        ts.step(2)
        tf.step(2)
    assert ts._spmd_active is False  # disengaged on first step
    for p_s, p_f in zip(ps, pf):
        np.testing.assert_allclose(
            p_s.list_data()[0].asnumpy().astype("f4"),
            p_f.list_data()[0].asnumpy().astype("f4"),
            rtol=2e-2, atol=1e-2)


def test_multi_precision_bf16_master_weights():
    ctx = [mx.cpu(0), mx.cpu(1)]
    ps = _make_params(ctx=ctx, dtype="bfloat16")
    pf = _make_params(ctx=ctx, dtype="bfloat16")
    ts = Trainer(ps, "sgd", {"momentum": 0.9, "multi_precision": True},
                 kvstore="device", spmd=True)
    tf = Trainer(pf, "sgd", {"momentum": 0.9, "multi_precision": True},
                 kvstore="device", fuse_step=True)
    for step in range(3):
        _set_grads(ps, step)
        _set_grads(pf, step)
        ts.step(2)
        tf.step(2)
    assert ts._spmd_active is True
    for p_s, p_f in zip(ps, pf):
        np.testing.assert_allclose(
            p_s.list_data()[0].asnumpy().astype("f4"),
            p_f.list_data()[0].asnumpy().astype("f4"),
            rtol=2e-2, atol=1e-2)


def test_kvstore_pushpull_fused_spmd_parity(monkeypatch):
    """MXNET_SPMD=1 routes pushpull_fused's buckets through one mesh
    program per bucket — same values, store still published."""
    from mxnet_tpu import kvstore as kvs

    rng = np.random.RandomState(3)
    keys = [0, 1, 2]
    shapes = [(4, 3), (16,), (2, 2)]

    def build():
        kv = kvs.create("device")
        vals = []
        for k, s in zip(keys, shapes):
            reps = [nd_array(rng.randn(*s).astype("f4"), ctx=mx.cpu(r))
                    for r in range(2)]
            kv.init(k, reps[0])
            vals.append(reps)
        return kv, vals

    rng = np.random.RandomState(3)
    monkeypatch.setenv("MXNET_SPMD", "0")
    kv_a, vals_a = build()
    rng = np.random.RandomState(3)
    monkeypatch.setenv("MXNET_SPMD", "1")
    kv_b, vals_b = build()
    kv_a.pushpull_fused(keys, vals_a, out=vals_a)
    kv_b.pushpull_fused(keys, vals_b, out=vals_b)
    for ra, rb in zip(vals_a, vals_b):
        for a, b in zip(ra, rb):
            np.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                       rtol=1e-6)
    for k in keys:
        np.testing.assert_allclose(kv_a._store[k].asnumpy(),
                                   kv_b._store[k].asnumpy(), rtol=1e-6)


def test_phased_spans_and_collective_bytes():
    """Tracing on: the step runs the phased variant with
    reduce-scatter/shard-update/all-gather spans, layout gauges, and
    the collective-bytes counters move."""
    from mxnet_tpu.telemetry import tracing

    ctx = [mx.cpu(0), mx.cpu(1)]
    ps = _make_params(ctx=ctx)
    pf = _make_params(ctx=ctx)
    ts = Trainer(ps, "sgd", {"momentum": 0.9}, kvstore="device",
                 spmd=True)
    tf = Trainer(pf, "sgd", {"momentum": 0.9}, kvstore="device",
                 spmd=True)
    _set_grads(ps, 0)
    ts.step(2)  # untraced warmup engages the mesh
    tracing.enable()
    try:
        b0 = _ins.collective_bytes_total("reduce-scatter", "dp").value
        s0 = _ins.training_phase_seconds("shard-update").count
        for step in range(2):
            _set_grads(ps, step + 1)
            ts.step(2)
        assert _ins.collective_bytes_total(
            "reduce-scatter", "dp").value > b0
        assert _ins.training_phase_seconds("shard-update").count >= s0 + 2
        assert _ins.step_layout_axis_size("dp").value == 2
        assert _ins.step_state_shard_factor().value == 2
        # phased result == fused-program result (same stages, split)
        _set_grads(pf, 0)
        tf.step(2)
    finally:
        tracing.disable()
    for step in range(2):
        _set_grads(pf, step + 1)
        tf.step(2)
    for p_s, p_f in zip(ps, pf):
        np.testing.assert_allclose(p_s.list_data()[0].asnumpy(),
                                   p_f.list_data()[0].asnumpy(),
                                   rtol=2e-5, atol=1e-6)


@pytest.mark.slow
def test_cross_process_mesh_warm_starts_from_shared_cache(tmp_path):
    """ISSUE 9 acceptance, cross-process half: a 2-process job runs ONE
    mesh program spanning both workers' devices (states sharded 4-way,
    replicas bit-identical job-wide), and a SECOND job over the same
    shared compile-cache dir warm-starts the executable from disk —
    zero XLA builds (PR-7 store)."""
    import json as _json
    import os
    import socket
    import subprocess
    import sys

    worker = os.path.join(os.path.dirname(__file__), "dist_worker.py")

    def spawn(cache_dir):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = str(s.getsockname()[1])
        procs = []
        for i in range(2):
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env.pop("XLA_FLAGS", None)
            env["MXNET_COMPILE_CACHE_DIR"] = cache_dir
            env.update({"DMLC_ROLE": "worker",
                        "DMLC_PS_ROOT_URI": "127.0.0.1",
                        "DMLC_PS_ROOT_PORT": port,
                        "DMLC_NUM_WORKER": "2",
                        "DMLC_WORKER_ID": str(i)})
            procs.append(subprocess.Popen(
                [sys.executable, worker, "spmd"], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        stats = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=300)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            assert p.returncode == 0, out[-2000:]
            assert "DIST_OK" in out
            line = [ln for ln in out.splitlines()
                    if ln.startswith("SPMD_STATS ")][0]
            stats.append(_json.loads(line.split(" ", 1)[1]))
        return stats

    cache = str(tmp_path / "cc")
    cold = spawn(cache)
    assert {s["params_sha"] for s in cold} == {cold[0]["params_sha"]}
    for s in cold:  # exactly ONE executable built per (mesh, layout)
        assert s["compiles"] == 1, s
    warm = spawn(cache)
    for s in warm:  # fresh processes warm-start from the shared store
        assert s["compiles"] == 0, s
        assert s["cache_loads"] >= 1, s
    assert warm[0]["params_sha"] == cold[0]["params_sha"]


def test_single_replica_single_device_degenerate_case():
    """dp=1: same code path, no collectives, parity with fused."""
    ctx = [mx.cpu(0)]
    ts, tf, ps, pf = _run_pair("adam", {}, ctx=ctx)
    assert ts._spmd_active
    for p_s, p_f in zip(ps, pf):
        np.testing.assert_allclose(p_s.list_data()[0].asnumpy(),
                                   p_f.list_data()[0].asnumpy(),
                                   rtol=2e-5, atol=1e-6)
