"""Tests for mxnet_tpu.parallel: mesh, sharding rules, SPMD training,
ring attention, pipeline parallelism — on the 8-virtual-device CPU backend
(SURVEY.md §4: multi-device behaviour simulated via XLA host devices)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.gluon import nn, loss as gloss


def test_mesh_basics():
    mesh = parallel.make_mesh(dp=4, tp=2)
    assert mesh.size() == 8
    assert mesh.size("dp") == 4 and mesh.size("tp") == 2
    assert "dp" in mesh and "pp" not in mesh
    with mesh:
        assert parallel.current_mesh() is mesh
    assert parallel.current_mesh() is None


def test_mesh_default_all_devices():
    mesh = parallel.make_mesh()
    assert mesh.size("dp") == jax.device_count()


def test_sharding_rules_tp_and_fallback():
    mesh = parallel.make_mesh(dp=2, tp=2)
    rules = parallel.DEFAULT_RULES
    spec = rules.spec_for("bert0_attn_qkv_weight", (192, 64), mesh)
    assert spec == P("tp", None)
    # row-parallel out projection
    spec = rules.spec_for("bert0_attn_out_proj_weight", (64, 64), mesh)
    assert spec == P(None, "tp")
    # unmatched -> replicated (no fsdp axis)
    assert rules.spec_for("conv0_weight", (64, 3, 3, 3), mesh) == P()
    # non-divisible dims fall through to replication
    assert rules.spec_for("q_proj_weight", (63, 64), mesh) == P()


def test_sharding_rules_fsdp():
    mesh = parallel.make_mesh(fsdp=8)
    rules = parallel.ShardingRules()
    spec = rules.spec_for("dense0_weight", (256, 128), mesh)
    assert spec == P("fsdp", None)
    # tiny params stay replicated
    assert rules.spec_for("dense0_bias", (128,), mesh) == P()


def test_shard_batch_spec():
    mesh = parallel.make_mesh(dp=2, sp=4)
    sh = parallel.shard_batch(mesh, extra_dims=2, seq_axis=1)
    assert sh.spec == P(("dp",), "sp", None)


def _make_mlp():
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=16))
    net.add(nn.Dense(10, in_units=32))
    net.initialize()
    return net


def test_spmd_trainer_dp_loss_decreases():
    mesh = parallel.make_mesh(dp=8)
    with mesh:
        net = _make_mlp()
        trainer = parallel.SPMDTrainer(
            net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.5})
        rng = np.random.RandomState(0)
        x = rng.randn(64, 16).astype(np.float32)
        y = (rng.rand(64) * 10).astype(np.int32)
        losses = [float(trainer.step(x, y).asnumpy()) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.7, losses[::10]


from test_fused_step import CASES as OPTIMIZER_CASES


@pytest.mark.parametrize("name,kwargs", OPTIMIZER_CASES,
                         ids=[f"{n}-{i}" for i, (n, _)
                              in enumerate(OPTIMIZER_CASES)])
def test_spmd_trainer_matches_local_training(name, kwargs):
    """DP-SPMD must compute the same math as single-device Trainer+KVStore
    (the check_consistency pattern, SURVEY.md §4), for every registered
    optimizer: the step program's update is Optimizer.fused_apply, the
    reference is the eager update()."""
    rng = np.random.RandomState(1)
    x = rng.randn(32, 16).astype(np.float32)
    y = (rng.rand(32) * 10).astype(np.int32)
    opt_kw = dict(kwargs, learning_rate=0.1)

    def run_local():
        np.random.seed(7)
        mx.random.seed(7)
        net = _make_mlp()
        tr = mx.gluon.Trainer(net.collect_params(), name, dict(opt_kw))
        lfn = gloss.SoftmaxCrossEntropyLoss()
        for _ in range(5):
            with mx.autograd.record():
                l = lfn(net(mx.nd.array(x)), mx.nd.array(y)).mean()
            l.backward()
            tr.step(1)  # loss is already a mean
        return {n: p.data().asnumpy()
                for n, p in net.collect_params().items()}

    def run_spmd():
        np.random.seed(7)
        mx.random.seed(7)
        mesh = parallel.make_mesh(dp=4)
        with mesh:
            net = _make_mlp()
            tr = parallel.SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                                      name, dict(opt_kw))
            for _ in range(5):
                tr.step(x, y)
            tr.sync_to_block()
            return {n: p.data().asnumpy()
                    for n, p in net.collect_params().items()}

    local, spmd = run_local(), run_spmd()
    # the two nets differ in their name-scope counters: compare in
    # collect_params() order (sorting the names breaks when the counters
    # straddle a digit boundary, dense9_ vs dense10_)
    for a, b in zip(local.values(), spmd.values()):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_spmd_trainer_tp_mesh():
    """Params matching tp rules actually shard; training still works."""
    mesh = parallel.make_mesh(dp=2, tp=4)
    with mesh:
        net = nn.HybridSequential(prefix="tpnet_")
        with net.name_scope():
            net.add(nn.Dense(64, activation="relu", in_units=16,
                             prefix="fc1_"))
            net.add(nn.Dense(10, in_units=64, prefix="head_"))
        net.initialize()
        trainer = parallel.SPMDTrainer(
            net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.2})
        w1 = trainer.params["tpnet_fc1_weight"]
        assert w1.sharding.spec == P("tp", None)
        rng = np.random.RandomState(0)
        x = rng.randn(16, 16).astype(np.float32)
        y = (rng.rand(16) * 10).astype(np.int32)
        l0 = float(trainer.step(x, y).asnumpy())
        for _ in range(20):
            l = float(trainer.step(x, y).asnumpy())
        assert l < l0


def test_spmd_trainer_adam_and_bn():
    """Adam functional path + BatchNorm aux-state updates under SPMD."""
    mesh = parallel.make_mesh(dp=8)
    with mesh:
        net = nn.HybridSequential()
        net.add(nn.Dense(32, in_units=16))
        net.add(nn.BatchNorm(in_channels=32))
        net.add(nn.Activation("relu"))
        net.add(nn.Dense(10, in_units=32))
        net.initialize()
        trainer = parallel.SPMDTrainer(
            net, gloss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 1e-2})
        rng = np.random.RandomState(0)
        x = rng.randn(64, 16).astype(np.float32) * 3 + 1
        y = (rng.rand(64) * 10).astype(np.int32)
        mean_before = net[1].running_mean.data().asnumpy().copy()
        losses = [float(trainer.step(x, y).asnumpy()) for _ in range(20)]
        mean_after = net[1].running_mean.data().asnumpy()
    assert losses[-1] < losses[0]
    assert not np.allclose(mean_before, mean_after)
    # stats must ACCUMULATE across steps (EMA toward the batch stats), not
    # re-apply one step from init: after N steps with near-constant input
    # distribution, |mean| magnitude ≈ (1 - momentum^N) * batch_mean ≫ one
    # step's (1 - momentum) * batch_mean
    one_step_norm = 0.1 * np.abs(mean_after).max() / max(
        1.0 - 0.9 ** 20, 1e-9)
    assert np.abs(mean_after).max() > 3 * one_step_norm


def test_ring_attention_matches_dense():
    mesh = parallel.make_mesh(sp=8)
    rng = np.random.RandomState(0)
    B, H, L, D = 2, 4, 64, 16
    q = rng.randn(B, H, L, D).astype(np.float32)
    k = rng.randn(B, H, L, D).astype(np.float32)
    v = rng.randn(B, H, L, D).astype(np.float32)
    ref = parallel.ring.local_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    with mesh:
        out = parallel.ring.ring_attention_sharded(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_causal():
    mesh = parallel.make_mesh(sp=4)
    rng = np.random.RandomState(1)
    B, H, L, D = 1, 2, 32, 8
    q = rng.randn(B, H, L, D).astype(np.float32)
    k = rng.randn(B, H, L, D).astype(np.float32)
    v = rng.randn(B, H, L, D).astype(np.float32)
    ref = parallel.ring.local_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True)
    with mesh:
        out = parallel.ring.ring_attention_sharded(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_matches_sequential():
    mesh = parallel.make_mesh(pp=4)
    rng = np.random.RandomState(2)
    S, B, Dm = 4, 16, 32
    ws = [rng.randn(Dm, Dm).astype(np.float32) * 0.1 for _ in range(S)]
    stacked = {"w": jnp.stack([jnp.asarray(w) for w in ws])}

    def stage(params, x):
        return jnp.tanh(x @ params["w"])

    x = rng.randn(B, Dm).astype(np.float32)
    ref = jnp.asarray(x)
    for w in ws:
        ref = jnp.tanh(ref @ jnp.asarray(w))
    with mesh:
        out = parallel.pipeline.pipeline_apply(
            stage, stacked, jnp.asarray(x), n_microbatch=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_dist_single_process_noops():
    parallel.dist.init()
    assert parallel.dist.rank() == 0
    assert parallel.dist.num_workers() == 1
    parallel.dist.barrier()
    x = mx.nd.array(np.ones((3,), np.float32))
    out = parallel.dist.allreduce_nd(x)
    np.testing.assert_allclose(out.asnumpy(), np.ones(3))


def test_spmd_trainer_bf16_master_weights():
    """bf16 params carry an fp32 master weight in the optimizer state
    (reference mp_sgd_* weight32 semantics): updates far below one bf16
    ulp must still accumulate instead of rounding away."""
    mesh = parallel.make_mesh(dp=1)
    with mesh:
        net = mx.gluon.nn.Dense(1, use_bias=False)
        net.initialize(mx.initializer.One(), ctx=mx.cpu())
        net(mx.nd.ones((1, 4)))
        net.cast("bfloat16")
        # plain SGD, no momentum: each update is lr * grad
        opt = mx.optimizer.SGD(learning_rate=1e-4, multi_precision=True)
        trainer = parallel.SPMDTrainer(
            net, lambda out, y: ((out - y) ** 2).mean(), opt,
            n_labels=1)
        name = [n for n, _ in trainer._plist][0]
        assert len(trainer.opt_state[name]) == 1    # the master, alone
        x = np.ones((8, 4), "bfloat16")
        y = np.zeros((8, 1), "bfloat16")
        for _ in range(40):
            trainer.step(x, y)
        master = np.asarray(trainer.opt_state[name][-1], dtype="float32")
        # grad = 2*(w.x) * x = 8 per element initially; 40 steps of ~8e-4
        # updates: far below bf16 ulp (0.0078 at 1.0) per step, but the
        # master must have accumulated a visible decrease
        assert master.max() < 1.0 - 1e-3, master
        assert master.dtype == np.float32


def _bf16_net():
    # fixed prefix: checkpoint keys must not depend on how many nets
    # were created earlier in the process
    np.random.seed(3)
    mx.random.seed(3)
    net = nn.HybridSequential(prefix="bf16net_")
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu", in_units=4),
                nn.Dense(1, in_units=8))
    net.initialize(ctx=mx.cpu())
    net.cast("bfloat16")
    return net


def _mse(out, y):
    return ((out - y) ** 2).mean()


def _state_dtypes(trainer):
    return {n: (str(trainer.params[n].dtype),
                tuple(str(s.dtype) for s in trainer.opt_state[n]))
            for n in trainer.opt_state}


@pytest.mark.parametrize("base,kwargs,n_state", [
    ("Adam", {}, 2), ("SGD", {"momentum": 0.9}, 1)],
    ids=["adam", "sgd_momentum"])
def test_spmd_trainer_bf16_hyper_scalars_stay_float32(base, kwargs, n_state):
    """bf16 weights without a master: lr (and Adam's bias correction in
    it) reaches fused_apply as a float32 scalar - cast to bfloat16 it
    would keep three digits - while wd and rescale_grad are constants
    of the program; weights and states keep their dtypes from step to
    step, so the one step program is built once."""
    from mxnet_tpu.parallel.spmd import step_compile_stats

    seen = []

    class Recording(getattr(mx.optimizer, base)):
        def fused_apply(self, weight, grad, state, hyper):
            seen.append((str(weight.dtype),
                         {k: str(getattr(v, "dtype", type(v).__name__))
                          for k, v in hyper.items()}))
            return super().fused_apply(weight, grad, state, hyper)

    x = np.ones((8, 4), "bfloat16")
    y = np.zeros((8, 1), "bfloat16")
    with parallel.make_mesh(dp=1):
        trainer = parallel.SPMDTrainer(
            _bf16_net(), _mse, Recording(learning_rate=1e-2, **kwargs))
        first = _state_dtypes(trainer)
        builds = step_compile_stats()["count"]
        losses = [float(trainer.step(x, y).asnumpy()) for _ in range(4)]
    assert step_compile_stats()["count"] == builds + 1
    assert losses[-1] < losses[0]
    assert _state_dtypes(trainer) == first
    assert all(v == ("bfloat16", ("bfloat16",) * n_state)
               for v in first.values()), first
    assert len(seen) == len(first)          # traced once, a call a weight
    for weight_dtype, hyper in seen:
        assert weight_dtype == "bfloat16"
        assert hyper == {"lr": "float32", "wd": "float",
                         "rescale_grad": "float"}


def test_spmd_trainer_bf16_master_checkpoint_round_trip(tmp_path):
    """multi_precision=True: float32 moments, the float32 master LAST in
    each parameter's flat state tuple, and save_checkpoint /
    load_checkpoint carry it (the resumed trainer's next loss is the
    uninterrupted one's)."""
    pytest.importorskip("orbax.checkpoint")
    x = np.ones((8, 4), "bfloat16")
    y = np.zeros((8, 1), "bfloat16")

    def make():
        return parallel.SPMDTrainer(
            _bf16_net(), _mse,
            mx.optimizer.Adam(learning_rate=1e-3, multi_precision=True))

    with parallel.make_mesh(dp=1):
        trainer = make()
        for _ in range(3):
            trainer.step(x, y)
        for n, state in trainer.opt_state.items():
            assert [str(s.dtype) for s in state] == ["float32"] * 3
            master = np.asarray(state[-1])
            np.testing.assert_array_equal(
                master.astype("bfloat16"), np.asarray(trainer.params[n]))
            # the master holds what bfloat16 cannot
            assert np.any(master != master.astype("bfloat16").astype("f4"))
        trainer.save_checkpoint(str(tmp_path / "ckpt"))
        saved = {n: [np.asarray(s) for s in state]
                 for n, state in trainer.opt_state.items()}
        resumed = make()
        resumed.load_checkpoint(str(tmp_path / "ckpt"))
        for n, state in saved.items():
            for a, b in zip(state, resumed.opt_state[n]):
                assert b.dtype == a.dtype
                np.testing.assert_array_equal(a, np.asarray(b))
        assert float(resumed.step(x, y).asnumpy()) == \
            float(trainer.step(x, y).asnumpy())


def test_spmd_trainer_retrace_on_shape_change():
    """Mid-training input-shape change retraces the step; BN aux stats
    must keep flowing correctly (aux is keyed by name in the traced
    outputs, not by a trace-order side channel)."""
    mesh = parallel.make_mesh(dp=1)
    with mesh:
        net = mx.gluon.nn.HybridSequential()
        net.add(mx.gluon.nn.Dense(8), mx.gluon.nn.BatchNorm(),
                mx.gluon.nn.Dense(4))
        net.initialize(ctx=mx.cpu())
        net(mx.nd.zeros((2, 6)))
        trainer = parallel.SPMDTrainer(
            net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1})
        bn = [b for b in net._children.values()
              if isinstance(b, mx.gluon.nn.BatchNorm)][0]
        rng = np.random.RandomState(0)
        for bs in (16, 16, 24, 16, 32):  # shape changes force retraces
            x = (rng.randn(bs, 6) * 2 + 1).astype("f4")
            y = (rng.rand(bs) * 4).astype(np.int32)
            loss = trainer.step(x, y)
            assert np.isfinite(float(loss.asnumpy()))
        # moving stats moved off their init and stayed finite
        mm = bn.running_mean.data().asnumpy()
        mv = bn.running_var.data().asnumpy()
        assert np.isfinite(mm).all() and np.isfinite(mv).all()
        assert not np.allclose(mm, 0.0)
        assert not np.allclose(mv, 1.0)


def test_collective_watchdog():
    """_run_with_watchdog: passes values/errors through, and converts a
    never-completing collective into a loud MXNetError."""
    import os
    import time

    from mxnet_tpu.parallel import dist

    try:
        assert dist._run_with_watchdog(lambda: 42, timeout=5,
                                       what="x") == 42
        with pytest.raises(ValueError):
            dist._run_with_watchdog(lambda: (_ for _ in ()).throw(
                ValueError("boom")), timeout=5, what="x")
        with pytest.raises(mx.MXNetError, match="timed out.*unreachable"):
            dist._run_with_watchdog(lambda: time.sleep(30), timeout=0.2,
                                    what="hung")
        # the timed-out collective may complete later on its stuck
        # thread: all further collectives must refuse (sequence desync)
        with pytest.raises(mx.MXNetError, match="refused"):
            dist._run_with_watchdog(lambda: 1, timeout=5, what="next")
        dist._POISONED = None
        # env-var route (MXNET_KVSTORE_TIMEOUT)
        os.environ[dist._TIMEOUT_ENV] = "0.2"
        with pytest.raises(mx.MXNetError, match="timed out"):
            dist._run_with_watchdog(lambda: time.sleep(30), timeout=None,
                                    what="hung")
        os.environ[dist._TIMEOUT_ENV] = "5m"
        with pytest.raises(mx.MXNetError, match="MXNET_KVSTORE_TIMEOUT"):
            dist._collective_timeout(None)
    finally:
        dist._POISONED = None
        os.environ.pop(dist._TIMEOUT_ENV, None)


def test_dist_async_emulation_pin():
    """dist_async is served by the dist_sync path (documented emulation:
    synchronous application is a legal schedule of async). Pin the
    observable semantics so a behavioral change is caught — and that
    creation warns ONCE that the staleness semantics changed (round-4
    verdict item #7)."""
    import warnings

    from mxnet_tpu import kvstore as kvs

    kvs._ASYNC_WARNED[0] = False
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        kv = mx.kvstore.create("dist_async")
        again = mx.kvstore.create("dist_async")
    msgs = [str(w.message) for w in rec
            if "emulated as 'dist_sync'" in str(w.message)]
    assert len(msgs) == 1, msgs  # loud, but once per process
    del again
    assert kv.type == "dist_async"
    assert kv.num_workers == 1  # single-process here
    kv.init(0, mx.nd.zeros((3,)))
    kv.push(0, mx.nd.array(np.array([1.0, 2.0, 3.0], "f4")))
    out = mx.nd.zeros((3,))
    kv.pull(0, out)
    # same-result-as-sync pin: push overwrites the stored value
    np.testing.assert_array_equal(out.asnumpy(), [1.0, 2.0, 3.0])
    sync = mx.kvstore.create("dist_sync")
    sync.init(0, mx.nd.zeros((3,)))
    sync.push(0, mx.nd.array(np.array([1.0, 2.0, 3.0], "f4")))
    out2 = mx.nd.zeros((3,))
    sync.pull(0, out2)
    np.testing.assert_array_equal(out.asnumpy(), out2.asnumpy())


def test_spmd_trainer_remat_segments():
    """SPMDTrainer(remat=True): gradients identical to the plain step,
    and the compiled step really contains remat segments."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu import parallel

    def build(remat):
        np.random.seed(0)
        net = nn.Sequential()
        net.add(nn.Dense(8, activation="relu", in_units=6))
        net.add(nn.Dense(4, in_units=8))
        net.initialize(mx.initializer.Xavier())
        mesh = parallel.make_mesh(dp=2)
        return parallel.SPMDTrainer(
            net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=mesh, remat=remat), net

    rng = np.random.RandomState(0)
    X = rng.randn(8, 6).astype("f4")
    y = (rng.rand(8) * 4).astype(np.int32)
    losses = []
    jaxprs = []
    for remat in (False, True):
        tr, net = build(remat)
        for _ in range(3):
            l = tr.step(X, y)
        losses.append(float(l.asnumpy()))
        # the compiled step must literally contain remat segments when on
        import jax as _jax

        pure = tr._build_pure()
        key = _jax.numpy.zeros((2,), _jax.numpy.uint32)
        jaxprs.append(str(_jax.make_jaxpr(pure)(
            {n: v for n, v in tr.params.items()}, tr.opt_state,
            (_jax.numpy.asarray(X),), (_jax.numpy.asarray(y),), key,
            _jax.numpy.float32(0.1), _jax.numpy.int32(1))))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
    assert "remat" not in jaxprs[0] and "checkpoint" not in jaxprs[0]
    assert "remat" in jaxprs[1] or "checkpoint" in jaxprs[1]


def test_spmd_batchnorm_is_sync_bn():
    """Under dp-sharded SPMD, BatchNorm statistics are computed over the
    GLOBAL batch (GSPMD reduces over the full logical array), i.e.
    SyncBatchNorm semantics come for free — pin it: per-shard stats
    would differ from the global-batch oracle."""
    from mxnet_tpu.gluon import nn, loss as gloss

    mesh = parallel.make_mesh(dp=8)
    rng = np.random.RandomState(0)
    # make shards statistically DIFFERENT so per-shard stats would be
    # visibly wrong: sample i's scale grows with its index
    x = (rng.randn(64, 16) * np.linspace(0.5, 4.0, 64)[:, None]) \
        .astype(np.float32)
    y = (rng.rand(64) * 4).astype(np.int32)
    with mesh:
        net = nn.HybridSequential()
        net.add(nn.Dense(8, in_units=16))
        net.add(nn.BatchNorm(in_channels=8, momentum=0.0))  # stats=batch
        net.add(nn.Dense(4, in_units=8))
        net.initialize(mx.initializer.Xavier())
        tr = parallel.SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                                  "sgd", {"learning_rate": 0.0})
        tr.step(x, y)
        tr.sync_to_block()
        got_mean = net[1].running_mean.data().asnumpy()
    # oracle: global-batch stats of the SAME pre-BN activations
    w = net[0].weight.data().asnumpy()
    b = net[0].bias.data().asnumpy()
    pre = x @ w.T + b
    np.testing.assert_allclose(got_mean, pre.mean(axis=0), rtol=1e-4,
                               atol=1e-5)


def test_hetero_pipeline_matches_sequential():
    """HeteroPipeline: stages with DIFFERENT param shapes and activation
    widths (16->32->8->4) across devices must reproduce the
    single-device forward, loss, and every parameter gradient."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.pipeline import HeteroPipeline

    rng = np.random.RandomState(0)
    p0 = {"w": jnp.asarray(rng.randn(16, 32).astype("float32")) * 0.1}
    p1 = {"w": jnp.asarray(rng.randn(32, 8).astype("float32")) * 0.1,
          "b": jnp.zeros((8,), jnp.float32)}
    p2 = {"w": jnp.asarray(rng.randn(8, 4).astype("float32")) * 0.1}

    def f0(p, a):
        return jnp.tanh(a @ p["w"])

    def f1(p, a):
        return jax.nn.relu(a @ p["w"] + p["b"])

    def f2(p, a):
        return a @ p["w"]

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    x = rng.randn(8, 16).astype("float32")
    t = rng.randn(8, 4).astype("float32")

    pipe = HeteroPipeline([f0, f1, f2], [p0, p1, p2])
    y = np.asarray(pipe(x, n_microbatch=4))

    def seq(params, xx):
        return f2(params[2], f1(params[1], f0(params[0], xx)))

    y_ref = np.asarray(seq([p0, p1, p2], jnp.asarray(x)))
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-6)

    loss, grads = pipe.value_and_grad(loss_fn, x, t, n_microbatch=4)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda ps: loss_fn(seq(ps, jnp.asarray(x)), jnp.asarray(t)))(
        [p0, p1, p2])
    np.testing.assert_allclose(loss, float(ref_loss), rtol=1e-5)
    for g, rg in zip(grads, ref_grads):
        for k in rg:
            np.testing.assert_allclose(np.asarray(g[k]),
                                       np.asarray(rg[k]),
                                       rtol=1e-4, atol=1e-6,
                                       err_msg=f"grad {k}")
    # stages really live on distinct devices
    devs = {list(p["w"].devices())[0] for p in pipe.params}
    assert len(devs) == 3


def test_ulysses_attention_matches_dense_and_ring():
    """All-to-all sequence parallelism: matches dense attention exactly
    (and hence the ring variant) for plain and causal, including H == n
    (one head per device)."""
    mesh = parallel.make_mesh(sp=4)
    rng = np.random.RandomState(3)
    B, H, L, D = 2, 4, 32, 8
    q = jnp.asarray(rng.randn(B, H, L, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, L, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, L, D).astype(np.float32))
    for causal in (False, True):
        ref = parallel.ring.local_attention(q, k, v, causal=causal)
        with mesh:
            out = parallel.ulysses.ulysses_attention_sharded(
                q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    mesh = parallel.make_mesh(sp=8)
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 4, 32, 8).astype(np.float32))  # H=4 < sp=8
    with mesh, pytest.raises(mx.MXNetError, match="divisible"):
        parallel.ulysses.ulysses_attention_sharded(q, q, q)


def _moe_oracle(x, u, wr, w1, w2, top_k, scale, bias=0.0):
    """The routed part of a sigmoid top-k expert layer in numpy, token by
    token: the top_k of score + bias chosen, weight = scale * score / sum
    of the chosen scores; an expert is relu(u W1)^2 W2.  Nothing is ever
    dropped."""
    score = 1.0 / (1.0 + np.exp(-(x @ wr.T)))
    y = np.zeros_like(u)
    for t in range(len(x)):
        chosen = np.argsort(-(score[t] + bias), kind="stable")[:top_k]
        for e in chosen:
            hidden = np.maximum(u[t] @ w1[e], 0) ** 2
            y[t] += (scale * score[t, e] / score[t, chosen].sum()
                     * (hidden @ w2[e]))
    return y


@pytest.mark.parametrize("ep,skewed", [(4, False), (2, False), (4, True)],
                         ids=["4", "2", "4-trip_counts_differ"])
def test_moe_expert_parallel_matches_oracle(monkeypatch, ep, skewed):
    """The expert layer over an `ep` mesh (each device holds E / ep
    experts, routes over all E, computes its own part; the parts are
    summed) equals the one-program result and the numpy oracle.  Skewed:
    a bias sends every token to the first device's two experts, so its
    loop over the rows runs 4 chunks of 16 where the others run 1: the
    trip count is each device's own and the sum waits outside the loop."""
    from mxnet_tpu.parallel import moe

    rng = np.random.RandomState(5)
    T, D, K, N, E = 32, 8, 8, 12, 8
    x = rng.randn(T, D).astype(np.float32)
    u = rng.randn(T, K).astype(np.float32)
    wr = rng.randn(E, D).astype(np.float32) * 0.5
    w1 = rng.randn(E, K, N).astype(np.float32) * 0.3
    w2 = rng.randn(E, N, K).astype(np.float32) * 0.3
    bias = np.zeros(E, np.float32)
    if skewed:
        bias[:2] = 10.0
        monkeypatch.setattr(moe, "ROW_CHUNK", 16)
        monkeypatch.setenv("MXNET_USE_PALLAS", "0")     # 16 is no row tile
        trips = [int(moe.plan_chunks(moe.route(
            jnp.asarray(x), jnp.asarray(wr), jnp.asarray(bias), top_k=3,
            first_expert=first, n_local=E // ep).group_sizes))
            for first in range(0, E, E // ep)]
        assert trips[0] == 4 and set(trips[1:]) == {1}, trips
    args = [jnp.asarray(a) for a in (x, u, wr, bias, w1, w2)]
    ref = _moe_oracle(x, u, wr, w1, w2, top_k=3, scale=2.5, bias=bias)
    with parallel.make_mesh(ep=ep, devices=jax.devices()[:ep]):
        y, dropped = jax.jit(lambda *a: moe.moe_apply(
            *a, top_k=3, scale=2.5))(*args)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-5, atol=2e-5)
    assert int(dropped) == 0
    # without a mesh the same layer runs in one piece
    y2, dropped2 = moe.moe_apply(*args, top_k=3, scale=2.5)
    np.testing.assert_allclose(np.asarray(y2), ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=2e-5,
                               atol=2e-5)
    assert int(dropped2) == 0
