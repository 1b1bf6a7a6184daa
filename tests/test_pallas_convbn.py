"""Fused Conv+BN+ReLU unit (ops/pallas_convbn.py) vs the op-granular path.

Oracle strategy (SURVEY.md §4): the composed XLA ops (Convolution +
explicit affine/relu/stat math) are the reference; the fused unit must
match in forward values, BN statistics, and every gradient.  The Pallas
kernel itself runs under MXNET_PALLAS_INTERPRET on the CPU backend.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_convbn as pcb


def _rand(shape, dtype=np.float32, scale=1.0):
    return (np.random.RandomState(hash(shape) % 2**31).randn(*shape)
            * scale).astype(dtype)


def _ref_unit(x, w, sc, bi, sh, kernel, stride, pad, act_in):
    """Composed op-granular math (the oracle)."""
    if act_in:
        u = (x.astype(jnp.float32) * sc.reshape(1, 1, 1, -1)
             + bi.reshape(1, 1, 1, -1))
        u = jnp.maximum(u, 0.0).astype(x.dtype)
    else:
        u = x
    y = jax.lax.conv_general_dilated(
        u, jnp.transpose(w, (2, 3, 1, 0)), stride,
        [(pad[0], pad[0]), (pad[1], pad[1])],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    yf = y.astype(jnp.float32)
    s1 = jnp.sum(yf, axis=(0, 1, 2))
    d = yf - sh.reshape(1, 1, 1, -1)
    s2 = jnp.sum(d * d, axis=(0, 1, 2))
    return y, s1, s2


CASES = [
    # (shape NHWC, Co, kernel, stride, pad, act_in)
    ((4, 8, 8, 16), 16, (3, 3), (1, 1), (1, 1), True),
    ((4, 8, 8, 16), 32, (1, 1), (1, 1), (0, 0), True),
    ((4, 9, 9, 8), 16, (1, 1), (2, 2), (0, 0), False),
    ((2, 8, 8, 8), 8, (3, 3), (2, 2), (1, 1), True),
    ((1, 7, 7, 24), 12, (3, 3), (1, 1), (1, 1), False),
]


@pytest.mark.parametrize("case", CASES)
def test_fallback_matches_composed(case):
    shape, co, kernel, stride, pad, act_in = case
    x = jnp.asarray(_rand(shape))
    w = jnp.asarray(_rand((co, shape[-1]) + kernel, scale=0.2))
    sc = jnp.asarray(_rand((shape[-1],)) ** 2 + 0.5)
    bi = jnp.asarray(_rand((shape[-1],)))
    sh = jnp.asarray(_rand((co,)))
    y, s1, s2 = pcb.fused_conv_unit(x, w, sc, bi, sh, kernel=kernel,
                                    stride=stride, pad=pad, act_in=act_in)
    yr, s1r, s2r = _ref_unit(x, w, sc, bi, sh, kernel, stride, pad, act_in)
    np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, s1r, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(s2, s2r, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("case", CASES)
def test_pallas_interpret_matches_fallback(case, monkeypatch):
    shape, co, kernel, stride, pad, act_in = case
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    x = jnp.asarray(_rand(shape))
    w = jnp.asarray(_rand((co, shape[-1]) + kernel, scale=0.2))
    sc = jnp.asarray(_rand((shape[-1],)) ** 2 + 0.5)
    bi = jnp.asarray(_rand((shape[-1],)))
    sh = jnp.asarray(_rand((co,)))
    y, s1, s2 = pcb._pallas_unit(x, w, sc, bi, sh, kernel=kernel,
                                 stride=stride, pad=pad, act_in=act_in,
                                 want_stats=True)
    yr, s1r, s2r = _ref_unit(x, w, sc, bi, sh, kernel, stride, pad, act_in)
    np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, s1r, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(s2, s2r, rtol=1e-4, atol=1e-3)


def _per_image_bytes(h, w, ci, ho, wo, co, itemsize=2):
    # mirror of the tap-accumulation working set in pcb._batch_tile
    return ((h + 2) * (w + 2) * ci * itemsize + ho * wo * co * 4
            + ho * wo * ci * itemsize + 2 * h * w * ci * itemsize
            + 2 * ho * wo * co * itemsize)


def test_batch_tile_divides_and_respects_budget():
    # 56x56-stage image: a few MB — must be admitted (nb >= 1) and any
    # nb > 1 must stay inside the budget
    nb = pcb._batch_tile(256, 56, 56, 64, 56, 56, 64)
    assert 256 % nb == 0 and nb >= 1
    assert nb == 1 or nb * _per_image_bytes(56, 56, 64, 56, 56, 64) \
        <= pcb._COLS_BUDGET_BYTES
    nb = pcb._batch_tile(256, 7, 7, 512, 7, 7, 512)
    assert 256 % nb == 0 and nb >= 2
    # 1x1 expansion conv: the fp32 accumulator + y blocks (co=2048)
    # dominate the working set — the budget must count them
    nb = pcb._batch_tile(256, 7, 7, 512, 7, 7, 2048)
    assert nb == 1 or nb * _per_image_bytes(7, 7, 512, 7, 7, 2048) \
        <= pcb._COLS_BUDGET_BYTES
    # nb must divide n even for odd n
    assert pcb._batch_tile(3, 8, 8, 16, 8, 8, 16) in (1, 3)


@pytest.mark.parametrize("act_in", [True, False])
def test_gradients_match_composed(act_in):
    shape, co, kernel, stride, pad = (2, 6, 6, 8), 8, (3, 3), (1, 1), (1, 1)
    x = jnp.asarray(_rand(shape))
    w = jnp.asarray(_rand((co, shape[-1]) + kernel, scale=0.2))
    sc = jnp.asarray(_rand((shape[-1],)) ** 2 + 0.5)
    bi = jnp.asarray(_rand((shape[-1],)))
    sh = jnp.asarray(_rand((co,)))

    # scalar losses touching y, s1 AND s2 so every cotangent path is live
    def loss_fused(x, w, sc, bi):
        y, s1, s2 = pcb.fused_conv_unit(x, w, sc, bi, sh, kernel=kernel,
                                        stride=stride, pad=pad,
                                        act_in=act_in)
        return (jnp.sum(y * y) + jnp.sum(jnp.sin(s1)) + jnp.sum(s2 * 0.1))

    def loss_ref(x, w, sc, bi):
        y, s1, s2 = _ref_unit(x, w, sc, bi, sh, kernel, stride, pad, act_in)
        return (jnp.sum(y * y) + jnp.sum(jnp.sin(s1)) + jnp.sum(s2 * 0.1))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, w, sc, bi)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, w, sc, bi)
    for a, b, name in zip(gf, gr, ("x", "w", "scale", "bias")):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4,
                                   err_msg=f"grad {name}")


def test_shift_gets_zero_gradient():
    shape, co = (2, 4, 4, 8), 8
    x = jnp.asarray(_rand(shape))
    w = jnp.asarray(_rand((co, 8, 1, 1), scale=0.2))
    sh = jnp.asarray(_rand((co,)))

    def loss(sh):
        _, _, s2 = pcb.fused_conv_unit(x, w, None, None, sh)
        return jnp.sum(s2)

    np.testing.assert_allclose(jax.grad(loss)(sh), np.zeros(co), atol=0)


def test_defaults_are_identity():
    x = jnp.asarray(_rand((2, 4, 4, 8)))
    w = jnp.asarray(_rand((16, 8, 1, 1), scale=0.2))
    y, s1, s2 = pcb.fused_conv_unit(x, w)
    yr, s1r, s2r = _ref_unit(x, w, jnp.ones(8), jnp.zeros(8), jnp.zeros(16),
                             (1, 1), (1, 1), (0, 0), False)
    np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s2, s2r, rtol=1e-4, atol=1e-3)


def test_multi_device_mesh_selects_sharded_pallas(monkeypatch):
    """Under a multi-device mesh the fused unit now takes the
    shard_map-wrapped per-shard Pallas kernel (round-4 verdict item #2:
    the flagship optimization must survive dp>1); a single-device or
    no-mesh trace keeps the direct Pallas path; a batch that doesn't
    divide the dp shards falls back to XLA.  Also pins that
    SPMDTrainer's traced step runs under ITS mesh scope even when
    step() is called outside `with mesh:`."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")

    calls = {"pallas": 0, "sharded": 0}
    real = pcb._pallas_unit
    real_sh = pcb._pallas_unit_sharded

    def spy(*a, **k):
        calls["pallas"] += 1
        return real(*a, **k)

    def spy_sh(*a, **k):
        calls["sharded"] += 1
        return real_sh(*a, **k)

    monkeypatch.setattr(pcb, "_pallas_unit", spy)
    monkeypatch.setattr(pcb, "_pallas_unit_sharded", spy_sh)
    x = jnp.asarray(_rand((2, 4, 4, 8)))
    w = jnp.asarray(_rand((8, 8, 1, 1), scale=0.2))

    pcb.fused_conv_unit(x, w)   # warm-up (probe + first call both spy)
    base = calls["pallas"]
    pcb.fused_conv_unit(x, w)                      # no mesh: direct Pallas
    assert calls["pallas"] == base + 1 and calls["sharded"] == 0
    with parallel.make_mesh(dp=2):
        pcb.fused_conv_unit(x, w)                  # dp=2: sharded Pallas
    assert calls["sharded"] == 1
    with parallel.make_mesh(dp=1):
        pcb.fused_conv_unit(x, w)                  # size-1 mesh: direct
    assert calls["pallas"] >= base + 2 and calls["sharded"] == 1
    with parallel.make_mesh(dp=8):
        sh_before = calls["sharded"]
        # batch 2 does not divide 8 dp shards -> XLA fallback, no crash
        y, _, _ = pcb.fused_conv_unit(x, w)
        assert y.shape == (2, 4, 4, 8)
    assert calls["sharded"] == sh_before

    # trainer path: mesh scope is pushed by the trace itself, so the
    # sharded kernel engages even when step() runs outside `with mesh:`
    mesh = parallel.make_mesh(dp=2)
    assert parallel.current_mesh() is None
    from mxnet_tpu.gluon.block import HybridBlock

    class Step(HybridBlock):
        def hybrid_forward(self, F, a):
            y, _s1, _s2 = F.FusedConvUnit(a, jnp.asarray(w))
            return y.astype(jnp.float32).mean()

    blk = Step()
    blk.initialize(ctx=mx.cpu())

    class _Id:
        def __call__(self, out, *l):
            return out

    tr = parallel.SPMDTrainer(blk, _Id(), "sgd", {"learning_rate": 0.1},
                              mesh=mesh, n_labels=0)
    before = calls["sharded"]
    tr.step(tr._place(np.asarray(x), None))        # OUTSIDE with mesh:
    assert calls["sharded"] > before


@pytest.mark.parametrize("axes", [{"dp": 8}, {"dp": 2, "tp": 2, "sp": 2},
                                  {"fsdp": 4, "tp": 2}])
def test_sharded_pallas_matches_fallback_full(axes, monkeypatch):
    """Round-4 verdict item #2 'Done' criterion: fused == unfused to
    tolerance — outputs, ALL gradients, and the BN-stat aux — under the
    8-device CPU mesh in interpret mode, across dp-only, mixed, and
    fsdp batch-sharding layouts."""
    from mxnet_tpu import parallel

    shape, co, kernel, stride, pad = (8, 8, 8, 16), 32, (3, 3), (1, 1), (1, 1)
    x = jnp.asarray(_rand(shape))
    w = jnp.asarray(_rand((co, shape[-1]) + kernel, scale=0.2))
    sc = jnp.asarray(_rand((shape[-1],)) ** 2 + 0.5)
    bi = jnp.asarray(_rand((shape[-1],)))
    sh = jnp.asarray(_rand((co,)))

    def loss(x, w, sc, bi, sh):
        y, s1, s2 = pcb.fused_conv_unit(
            x, w, sc, bi, sh, kernel=kernel, stride=stride, pad=pad,
            act_in=True)
        return ((y.astype(jnp.float32) ** 2).sum()
                + (s1 * s1).sum() * 1e-3 + s2.sum() * 1e-3)

    def all_outputs():
        y, s1, s2 = pcb.fused_conv_unit(
            x, w, sc, bi, sh, kernel=kernel, stride=stride, pad=pad,
            act_in=True)
        g = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, sc, bi, sh)
        return y, s1, s2, g

    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    yr, s1r, s2r, gr = all_outputs()

    monkeypatch.setenv("MXNET_USE_PALLAS", "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    with parallel.make_mesh(**axes):
        yf, s1f, s2f, gf = all_outputs()

    np.testing.assert_allclose(yf, yr, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1f, s1r, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(s2f, s2r, rtol=1e-4, atol=1e-3)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


BWD_CASES = [
    # (shape NHWC, Co, kernel, pad, act_in, want_stats)
    ((4, 8, 8, 16), 16, (3, 3), (1, 1), True, True),
    ((2, 8, 8, 8), 24, (1, 1), (0, 0), True, True),
    ((2, 6, 6, 8), 8, (3, 3), (1, 1), False, True),
    ((2, 6, 6, 8), 8, (3, 3), (1, 1), True, False),
]


@pytest.mark.parametrize("case", BWD_CASES)
def test_pallas_bwd_matches_xla_bwd(case, monkeypatch):
    """MXNET_FUSED_CONVBN_BWD=1 single-pass backward kernel == the XLA
    linear_transpose backward for every gradient, with a spy proving
    the Pallas path actually engaged (an exception inside it silently
    falls back, which would make this comparison vacuous)."""
    shape, co, kernel, pad, act_in, want_stats = case
    x = jnp.asarray(_rand(shape))
    w = jnp.asarray(_rand((co, shape[-1]) + kernel, scale=0.2))
    sc = jnp.asarray(_rand((shape[-1],)) ** 2 + 0.5)
    bi = jnp.asarray(_rand((shape[-1],)))
    sh = jnp.asarray(_rand((co,)))

    def loss(x, w, sc, bi):
        y, s1, s2 = pcb.fused_conv_unit(
            x, w, sc, bi, sh, kernel=kernel, stride=(1, 1), pad=pad,
            act_in=act_in, want_stats=want_stats)
        return ((y.astype(jnp.float32) ** 2).sum()
                + (s1 * s1).sum() * 1e-3 + s2.sum() * 1e-3)

    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")

    monkeypatch.setenv("MXNET_FUSED_CONVBN_BWD", "0")
    ref = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, sc, bi)

    calls = {"bwd": 0}
    real = pcb._pallas_unit_bwd

    def spy(*a, **k):
        calls["bwd"] += 1
        return real(*a, **k)

    monkeypatch.setattr(pcb, "_pallas_unit_bwd", spy)
    monkeypatch.setenv("MXNET_FUSED_CONVBN_BWD", "1")
    got = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, sc, bi)
    assert calls["bwd"] == 1

    for name, a, b in zip(("gx", "dw", "gscale", "gbias"), got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name}")


def test_pallas_bwd_strided_falls_back(monkeypatch):
    """Strided units keep the XLA backward even with the knob on (the
    dgrad of a strided conv needs interior-dilated pads)."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("MXNET_FUSED_CONVBN_BWD", "1")
    calls = {"bwd": 0}
    real = pcb._pallas_unit_bwd

    def spy(*a, **k):
        calls["bwd"] += 1
        return real(*a, **k)

    monkeypatch.setattr(pcb, "_pallas_unit_bwd", spy)
    x = jnp.asarray(_rand((2, 8, 8, 8)))
    w = jnp.asarray(_rand((8, 8, 3, 3), scale=0.2))

    def loss(x, w):
        y, s1, s2 = pcb.fused_conv_unit(x, w, kernel=(3, 3),
                                        stride=(2, 2), pad=(1, 1))
        return (y.astype(jnp.float32) ** 2).sum() + s2.sum() * 1e-3

    g = jax.grad(loss, argnums=(0, 1))(x, w)
    assert calls["bwd"] == 0
    assert all(np.isfinite(np.asarray(t)).all() for t in g)


def test_pallas_bwd_multi_program_accumulation(monkeypatch):
    """Force nb < n (tiny VMEM budget) so the cross-program accumulator
    path — pl.when zero-init at program 0, += on dw/gscale/gbias across
    the sequential grid — is actually executed, and still matches the
    XLA backward.  The default budget admits every BWD_CASES batch in
    one program, which would leave that path untested."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("MXNET_FUSED_CONVBN_BWD", "1")
    monkeypatch.setattr(pcb, "_COLS_BUDGET_BYTES", 1)  # nb floor = 1

    shape, co, kernel, pad = (4, 6, 6, 8), 8, (3, 3), (1, 1)
    assert pcb._batch_tile_bwd(shape[0], 6, 6, 8, 6, 6, co, 3, 3) == 1
    x = jnp.asarray(_rand(shape))
    w = jnp.asarray(_rand((co, shape[-1]) + kernel, scale=0.2))
    sc = jnp.asarray(_rand((shape[-1],)) ** 2 + 0.5)
    bi = jnp.asarray(_rand((shape[-1],)))
    sh = jnp.asarray(_rand((co,)))

    def loss(x, w, sc, bi):
        y, s1, s2 = pcb.fused_conv_unit(
            x, w, sc, bi, sh, kernel=kernel, stride=(1, 1), pad=pad,
            act_in=True, want_stats=True)
        return ((y.astype(jnp.float32) ** 2).sum()
                + (s1 * s1).sum() * 1e-3 + s2.sum() * 1e-3)

    got = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, sc, bi)

    monkeypatch.setenv("MXNET_FUSED_CONVBN_BWD", "0")
    ref = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, sc, bi)
    for name, a, b in zip(("gx", "dw", "gscale", "gbias"), got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name}")


@pytest.mark.parametrize("axes", [{"dp": 4}, {"dp": 2, "fsdp": 2, "tp": 2}])
def test_sharded_pallas_bwd_matches_fallback(axes, monkeypatch):
    """MXNET_FUSED_CONVBN_BWD under a multi-device mesh: the per-shard
    backward kernel with psum'd dw/gscale/gbias must equal the XLA
    backward on the unsharded oracle, spy-verified to have engaged."""
    from mxnet_tpu import parallel

    shape, co, kernel, pad = (8, 8, 8, 16), 16, (3, 3), (1, 1)
    x = jnp.asarray(_rand(shape))
    w = jnp.asarray(_rand((co, shape[-1]) + kernel, scale=0.2))
    sc = jnp.asarray(_rand((shape[-1],)) ** 2 + 0.5)
    bi = jnp.asarray(_rand((shape[-1],)))
    sh = jnp.asarray(_rand((co,)))

    def loss(x, w, sc, bi):
        y, s1, s2 = pcb.fused_conv_unit(
            x, w, sc, bi, sh, kernel=kernel, stride=(1, 1), pad=pad,
            act_in=True, want_stats=True)
        return ((y.astype(jnp.float32) ** 2).sum()
                + (s1 * s1).sum() * 1e-3 + s2.sum() * 1e-3)

    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    ref = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, sc, bi)

    calls = {"sharded_bwd": 0}
    real = pcb._pallas_unit_bwd_sharded

    def spy(*a, **k):
        calls["sharded_bwd"] += 1
        return real(*a, **k)

    monkeypatch.setattr(pcb, "_pallas_unit_bwd_sharded", spy)
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("MXNET_FUSED_CONVBN_BWD", "1")
    with parallel.make_mesh(**axes):
        got = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, sc, bi)
    assert calls["sharded_bwd"] == 1

    for name, a, b in zip(("gx", "dw", "gscale", "gbias"), got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name}")


def test_refused_shape_warns_once_and_is_counted(monkeypatch, caplog):
    """A shape the compiler refuses runs the XLA path — said once per
    shape at WARNING with the compiler's own message, and visible in
    unit_counts() (a "fused" number whose units all ran XLA measured the
    restructured graph, not the kernel)."""
    import logging

    class _Refuses:
        def lower(self, *a):
            raise RuntimeError("scoped vmem limit exceeded by 5.23M")

    monkeypatch.setattr(pcb, "_SHAPE_OK", {})
    monkeypatch.setattr(pcb, "_pallas_wanted", lambda: True)
    monkeypatch.setattr(pcb.jax, "jit", lambda fn: _Refuses())
    x = jnp.asarray(_rand((2, 8, 8, 8)))
    w = jnp.asarray(_rand((8, 8, 3, 3), scale=0.2))
    before = pcb.unit_counts()
    with caplog.at_level(logging.WARNING):
        for _ in range(2):
            y, _s1, _s2 = pcb.fused_conv_unit(x, w, kernel=(3, 3),
                                              pad=(1, 1))
    assert y.shape == (2, 8, 8, 8)
    refusals = [r for r in caplog.records if "refused" in r.getMessage()]
    assert len(refusals) == 1
    assert "scoped vmem limit exceeded by 5.23M" in refusals[0].getMessage()
    after = pcb.unit_counts()
    assert after["xla"] - before["xla"] == 2
    assert after["pallas"] == before["pallas"]
