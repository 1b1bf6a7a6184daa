"""`rotary_embedding`'s kernel route (PR 40): the one-pass Pallas rotation
of `ops.rotary` under the interpreter at small sizes against the XLA form
`_rotate` (a product with a signed permutation), forward and backward,
for every pairing the op has; which operands take which route."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import rotary
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel import make_mesh

S = 64

# name: heads, head size, r, interleaved, rotate_last
PAIRINGS = {
    # laguna's window layers, evabyte: rotate-half over the whole head
    "whole_head": (4, 128, 128, False, False),
    # laguna's full layers: the first 64 of 128, the rest passed through
    "part_of_the_head": (3, 128, 64, False, False),
    # joyai's queries: (2i, 2i + 1) over the last 64 of 192
    "interleaved_last_of_192": (4, 192, 64, True, True),
    # joyai's one rotary key: half a lane tile wide
    "one_shared_key_of_64": (1, 64, 64, True, True),
    # no cell's: the 128 rotated lanes straddle the head's two lane tiles
    "last_128_of_192": (2, 192, 128, False, True),
    # no cell's: the first of a head's two lane tiles, turned in place
    "first_64_of_256": (2, 256, 64, False, False),
}
pairings = pytest.mark.parametrize("pairing", list(PAIRINGS))


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")


def _inputs(pairing, dtype, fixed_point=False, s=S, b=2, seed=0):
    """x (B, S, heads * D) and the tables.  `fixed_point`: x in 32nds and
    the tables in 128ths, so that both products and their sum are exact
    in float32 and the result does not depend on whether a compiler
    contracts `x * cos + partner * sin` into a fused multiply-add (XLA's
    CPU backend does, here and there)."""
    h, d, r, interleaved, _ = PAIRINGS[pairing]
    rng = np.random.RandomState(seed)
    cos, sin = rotary.rotary_tables(rotary.default_inv_freq(10000.0, r), s,
                                    1.25, interleaved=interleaved)
    if fixed_point:
        x = rng.randint(-64, 64, (b, s, h * d)) / 32.0
        cos, sin = (jnp.round(t * 128) / 128 for t in (cos, sin))
    else:
        x = rng.randn(b, s, h * d)
    return jnp.asarray(x, dtype), cos, sin


def _kernel(pairing, x, cos, sin):
    h, _, _, interleaved, rotate_last = PAIRINGS[pairing]
    return rotary._rotate_routed(x, cos, sin, h, interleaved, rotate_last)


def _xla(pairing, x, cos, sin):
    h, _, _, interleaved, rotate_last = PAIRINGS[pairing]
    return rotary._rotate(x, cos, sin, heads=h, interleaved=interleaved,
                          rotate_last=rotate_last)


def _f32(v):
    return np.asarray(v, np.float32)


@pairings
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_forward_equals_the_xla_form_to_the_bit(interpreted, pairing, dtype):
    """Same arithmetic: float32 `x * cos + partner * sin`, one rounding
    to x's dtype.  On fixed-point inputs every compiler must agree."""
    x, cos, sin = _inputs(pairing, dtype, fixed_point=True)
    before = rotary.route_counts()
    got = _kernel(pairing, x, cos, sin)
    assert rotary.route_counts()["kernel"] == before["kernel"] + 1
    want = _xla(pairing, x, cos, sin)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(_f32(got), _f32(want))
    assert np.abs(_f32(got) - _f32(x)).max() > 0.1      # it turned


@pairings
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_forward_on_real_tables_is_the_xla_form_within_one_rounding(
        interpreted, pairing, dtype):
    """Normal x and the tables as the models make them: equal but where
    a contracted multiply-add moved the float32 sum across a tie."""
    x, cos, sin = _inputs(pairing, dtype)
    got, want = _kernel(pairing, x, cos, sin), _xla(pairing, x, cos, sin)
    step = 2.0 ** -7 if dtype == "bfloat16" else 2.0 ** -22
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=step, atol=1e-6)
    assert (_f32(got) != _f32(want)).mean() < 1e-3


@pairings
def test_gradient_equals_autodiff_of_the_xla_form(interpreted, pairing):
    """The backward rule, `g * cos + (g * sin) P^T` through the same
    kernel body, against `jax.grad` of `_rotate`, in float32."""
    x, cos, sin = _inputs(pairing, "float32")
    weights = jnp.asarray(np.random.RandomState(1).randn(*x.shape),
                          jnp.float32)

    def gradient(f):
        return jax.grad(lambda v: (f(pairing, v, cos, sin) * weights).sum())(x)

    got, want = gradient(_kernel), gradient(_xla)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert np.abs(_f32(got) - _f32(weights)).max() > 0.1


@pairings
def test_a_bfloat16_cotangent_gives_a_bfloat16_gradient(interpreted, pairing):
    """The rule takes the cotangent in the dtype it arrives in and
    rounds once: within half a bfloat16 step of the float32 gradient."""
    x, cos, sin = _inputs(pairing, "bfloat16")
    g = jnp.asarray(np.random.RandomState(1).randn(*x.shape), jnp.bfloat16)
    (got,) = jax.vjp(lambda v: _kernel(pairing, v, cos, sin), x)[1](g)
    assert got.dtype == jnp.bfloat16
    (want,) = jax.vjp(lambda v: _xla(pairing, v, cos, sin),
                      x.astype(jnp.float32))[1](g.astype(jnp.float32))
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=2.0 ** -8,
                               atol=1e-6)


def test_the_tables_get_no_gradient(interpreted):
    x, cos, sin = _inputs("whole_head", "float32")
    d_cos, d_sin = jax.grad(
        lambda c, s_: _kernel("whole_head", x, c, s_).sum(),
        argnums=(0, 1))(cos, sin)
    assert not np.asarray(d_cos).any() and not np.asarray(d_sin).any()


# ---- which operand takes which route ----------------------------------------

def _counted(before):
    return {k: v - before[k] for k, v in rotary.route_counts().items()
            if v != before[k]}


def _op_routes(h, d, r, kv_heads=0, dk=0, s=S, **pairing):
    """The routes one `rotary_embedding` call counts, traced only."""
    q = jax.ShapeDtypeStruct((2, s, h * d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, s, (kv_heads or h) * (dk or d)),
                             jnp.bfloat16)
    table = jax.ShapeDtypeStruct((s, r), jnp.float32)
    before = rotary.route_counts()
    jax.eval_shape(lambda *a: apply_pure(
        "rotary_embedding", *a, num_heads=h, num_kv_heads=kv_heads,
        **pairing), q, k, table, table)
    return _counted(before)


@pytest.mark.parametrize("routes, sizes", [
    ({"kernel": 2}, dict(h=4, d=128, r=128, kv_heads=2)),
    ({"kernel": 2}, dict(h=3, d=128, r=64, kv_heads=1)),
    ({"kernel": 2}, dict(h=4, d=192, r=64, kv_heads=1, dk=64,
                         interleaved=True, rotate_last=True)),
    ({"kernel": 2}, dict(h=6, d=64, r=32)),     # heads of half a tile
    ({"xla": 2}, dict(h=4, d=128, r=128, s=24)),    # no row block fits
    ({"xla": 2}, dict(h=4, d=96, r=32)),        # a head of 0.75 tiles
    ({"kernel": 1, "xla": 1}, dict(h=4, d=128, r=16, kv_heads=1, dk=16)),
])
def test_route_is_chosen_from_the_shape_an_operand_at_a_time(routes, sizes):
    assert _op_routes(**sizes) == routes


def test_route_is_the_xla_one_under_a_mesh_of_two_devices_or_the_switch(
        monkeypatch):
    """GSPMD cannot partition a Mosaic call, so a mesh of several devices
    keeps the XLA form; a mesh of one does not; `MXNET_USE_PALLAS=0`, the
    switch every kernel route honours, selects XLA."""
    sizes = dict(h=4, d=128, r=128)
    with make_mesh(dp=2):
        assert _op_routes(**sizes) == {"xla": 2}
    with make_mesh(dp=1):
        assert _op_routes(**sizes) == {"kernel": 2}
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    assert _op_routes(**sizes) == {"xla": 2}


def test_route_counts_count_a_trace_not_a_call_and_reach_telemetry():
    """Counted where the branch is chosen, once a compiled program; the
    counter `mx_rotary_route_total{route}` is the dict's export."""
    x, cos, sin = _inputs("whole_head", "float32")
    step = jax.jit(lambda x: apply_pure("rotary_embedding", x, x[..., :16],
                                        cos[:, :16], sin[:, :16],
                                        num_heads=4 * 8, num_kv_heads=1))
    telemetry.enable()
    try:
        fam = lambda: telemetry.get_registry().get("mx_rotary_route_total")
        exported = {r: fam().labels(r).value if fam() else 0
                    for r in rotary.ROUTES}
        before = rotary.route_counts()
        for _ in range(3):
            step(x)
        assert _counted(before) == {"xla": 2}       # heads of 16
        assert fam().labels("xla").value == exported["xla"] + 2
        assert fam().labels("kernel").value == exported["kernel"]
    finally:
        telemetry.disable()


@pairings
def test_lowered_for_the_cpu_the_kernel_route_runs_the_xla_form(pairing):
    """Without the interpreter a program lowered for the CPU holds
    `_rotate` (`platform_dependent`), value and gradient."""
    x, cos, sin = _inputs(pairing, "float32")
    before = rotary.route_counts()
    got, vjp = jax.vjp(lambda v: _kernel(pairing, v, cos, sin), x)
    assert _counted(before) == {"kernel": 1}
    want, vjp_xla = jax.vjp(lambda v: _xla(pairing, v, cos, sin), x)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(vjp(got)[0], vjp_xla(want)[0])


@pytest.mark.parametrize("sizes, tiling", [
    # S, heads, D, r, rotate_last -> rows a block, first lane, lanes
    ((8192, 64, 128, 128, False), (128, 0, 128)),   # laguna's window layers
    ((8192, 48, 128, 64, False), (128, 0, 128)),    # its full layers
    ((8192, 8, 128, 128, False), (1024, 0, 128)),   # its keys
    ((32768, 32, 128, 128, False), (256, 0, 128)),  # evabyte
    # joyai's queries: the second lane tile of a head alone, in place
    ((8192, 32, 192, 64, True), (256, 128, 128)),
    ((8192, 1, 64, 64, True), (1024, 0, 64)),       # its one key
    ((8192, 2, 192, 128, True), (1024, 0, 192)),    # both tiles: the head
    ((8192, 4, 256, 64, False), (1024, 0, 128)),    # the first tile of two
])
def test_the_tiling_at_the_benchmark_shapes(sizes, tiling):
    """All heads of as many positions as 2 MiB of VMEM hold, and of a
    head only the lane tiles with rotated lanes where they are a block."""
    s, heads, d, r, rotate_last = sizes
    assert rotary._tiling(s, heads, d, r, rotate_last, 2) == tiling
    rows, _, lanes = tiling
    assert heads * rows * -(-lanes // 128) * 128 * 2 <= 2 << 20
