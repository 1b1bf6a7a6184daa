"""Mamba-1's selective scan (PR 49): the `chunked_xla` route and the
`fused_kernel` route (its two Pallas kernels under the interpreter)
against the step-by-step oracle, values and every gradient; the float32
state under bfloat16 inputs; what is live across chunks; `supports` and
the route counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import kernel_route
from mxnet_tpu.ops import selective_scan as ss
from mxnet_tpu.ops.registry import apply_pure

_NAMES = ("x", "delta", "a_log", "b", "c", "d_skip", "delta_bias")


def _operands(bsz, s, d, n, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    a_log = jnp.log(jnp.broadcast_to(
        jnp.arange(1, n + 1, dtype=jnp.float32), (d, n))) \
        + 0.1 * jax.random.normal(k[2], (d, n))
    operands = (
        jax.random.normal(k[0], (bsz, s, d)).astype(dtype),
        jax.random.normal(k[1], (bsz, s, d)).astype(dtype), a_log,
        jax.random.normal(k[3], (bsz, s, n)).astype(dtype),
        jax.random.normal(k[4], (bsz, s, n)).astype(dtype),
        1.0 + 0.1 * jax.random.normal(k[5], (d,)),
        jax.random.normal(k[6], (d,)) - 2.0)
    return operands, jax.random.normal(k[7], (bsz, s, d))


def _value_and_grads(fn, operands, weight):
    def loss(*operands):
        return (fn(*operands).astype(jnp.float32) * weight).sum()
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7))))(
        *operands)


def _close(got, want, tol):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) / scale < tol


def _held_to_the_oracle(operands, weight, chunk, tol=2e-5):
    value, grads = _value_and_grads(
        lambda *a: ss._selective_scan(*a, chunk=chunk), operands, weight)
    want, want_grads = _value_and_grads(ss.selective_scan_sequential,
                                        operands, weight)
    _close(value, want, tol)
    for name, got, ref in zip(_NAMES, grads, want_grads):
        assert got.dtype == ref.dtype, name
        _close(got, ref, tol)


# ---- the two routes against the oracle ---------------------------------------

@pytest.mark.parametrize("shape", [
    (2, 48, 96, 16, 16), (1, 40, 32, 8, 8), (2, 5, 24, 4, 64),
    (1, 64, 128, 16, 64)], ids=["3_chunks", "5_chunks_n8",
                                "shorter_than_a_chunk", "one_chunk"])
def test_chunked_xla_value_and_seven_gradients(shape):
    bsz, s, d, n, chunk = shape
    before = ss.route_counts()
    _held_to_the_oracle(*_operands(bsz, s, d, n), chunk)
    after = ss.route_counts()
    assert after["chunked_xla"] > before["chunked_xla"]
    assert after["fused_kernel"] == before["fused_kernel"]


@pytest.mark.parametrize("shape", [(2, 24, 1024, 8), (1, 32, 2048, 16)],
                         ids=["3_chunks_b2", "2_chunks_2_channel_blocks"])
def test_fused_kernel_value_and_seven_gradients(monkeypatch, shape):
    """Both kernels through the Pallas interpreter: the state carried
    over chunks and channel blocks, the reverse walk, dB and dC summed
    over every channel block."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    bsz, s, d, chunk = shape
    before = ss.route_counts()
    _held_to_the_oracle(*_operands(bsz, s, d, 16, seed=1), chunk)
    after = ss.route_counts()
    assert after["fused_kernel"] > before["fused_kernel"]
    assert after["chunked_xla"] == before["chunked_xla"]


def test_the_kernels_entering_states_are_the_oracles(monkeypatch):
    """What the forward kernel writes beside y: the state each chunk
    enters with, 1 / chunk of the states."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    (x, delta, a_log, b, c, _d, bias), _ = _operands(1, 24, 1024, 16)
    dt, a = ss._discretize(delta, bias, a_log)

    def step(h, inputs):
        x_t, dt_t, b_t = inputs
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t
        return h, h

    states = lax.scan(step, jnp.zeros_like(a), (x[0], dt[0], b[0]))[1]
    tiled = lambda v: v.reshape(1, 24, 8, 128)
    _y, hs = ss._fwd_pallas(tiled(x), tiled(dt), a.T.reshape(16, 8, 128),
                            b.reshape(1, -1), c.reshape(1, -1), chunk=8)
    assert hs.shape == (1, 3, 16, 8, 128)
    assert not np.asarray(hs[0, 0]).any()
    for j in (1, 2):
        _close(hs[0, j].reshape(16, 1024).T, states[8 * j - 1], 2e-5)


# ---- precision ---------------------------------------------------------------

def _bfloat16_state(x, delta, a_log, b, c, d_skip, delta_bias):
    """The oracle with the state rounded to bfloat16 after every step."""
    dt, a = ss._discretize(delta, delta_bias, a_log)
    f32 = lambda v: v.astype(jnp.float32)

    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs
        h = (jnp.exp(dt_t[..., None] * a) * f32(h)
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        h = h.astype(jnp.bfloat16)
        return h, jnp.einsum("bdn,bn->bd", f32(h), c_t)

    h0 = jnp.zeros(x.shape[:1] + a.shape, jnp.bfloat16)
    y = lax.scan(step, h0, tuple(jnp.moveaxis(f32(v), 1, 0)
                                 for v in (x, dt, b, c)))[1]
    return jnp.moveaxis(y, 0, 1) + d_skip * f32(x)


@pytest.mark.parametrize("route", ss.ROUTES)
def test_the_state_is_float32_under_bfloat16_inputs(monkeypatch, route):
    """bfloat16 x, delta, B, C: y leaves in bfloat16 after ONE rounding of
    a float32 sum over a float32 state; a state held in bfloat16 reads
    several times further off."""
    if route == "fused_kernel":
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    d = 1024 if route == "fused_kernel" else 64
    operands, _ = _operands(1, 256, d, 16, jnp.bfloat16, seed=2)
    # long memories (small steps), and no skip term: y is the state's
    operands = operands[:5] + (jnp.zeros_like(operands[5]),
                               operands[6] - 3.0)
    exact = ss.selective_scan_sequential(
        *(v.astype(jnp.float32) for v in operands))
    before = ss.route_counts()[route]
    got = ss._selective_scan(*operands, chunk=64)
    assert ss.route_counts()[route] == before + 1
    assert got.dtype == jnp.bfloat16

    def off(y):
        y, ref = np.asarray(y, np.float32), np.asarray(exact)
        return float(np.linalg.norm(y - ref) / np.linalg.norm(ref))

    assert off(got) < 4e-3                       # one bfloat16 rounding
    assert off(_bfloat16_state(*operands)) > 3 * off(got)


# ---- what is live across chunks ----------------------------------------------

def _outer_sizes(jaxpr):
    """Element counts of every value of `jaxpr` outside the bodies of
    its loops (a loop's own operands and results count: they cross)."""
    sizes = []
    for eqn in jaxpr.eqns:
        sizes += [int(np.prod(v.aval.shape)) for v in eqn.outvars]
        if eqn.primitive.name in ("pjit", "remat", "checkpoint",
                                  "custom_vjp_call", "custom_jvp_call"):
            for sub in jax.core.jaxprs_in_params(eqn.params):
                sizes += _outer_sizes(sub)
    return sizes


def test_no_state_array_of_the_whole_sequence_in_the_twin():
    """Value and gradients of the `chunked_xla` route: outside the
    chunks' loop nothing is as large as (B, S, D, N) or a quarter of it;
    the largest is the (S / chunk, B, D, N) entering states."""
    bsz, s, d, n, chunk = 2, 128, 32, 16, 16
    operands, weight = _operands(bsz, s, d, n)

    def loss(*operands):
        return (ss._scan_xla(*operands, chunk=chunk) * weight).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        loss, argnums=tuple(range(7))))(*operands).jaxpr
    assert any(e.primitive.name == "scan" for e in jaxpr.eqns)
    sizes = _outer_sizes(jaxpr)
    assert max(sizes) == bsz * (s // chunk) * d * n
    assert max(sizes) * 4 <= bsz * s * d * n


# ---- shapes, routes, the registry --------------------------------------------

@pytest.mark.parametrize("shape, admitted", [
    ((5120, 16, 16384, 64), True), ((1024, 16, 128, 64), True),
    ((5120, 16, 16384, 128), True), ((2560, 16, 16384, 64), False),
    ((5120, 8, 16384, 64), False), ((5120, 128, 16384, 64), False),
    ((5120, 16, 16400, 64), False), ((96, 16, 64, 64), False)])
def test_supports_states_the_kernels_shapes(shape, admitted):
    assert ss.supports(*shape) is admitted


def test_the_knob_selects_the_twin(monkeypatch):
    operands, _ = _operands(1, 16, 1024, 16)
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    before = ss.route_counts()
    ss._selective_scan(*operands, chunk=8)
    after = ss.route_counts()
    assert after["chunked_xla"] == before["chunked_xla"] + 1
    assert after["fused_kernel"] == before["fused_kernel"]
    assert tuple(after) == ss.ROUTES == ("chunked_xla", "fused_kernel")
    assert kernel_route.counts("selective_scan") == after


def test_a_program_lowered_for_the_cpu_takes_the_twin():
    """Admitted and counted `fused_kernel`, but lowered for the CPU the
    dispatch takes the twin: no Mosaic call in the program."""
    operands, _ = _operands(1, 16, 1024, 16)
    text = jax.jit(lambda *a: ss._selective_scan(*a, chunk=8)).lower(
        *operands).as_text()
    assert "tpu_custom_call" not in text and "while" in text


@pytest.mark.parametrize("fault", ["delta", "a_log", "b", "chunk"])
def test_other_shapes_are_refused(fault):
    (x, delta, a_log, b, c, d_skip, bias), _ = _operands(1, 24, 32, 16)
    if fault == "delta":
        delta = delta[:, :-1]
    elif fault == "a_log":
        a_log = a_log[:-1]
    elif fault == "b":
        b = b[..., :-1]
    with pytest.raises(MXNetError, match="selective_scan"):
        ss._selective_scan(x, delta, a_log, b, c, d_skip, bias,
                           chunk=16 if fault != "chunk" else 5)


def test_the_registered_op_is_the_function():
    operands, _ = _operands(1, 12, 32, 16)
    got = apply_pure("selective_scan", *operands, chunk=4)
    _close(got, ss.selective_scan_sequential(*operands), 2e-5)
    out = mx.nd.selective_scan(*(mx.nd.array(np.asarray(v))
                                 for v in operands), chunk=4)
    _close(out.asnumpy(), got, 1e-6)
