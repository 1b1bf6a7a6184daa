"""The Mamba-2 scan's `fused_kernel` route (PR 28): the Pallas kernels of
`ops.pallas_ssd` under the interpreter at lane-filling widths, against the
step-by-step recurrence and against the `chunked_xla` route; what stays
float32; which calls take which route."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_ssd, ssm
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel import make_mesh, spmd

_CONFIG_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs",
    "nemotron3_super_120b")
CHUNK = 128


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")


def _scan_inputs(seed, s, dtype, b=2, h=4, p=64, g=2, n=128,
                 decays=(1, 16)):
    """`decays`: the range -a is drawn from; at (0.01, 0.05) a state
    lives through many chunks."""
    rng = np.random.RandomState(seed)
    x = rng.randn(b, s, h, p)
    dt = rng.randn(b, s, h)
    a_log = np.log(rng.uniform(*decays, h))
    bm, cm = (rng.randn(b, s, g, n) * 0.5 for _ in range(2))
    d = rng.randn(h)
    dt_bias = rng.randn(h) - 3.0
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    return (jnp.asarray(x, dtype), jnp.asarray(dt, dtype), f32(a_log),
            jnp.asarray(bm, dtype), jnp.asarray(cm, dtype), f32(d),
            f32(dt_bias))


def _rel(got, want):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    return np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30)


def _value_and_gradients(f, args):
    weights = jnp.asarray(np.random.RandomState(1).randn(*args[0].shape),
                          jnp.float32)

    def weighted(*a):
        y = f(*a)
        return (y.astype(jnp.float32) * weights).sum(), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        weighted, argnums=tuple(range(7)), has_aux=True))(*args)
    return (y,) + tuple(grads)


def _fused(*a):
    return apply_pure("ssd_scan", *a, chunk=CHUNK)


_ORACLES = {
    # the recurrence runs in float32 on the same (rounded) inputs
    "sequential": lambda *a: ssm.ssd_scan_sequential(
        *(v.astype(jnp.float32) for v in a)),
    "chunked_xla": lambda *a: ssm._scan_xla(*a, chunk=CHUNK),
}


@pytest.mark.parametrize("oracle", sorted(_ORACLES))
@pytest.mark.parametrize("dtype,h,p,g", [
    ("float32", 4, 64, 2), ("bfloat16", 4, 64, 2),
    ("float32", 4, 128, 2), ("bfloat16", 8, 64, 2)])
def test_kernels_match_values_and_all_seven_gradients(
        interpreted, oracle, dtype, h, p, g):
    """y and the gradients of x, dt, A_log, B, C, D and dt_bias, four
    chunks a sequence: two heads of 64 to a lane block, a head of 128
    alone in one, one and two lane blocks a group."""
    args = _scan_inputs(h + p, 4 * CHUNK, dtype, h=h, p=p, g=g)
    before = ssm.route_counts()
    got = _value_and_gradients(_fused, args)
    assert ssm.route_counts()["fused_kernel"] == before["fused_kernel"] + 1
    assert ssm.route_counts()["chunked_xla"] == before["chunked_xla"]
    want = _value_and_gradients(_ORACLES[oracle], args)
    assert got[0].dtype == args[0].dtype
    # bfloat16 operands round each product's inputs: the two chunked
    # routes sit as far from the recurrence as from each other, and the
    # (H,) gradients, sums of terms of both signs, furthest
    for name, a, b in zip("y x dt A_log B C D dt_bias".split(), got, want):
        assert a.shape == b.shape, name
        assert a.dtype == b.dtype or oracle == "sequential", name
        limit = 2e-4 if dtype == "float32" else (
            8e-3 if a.ndim > 1 else 3e-2)
        assert _rel(a, b) < limit, (name, _rel(a, b))


@pytest.mark.parametrize("route", ["chunked_xla", "fused_kernel"])
def test_chunked_scan_keeps_its_decays_in_float32_under_bfloat16_inputs(
        interpreted, route):
    """`tests/test_nemotron_h.py`'s test of the same name at widths both
    routes take, 16 chunks of 128, decays from slow to fast (the
    cumulative log-decay of a chunk reaches -45): bfloat16 x, B, C and dt,
    float32 decays and cumulative sums.  Either route reads 0.0022; the
    kernel handed a cumulative log-decay rounded to bfloat16 reads 0.020,
    which the second half of the test holds the limit to."""
    args = _scan_inputs(7, 16 * CHUNK, jnp.bfloat16, b=1, decays=(0.1, 4))
    want = jax.jit(_ORACLES["sequential"])(*args)
    f = _fused if route == "fused_kernel" else _ORACLES["chunked_xla"]
    before = ssm.route_counts()[route]
    got = jax.jit(f)(*args)
    assert ssm.route_counts()[route] == before + (route == "fused_kernel")
    assert got.dtype == jnp.bfloat16
    assert _rel(got, want) < 0.004, _rel(got, want)
    if route == "fused_kernel":
        operands, _ = _packed_operands(args)
        rounded = pallas_ssd.ssd_forward(
            *operands[:2],
            operands[2].astype(jnp.bfloat16).astype(jnp.float32),
            *operands[3:], groups=2, chunk=CHUNK)
        assert _rel(rounded.reshape(want.shape), want) > 0.012


def test_carried_state_stays_float32_under_bfloat16_inputs(interpreted):
    """B is zero after the first chunk, so every later chunk only decays
    the state it enters with: the forward's float32 residual must then be
    the second chunk's entering state times the float32 decay since, to
    float32's accuracy.  A state rounded to bfloat16 between chunks (or
    kept in the inputs' dtype) is off by 0.002-0.004 a chunk; the
    products' bfloat16 operands cannot hide it, as they do in y."""
    x, dt, a_log, b, c, d, dt_bias = _scan_inputs(
        9, 12 * CHUNK, jnp.bfloat16, b=1, decays=(0.01, 0.05))
    b = b.at[:, CHUNK:].set(0)
    operands, _ = _packed_operands((x, dt, a_log, b, c, d, dt_bias))
    y, states = pallas_ssd.ssd_forward(*operands, groups=2, chunk=CHUNK,
                                       keep_states=True)
    assert y.dtype == jnp.bfloat16 and states.dtype == jnp.float32
    cs = np.asarray(operands[2], np.float64)[0]          # (S, H)
    total = np.cumsum(cs[CHUNK - 1::CHUNK], axis=0)      # through chunk k
    # (lane block, N, 2 heads x P) -> (H, N, P)
    heads = lambda v: np.asarray(v, np.float64).reshape(
        2, 128, 2, 64).transpose(0, 2, 1, 3).reshape(4, 128, 64)
    first = heads(states[0, 1])
    assert np.abs(first).max() > 1.0
    for k in range(2, 12):
        since = np.exp(total[k - 1] - total[0])          # (H,)
        np.testing.assert_allclose(
            heads(states[0, k]), first * since[:, None, None],
            rtol=2e-5, atol=1e-6 * np.abs(first).max())
    assert since.min() > 0.01       # still alive after ten chunks


def _packed_operands(args):
    """-> (the kernels' operands, a)."""
    return (ssm._kernel_operands(*args, chunk=CHUNK),
            -jnp.exp(args[2].astype(jnp.float32)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_two_head_blocks_give_the_same_result(interpreted, dtype):
    """A group's four lane blocks as one grid step, two, or four: the
    same y, states and gradients (dB and dC accumulate over the group's
    steps in the kernel, not as partial tensors)."""
    args = _scan_inputs(3, 3 * CHUNK, dtype, b=1, h=16, g=2)
    operands, _ = _packed_operands(args)
    dy = jnp.asarray(np.random.RandomState(2).randn(*operands[0].shape),
                     dtype)
    outs = []
    for hb in (8, 4, 2):
        y, states = pallas_ssd.ssd_forward(
            *operands, groups=2, chunk=CHUNK, hb=hb, keep_states=True)
        assert states.dtype == jnp.float32
        outs.append((y, states) + tuple(pallas_ssd.ssd_backward(
            *operands, states, dy, groups=2, chunk=CHUNK, hb=hb)))
    assert pallas_ssd.head_block(8, 2) == 8 and pallas_ssd.head_block(16, 2) == 16
    for other in outs[1:]:
        for a, b in zip(outs[0], other):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-5, atol=1e-5)


def test_state_is_carried_across_five_chunks_in_float32(interpreted):
    """The forward's residual, the state each chunk enters with, against
    the recurrence's state at the chunk boundaries, with decays slow
    enough that the first chunk still weighs in the last."""
    args = _scan_inputs(11, 5 * CHUNK, jnp.float32, b=1, decays=(0.01, 0.05))
    x, dt, a_log, b, c, d, dt_bias = args
    operands, a = _packed_operands(args)
    _, states = pallas_ssd.ssd_forward(*operands, groups=2, chunk=CHUNK,
                                       keep_states=True)
    assert states.shape == (1, 5, 2, 128, 128)
    assert states.dtype == jnp.float32
    dtf = operands[1]
    rb = jnp.repeat(b, 2, axis=2)

    def step(state, inputs):
        x_t, dt_t, b_t = inputs
        new = (jnp.exp(dt_t * a)[..., None, None] * state
               + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return new, state

    _, entering = jax.lax.scan(
        step, jnp.zeros((1, 4, 64, 128)),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dtf, rb)))
    # (chunk, H, P, N) -> (chunk, lane block, N, 2 heads x P)
    want = entering[::CHUNK, 0].reshape(5, 2, 2, 64, 128).transpose(
        0, 1, 4, 2, 3).reshape(5, 2, 128, 128)
    assert float(jnp.abs(want[0]).max()) == 0.0
    np.testing.assert_allclose(states[0], want, rtol=2e-4, atol=2e-5)
    # the first chunk's input is still a tenth of the last state
    only_first = jax.lax.scan(
        step, jnp.zeros((1, 4, 64, 128)),
        tuple(jnp.moveaxis(v, 1, 0) for v in (
            x.at[:, CHUNK:].set(0), dtf, rb)))[1][4 * CHUNK, 0]
    assert float(jnp.linalg.norm(only_first)
                 / jnp.linalg.norm(entering[4 * CHUNK, 0])) > 0.05


# ---- which call takes which route --------------------------------------------

def _trace_route(h=128, p=64, g=8, n=128, s=8192, chunk=128, dtype="bfloat16"):
    """Route of one traced call at these widths (nothing runs)."""
    shapes = [(1, s, h, p), (1, s, h), (h,), (1, s, g, n), (1, s, g, n),
              (h,), (h,)]
    dtypes = [dtype, dtype, "float32", dtype, dtype, "float32", "float32"]
    before = ssm.route_counts()
    out = jax.eval_shape(
        lambda *a: apply_pure("ssd_scan", *a, chunk=chunk),
        *(jax.ShapeDtypeStruct(sh, dt) for sh, dt in zip(shapes, dtypes)))
    assert out.shape == shapes[0] and out.dtype == jnp.dtype(dtype)
    after = ssm.route_counts()
    assert sorted(after) == ["chunked_xla", "fused_kernel"] == sorted(
        ssm.ROUTES)
    taken = [r for r in after if after[r] != before[r]]
    assert len(taken) == 1 and after[taken[0]] == before[taken[0]] + 1
    return taken[0]


@pytest.mark.parametrize("route,widths", [
    # NVIDIA-Nemotron-3-Super's Mamba-2 layers at S = 8192
    ("fused_kernel", {}),
    ("fused_kernel", {"dtype": "float32", "s": 256}),
    ("fused_kernel", {"h": 4, "p": 128, "g": 4, "s": 512}),
    ("fused_kernel", {"h": 2, "p": 256, "g": 1, "n": 256, "s": 128}),
    # config.json's rehearsal widths
    ("chunked_xla", {"h": 4, "p": 8, "g": 2, "n": 16, "s": 64, "chunk": 16}),
    ("chunked_xla", {"s": 256, "chunk": 16}),          # a chunk of 16
    ("chunked_xla", {"s": 512, "chunk": 256}),         # and one of 256
    ("chunked_xla", {"h": 24, "s": 256}),     # 3 heads of 64 to a group
    ("chunked_xla", {"n": 64, "s": 256}),     # a state of half a lane block
    ("chunked_xla", {"p": 32, "s": 256}),
    ("chunked_xla", {"s": 64}),               # shorter than one chunk
])
def test_route_is_chosen_from_the_shape(route, widths):
    assert _trace_route(**widths) == route


def test_route_is_the_xla_one_under_a_mesh_of_two_devices_or_the_switch(
        monkeypatch):
    """GSPMD cannot partition a Mosaic call, so a mesh of several devices
    keeps the XLA route; a mesh of one does not; `MXNET_USE_PALLAS=0`,
    the switch every kernel route honours, selects XLA."""
    with make_mesh(dp=2):
        assert _trace_route(s=256) == "chunked_xla"
    with make_mesh(dp=1):
        assert _trace_route(s=256) == "fused_kernel"
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    assert _trace_route(s=256) == "chunked_xla"


def test_lowered_for_the_cpu_the_fused_route_runs_its_xla_twin():
    """Without the interpreter a program lowered for the CPU holds the
    XLA form (`platform_dependent`), and autodiff goes through it."""
    args = _scan_inputs(5, 2 * CHUNK, jnp.float32, b=1)
    before = ssm.route_counts()["fused_kernel"]
    got = _value_and_gradients(_fused, args)
    assert ssm.route_counts()["fused_kernel"] == before + 1
    want = _value_and_gradients(_ORACLES["chunked_xla"], args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_step_program_holds_both_kernels_under_the_op_scope(interpreted):
    """The zoo's decoder at lane-filling Mamba widths, remat on as the
    cell runs it: the forward kernel under `jvp(`, the forward done again
    and the backward kernel under `transpose(jvp(`, all inside
    `<layer>/ssd_scan/`: what `ssd_device_ms` is read by."""
    spec = importlib.util.spec_from_file_location(
        "nemotron3_model", os.path.join(_CONFIG_DIR, "model.py"))
    model_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model_py)
    with open(os.path.join(_CONFIG_DIR, "config.json")) as f:
        config = json.load(f)
    config.update(config["rehearsal"]["model"])
    config.update(mamba_num_heads=4, mamba_head_dim=64, n_groups=2,
                  ssm_state_size=128, chunk_size=CHUNK, dtype="float32",
                  pattern_held="ME", num_hidden_layers=2)
    traffic = {"seq_len": 2 * CHUNK, "batch": 1}
    np.random.seed(0)
    before = ssm.route_counts()
    trainer = model_py.build(0, config, traffic, 1)
    assert trainer.remat
    tokens, = model_py.batch(0, config, traffic, np.asarray)
    first = float(trainer.step(tokens).asnumpy())
    assert float(trainer.step(tokens).asnumpy()) < first
    after = ssm.route_counts()
    assert after["fused_kernel"] > before["fused_kernel"]
    assert after["chunked_xla"] == before["chunked_xla"]
    names = set(spmd.step_programs()[-1]["ops"].values())

    def holds(*parts):
        return any(all(p in n for p in parts) for n in names)

    scope = "/layer0_mamba/"
    assert holds("/jvp(", scope, "/ssd_scan/", "mx_ssd_scan_fwd")
    assert holds("/transpose(jvp(", scope, "rematted_computation/ssd_scan/",
                 "mx_ssd_scan_fwd")
    assert holds("/transpose(jvp(", scope, "/ssd_scan/", "mx_ssd_scan_bwd")
