"""The window / full attention decoder (PR 31): the sliding-window
attention route, rotary positions of two kinds, silu-gated experts, and
the zoo's Laguna stack against the benchmark's plain reference."""
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.block import ActiveTrace
from mxnet_tpu.gluon.model_zoo import laguna as zoo
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import residuals, rotary
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel import moe, spmd

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CONFIG_DIR = os.path.join(_REPO, "benchmark", "configs", "laguna_xs2")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "laguna_xs2_" + name, os.path.join(_CONFIG_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _load("reference")


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(_CONFIG_DIR, "config.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small_config(published):
    config = dict(published)
    config.update(config["rehearsal"]["model"])
    return config


# ---- sliding-window attention ----------------------------------------------

def _band_oracle(q, k, v, heads, kv_heads, window):
    """Dense-masked attention in float32 jnp: query i sees keys j with
    0 <= i - j < window."""
    b, s, _ = q.shape
    d = q.shape[2] // heads
    split = lambda x, n: x.reshape(b, s, n, d).transpose(0, 2, 1, 3)
    kh, vh = (jnp.repeat(split(x, kv_heads), heads // kv_heads, axis=1)
              for x in (k, v))
    score = jnp.einsum("bhqd,bhkd->bhqk", split(q, heads), kh) / math.sqrt(d)
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None]
    score = jnp.where((ahead >= 0) & (ahead < window), score, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(score, -1), vh)
    return out.transpose(0, 2, 1, 3).reshape(b, s, heads * d)


# (S, window, head size, the route the call is counted under, interpreter)
_WINDOWS = {
    "kernel_s_not_a_multiple_of_w": (384, 256, 128, "splash_window", True),
    "kernel_twin_on_cpu": (256, 128, 128, "splash_window", False),
    "odd_shape_s_not_a_multiple_of_w": (80, 32, 16, "reference", False),
    "odd_shape_s_a_multiple_of_w": (96, 32, 16, "reference", False),
    "odd_shape_one_block_and_a_bit": (40, 32, 16, "reference", False),
    "s_below_w_is_causal": (128, 512, 128, "flash_causal", False),
    "w_equals_s_is_causal": (128, 128, 128, "flash_causal", False),
}


@pytest.mark.parametrize("case", list(_WINDOWS))
def test_window_attention_matches_the_dense_masked_oracle(monkeypatch, case):
    """Value and the three gradients against a dense mask, through every
    way the op can take: the splash kernels (under the Pallas
    interpreter), their XLA twin in a program lowered for the CPU, the
    same banded XLA form for shapes the kernels do not take, and causal
    attention where the window covers the sequence."""
    s, window, d, route, interpret = _WINDOWS[case]
    if interpret:
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(s + window)
    b, h, kv = 2, 4, 2
    q = jnp.asarray(rng.randn(b, s, h * d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kv * d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kv * d), jnp.float32)
    ct = jnp.asarray(rng.randn(b, s, h * d), jnp.float32)

    def op(q, k, v):
        return apply_pure("sliding_window_attention", q, k, v, num_heads=h,
                          num_kv_heads=kv, window=window)

    before = pa.route_counts()
    got = op(q, k, v)
    after = pa.route_counts()
    assert after[route] == before[route] + 1
    assert sum(after.values()) == sum(before.values()) + 1
    np.testing.assert_allclose(got, _band_oracle(q, k, v, h, kv, window),
                               rtol=2e-5, atol=2e-5)
    if window >= s:     # the band is the causal triangle
        causal = apply_pure("dot_product_attention", q, k, v, None, None,
                            num_heads=h, num_kv_heads=kv, causal=True)
        np.testing.assert_allclose(got, causal, rtol=1e-6, atol=1e-6)
    grads = [jax.grad(lambda *a: (f(*a) * ct).sum(), argnums=(0, 1, 2))(
        q, k, v) for f in (op, lambda *a: _band_oracle(
            *a, h, kv, window))]
    for g, w in zip(*grads):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


# the causal route's own kernels: (query heads a key/value head, S); the
# blocks are 1024 rows where 1024 divides S and the fall-back elsewhere
_CAUSAL = [(1, 1024), (6, 2048), (16, 1024), (1, 384), (6, 384), (16, 640)]


@pytest.mark.parametrize("groups, s", _CAUSAL)
def test_causal_splash_kernels_match_the_xla_form(groups, s):
    """`flash_causal`'s kernels under the interpreter against
    `_causal_xla` on the same head-split operands: the output and the
    gradients of q, k and v, the key/value heads' summed over their
    group inside the kernel."""
    assert pa._splash_blocks(s, None)[0] == (1024 if s % 1024 == 0 else 128)
    rng = np.random.RandomState(groups + s)
    b, kv, d, scale = 1, 2, 128, 0.1
    q = jnp.asarray(rng.randn(b, kv * groups, s, d), jnp.float32)
    k, v = (jnp.asarray(rng.randn(b, kv, s, d), jnp.float32)
            for _ in range(2))
    ct = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    cores = (lambda q, k, v: pa._attend_causal(q, k, v, scale, None, True),
             lambda q, k, v: pa._causal_xla(q, k, v, scale))
    got, want = (jax.value_and_grad(
        lambda q, k, v: (core(q, k, v) * ct).sum(), argnums=(0, 1, 2))(
            q, k, v) for core in cores)
    np.testing.assert_allclose(cores[0](q, k, v), cores[1](q, k, v),
                               rtol=2e-5, atol=2e-5)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4 * groups)


def _reached_by(jaxpr, marks):
    """(shapes of the arrays that XLA computes from the marked inputs of
    `jaxpr`, which of its outputs are such arrays); sub-jaxprs are
    entered, and a Pallas kernel's results are the kernel's own."""
    reached = {v for v, m in zip(jaxpr.invars, marks) if m}
    shapes = []
    for eqn in jaxpr.eqns:
        marks = [isinstance(v, jax.extend.core.Var) and v in reached
                 for v in eqn.invars]
        if not any(marks) or eqn.primitive.name == "pallas_call":
            continue
        subs = [getattr(p, "jaxpr", p) for p in eqn.params.values()]
        subs = [j for j in subs if hasattr(j, "eqns")
                and len(j.invars) == len(marks)
                and len(j.outvars) == len(eqn.outvars)]
        if subs:
            inner, out_marks = _reached_by(subs[0], marks)
            shapes += inner
        else:
            out_marks = [True] * len(eqn.outvars)
            shapes += [v.aval.shape for v in eqn.outvars]
        reached.update(v for v, m in zip(eqn.outvars, out_marks) if m)
    return shapes, [isinstance(v, jax.extend.core.Var) and v in reached
                    for v in jaxpr.outvars]


@pytest.mark.parametrize("s", [256, 2048])
def test_causal_splash_program_repeats_no_key_or_value_head(s):
    """Forward and backward, traced: nothing XLA makes of k or v is as
    large as q (the six copies a key/value head had before the kernels
    took grouped heads), where the XLA form does hold such arrays; under
    upstream's split backward (S 256) and under the one kernel of the
    repo's own (S 2048, two key blocks)."""
    b, h, kv, d = 2, 12, 2, 128
    assert pa._fused_backward(None, s, d, d, h // kv) == (
        "fused" if s == 2048 else "split")
    q = jnp.zeros((b, h, s, d), jnp.bfloat16)
    k = v = jnp.zeros((b, kv, s, d), jnp.bfloat16)

    def from_kv(core):
        program = jax.make_jaxpr(jax.grad(
            lambda q, k, v: core(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        return _reached_by(program.jaxpr, [False, True, True])[0]

    as_q = lambda shapes: [x for x in shapes if math.prod(x) >= q.size]
    kernels = from_kv(lambda q, k, v: pa._causal_splash(
        q, k, v, 0.1, interpret=True))
    assert as_q(kernels) == [], kernels
    assert (b, h, s, d) in as_q(from_kv(
        lambda q, k, v: pa._causal_xla(q, k, v, 0.1)))


def _arrays(jaxpr, into=None):
    """(bytes, primitive) of every array a jaxpr makes, sub-jaxprs
    included; a Pallas kernel's results are the kernel's own."""
    into = [] if into is None else into
    for eqn in jaxpr.eqns:
        into += [(math.prod(v.aval.shape) * v.aval.dtype.itemsize,
                  eqn.primitive.name) for v in eqn.outvars
                 if hasattr(v.aval, "shape")]
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _arrays(sub, into)
    return into


def test_causal_splash_backward_holds_no_stack_of_partials():
    """The traced backward of a causal call over 4 key blocks makes no
    array larger than q in float32: dQ is ONE float32 array that the
    kernel adds to in place, where upstream's fused form writes a part
    a key block, each as large as q (the same check finds those)."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    b, h, kv, s, d = 1, 4, 2, 4096, 128
    q = jnp.zeros((b, h, s, d), jnp.bfloat16)
    k = v = jnp.zeros((b, kv, s, d), jnp.bfloat16)

    def largest(core):
        program = jax.make_jaxpr(jax.grad(
            lambda q, k, v: core(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        return max(_arrays(program.jaxpr))

    ours = largest(lambda q, k, v: pa._causal_splash(q, k, v, 0.1,
                                                     interpret=True))
    assert ours[0] <= q.size * 4, ours
    partials = sa.make_splash_mqa_single_device(
        sa.MultiHeadMask([sa.CausalMask((s, s))] * (h // kv)),
        block_sizes=sa.BlockSizes(
            block_q=1024, block_kv=1024, block_kv_compute=512,
            block_q_dkv=1024, block_kv_dkv=1024, block_kv_dkv_compute=512,
            use_fused_bwd_kernel=True), interpret=True)
    theirs = largest(lambda q, k, v: jax.vmap(jax.vmap(partials))(
        q.reshape(b, kv, h // kv, s, d), k, v))
    assert theirs[0] >= 4 * q.size * 2, theirs


def test_window_attention_counts_its_route_in_telemetry():
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import instruments

    x = jnp.zeros((1, 256, 128), jnp.float32)
    telemetry.enable()
    try:
        child = instruments.attention_route_total("splash_window")
        before = child.value
        apply_pure("sliding_window_attention", x, x, x, num_heads=1,
                   window=128)
        assert child.value == before + 1
    finally:
        telemetry.disable()
    with pytest.raises(ValueError, match="window 0"):
        apply_pure("sliding_window_attention", x, x, x, num_heads=1)


# ---- rotary positions --------------------------------------------------------

def test_yarn_frequencies_at_the_published_parameters(published):
    """`inv_freq` and the attention factor of the full layers, against
    values computed here by hand from the YaRN formula."""
    rope = published["rope_parameters"]["full_attention"]
    assert (rope["rope_theta"], rope["factor"], rope["beta_fast"],
            rope["beta_slow"], rope["original_max_position_embeddings"],
            rope["partial_rotary_factor"]) == (500000, 64, 64, 1, 4096, 0.5)
    r, base = 64, 500000.0
    # the dimension that turns n times over the original 4096 positions:
    # 64 ln(4096 / (2 pi n)) / (2 ln 500000); n = 64 -> 5.66, n = 1 -> 15.80
    low = math.floor(r * math.log(4096 / (2 * math.pi * 64))
                     / (2 * math.log(base)))
    high = math.ceil(r * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (5, 16)
    inv_freq, factor = zoo._inv_freq(published["head_dim"], **rope)
    assert inv_freq.shape == (32,)
    plain = lambda i: base ** (-2 * i / r)
    # below the ramp: theta's own frequency; above it: divided by 64
    np.testing.assert_allclose(inv_freq[0], 1.0, rtol=1e-12)
    np.testing.assert_allclose(inv_freq[low], plain(low), rtol=1e-12)
    np.testing.assert_allclose(inv_freq[high], plain(high) / 64, rtol=1e-12)
    np.testing.assert_allclose(inv_freq[31], plain(31) / 64, rtol=1e-12)
    np.testing.assert_allclose(inv_freq[31], 4.7091534e-08, rtol=1e-6)
    # mid-ramp, i = 10: ramp = (10 - 5) / 11
    ramp = 5 / 11
    np.testing.assert_allclose(
        inv_freq[10], ramp * plain(10) / 64 + (1 - ramp) * plain(10),
        rtol=1e-12)
    np.testing.assert_allclose(inv_freq[10], 0.00915058, rtol=1e-5)
    assert factor == rope["attention_factor"] == 1.4158883083359672
    np.testing.assert_allclose(factor, 0.1 * math.log(64) + 1, rtol=1e-15)
    # the sliding layers: theta 10000 over all 128 dimensions, no factor
    plain_freq, one = zoo._inv_freq(
        128, **published["rope_parameters"]["sliding_attention"])
    np.testing.assert_allclose(
        plain_freq, 10000.0 ** (-np.arange(64) / 64.0), rtol=1e-12)
    assert one == 1.0


def test_yarn_frequencies_agree_with_transformers(published):
    """A second check, never the only one: the installed transformers
    computes the same formula."""
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")
    from transformers import PretrainedConfig

    rope = published["rope_parameters"]["full_attention"]
    cfg = PretrainedConfig()
    cfg.rope_theta, cfg.head_dim = rope["rope_theta"], 128
    cfg.hidden_size, cfg.num_attention_heads = 2048, 16
    cfg.partial_rotary_factor = rope["partial_rotary_factor"]
    cfg.max_position_embeddings = published["max_position_embeddings"]
    cfg.rope_scaling = {k: rope[k] for k in (
        "rope_type", "factor", "original_max_position_embeddings",
        "beta_slow", "beta_fast")}
    want, want_factor = rope_utils._compute_yarn_parameters(cfg, "cpu")
    got, factor = zoo._inv_freq(128, **rope)
    np.testing.assert_allclose(got, want.numpy(), rtol=1e-6)
    np.testing.assert_allclose(factor, want_factor, rtol=1e-12)


@pytest.mark.parametrize("r", [64, 128])
def test_rotary_op_turns_the_first_r_dimensions_and_leaves_the_rest(r):
    rng = np.random.RandomState(r)
    b, s, h, kv, d = 2, 12, 3, 1, 128
    q = rng.randn(b, s, h * d).astype(np.float32)
    k = rng.randn(b, s, kv * d).astype(np.float32)
    inv_freq = rotary.default_inv_freq(10000.0, r)
    cos, sin = rotary.rotary_tables(inv_freq, s, 1.25)
    assert cos.shape == sin.shape == (s, r) and cos.dtype == jnp.float32
    got_q, got_k = apply_pure("rotary_embedding", jnp.asarray(q),
                              jnp.asarray(k), cos, sin, num_heads=h,
                              num_kv_heads=kv)
    for got, x, n in ((got_q, q, h), (got_k, k, kv)):
        got = np.asarray(got).reshape(b, s, n, d)
        x = x.reshape(b, s, n, d)
        np.testing.assert_array_equal(got[..., r:], x[..., r:])
        for p in range(s):
            for i in range(r // 2):     # the pair (i, i + r/2) turns
                angle = np.float32(p) * np.float32(inv_freq[i])
                c, sn = 1.25 * math.cos(angle), 1.25 * math.sin(angle)
                a, bb = x[:, p, :, i], x[:, p, :, i + r // 2]
                np.testing.assert_allclose(got[:, p, :, i], a * c - bb * sn,
                                           rtol=1e-4, atol=1e-5)
                np.testing.assert_allclose(got[:, p, :, i + r // 2],
                                           bb * c + a * sn, rtol=1e-4,
                                           atol=1e-5)
    # bfloat16 in, bfloat16 out, rotated in float32
    low, _ = apply_pure("rotary_embedding", jnp.asarray(q, jnp.bfloat16),
                        jnp.asarray(k, jnp.bfloat16), cos, sin, num_heads=h,
                        num_kv_heads=kv)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, np.float32), got_q,
                               rtol=2e-2, atol=2e-2)
    with pytest.raises(ValueError, match="tables"):
        apply_pure("rotary_embedding", jnp.asarray(q), jnp.asarray(k),
                   cos[:5], sin[:5], num_heads=h, num_kv_heads=kv)


# ---- silu-gated experts ------------------------------------------------------

def _gated_weights(rng, t=40, d=16, n=12, e=8):
    return dict(x=rng.randn(t, d).astype(np.float32),
                wr=rng.randn(e, d).astype(np.float32) * 0.5,
                b=np.zeros(e, np.float32),
                w1=rng.randn(e, d, 2 * n).astype(np.float32) * 0.3,
                w2=rng.randn(e, n, d).astype(np.float32) * 0.3)


def _expert_loop(u, table, w1, w2):
    """The routed part expert by expert: every expert over every token,
    weighed by `table` (T, n) (0 where not chosen)."""
    n = w2.shape[1]
    out = jnp.zeros_like(u)
    for e in range(w1.shape[0]):
        hidden = jax.nn.silu(u @ w1[e][:, :n]) * (u @ w1[e][:, n:])
        out = out + table[:, e, None] * (hidden @ w2[e])
    return out


# bias on the held experts 2..4, rows a chunk, the assignments the model
# expects (0: says nothing), the trips that makes; expert 4 is never
# chosen (an empty group) except under "every token"
_GATED_LOADS = {"one_trip_unequal_groups_an_empty_expert": (0.0, 64, 0, 1),
                "several_trips_a_group_across_chunks": (10.0, 16, 0, 8),
                "expected_rows_make_it_one_trip": (10.0, 16, 60, 1)}


@pytest.mark.parametrize("load", list(_GATED_LOADS))
def test_gated_experts_match_the_per_expert_loop(monkeypatch, load):
    """`experts(..., form="silu_gated")`: value and the gradients with
    respect to u, the combine weights, w1 = [gate | up] and w2 against a
    loop over the experts, in one trip of the chunk loop, in several, and
    in the one trip of a chunk grown to the load the model expects."""
    bias, chunk, expected, trips = _GATED_LOADS[load]
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")     # the ragged_dot twin
    w = _gated_weights(np.random.RandomState(8))
    w["b"][2:4] = bias
    w["b"][4] = -10.0 if bias == 0.0 else 10.0
    first, held, top_k = 2, 3, 3
    plan = moe.route(jnp.asarray(w["x"]), jnp.asarray(w["wr"]),
                     jnp.asarray(w["b"]), top_k=top_k, scale=2.5,
                     first_expert=first, n_local=held)
    sizes = np.asarray(plan.group_sizes)
    assert int(moe.plan_chunks(plan.group_sizes, expected)) == trips
    assert int(plan.dropped) == 0
    if bias == 0.0:
        assert sizes[2] == 0 and sizes[0] != sizes[1] \
            and sizes[:2].min() > 0
    else:
        assert sizes.tolist() == [40, 40, 40]
    rows = np.arange(plan.token.shape[0])
    expert = np.minimum(np.searchsorted(np.cumsum(sizes), rows, "right"),
                        held - 1)
    used = np.asarray(plan.token) < 40

    def table(weight):      # the plan's weights as a (T, held) table
        return jnp.zeros((40, held), jnp.float32).at[
            np.asarray(plan.token)[used], expert[used]].add(weight[used])

    ct = jnp.asarray(np.random.RandomState(9).randn(40, 16), jnp.float32)
    w1, w2 = jnp.asarray(w["w1"][first:first + held]), \
        jnp.asarray(w["w2"][first:first + held])

    def chunked(u, weight, w1, w2):
        out = moe.experts(u, plan._replace(weight=weight), w1, w2,
                          "silu_gated", expected)
        return (out * ct).sum(), out

    def looped(u, weight, w1, w2):
        out = _expert_loop(u, table(weight), w1, w2)
        return (out * ct).sum(), out

    args = (jnp.asarray(w["x"]), plan.weight, w1, w2)
    grads, out = jax.jit(jax.grad(chunked, argnums=(0, 1, 2, 3),
                                  has_aux=True))(*args)
    want, want_out = jax.grad(looped, argnums=(0, 1, 2, 3),
                              has_aux=True)(*args)
    for got, ref in zip((out, *grads), (want_out, *want)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        np.testing.assert_allclose(got, ref, rtol=3e-5, atol=3e-5)
    if bias == 0.0:     # the empty expert's matrices get no gradient
        assert not np.asarray(grads[2][2]).any()
        assert not np.asarray(grads[3][2]).any()


def test_row_chunk_holds_the_expected_load_in_one_trip():
    """`row_chunk`: ROW_CHUNK where the model says nothing (as
    nemotron3_super_s8192's does) or expects less than half a chunk,
    twice the expected load in whole row tiles where that is more
    (laguna_xs2_s8192: 16384 x 8 x 32 / 256 in training, half that for
    the one-sequence sample)."""
    assert moe.row_chunk() == moe.row_chunk(2048) == moe.ROW_CHUNK == 4096
    assert moe.row_chunk(16384) == 32768 and moe.row_chunk(8192) == 16384
    assert moe.row_chunk(2049) == 4608 and 4608 % moe.ROW_TILE == 0
    # the padding unit, not the kernels' row tile: that follows the 512
    # rows an expert expects here and divides the chunk
    tm = moe.choose_tile("gmm", 32768, 2048, 1024, 32, 2, 512)[0]
    assert tm in moe.ROW_TILES and 32768 % tm == 0 and tm <= 512
    sizes = jnp.full((32,), 1000, jnp.int32)        # 32,000 assignments
    assert int(moe.plan_chunks(sizes)) == 8
    assert int(moe.plan_chunks(sizes, 16384)) == 1
    assert int(moe.plan_chunks(sizes + 50, 16384)) == 2


def test_experts_refuse_a_form_or_a_width_they_do_not_know():
    w = _gated_weights(np.random.RandomState(1))
    plan = moe.route(jnp.asarray(w["x"]), jnp.asarray(w["wr"]),
                     jnp.asarray(w["b"]), top_k=2)
    u, w1, w2 = (jnp.asarray(w[k]) for k in ("x", "w1", "w2"))
    with pytest.raises(mx.MXNetError, match="form"):
        moe.experts(u, plan, w1, w2, "gelu")
    with pytest.raises(mx.MXNetError, match="form"):
        moe.experts(u, plan, w1, w2)                # relu2 wants (n, K, N)
    with pytest.raises(mx.MXNetError, match="form"):
        moe.experts(u, plan, w1[:, :, :12], w2, "silu_gated")
    out, dropped = moe.moe_apply(u, u, jnp.asarray(w["wr"]),
                                 jnp.asarray(w["b"]), w1, w2, top_k=2,
                                 form="silu_gated")
    assert out.shape == u.shape and int(dropped) == 0


# ---- the share tied to the model ---------------------------------------------

_SHARE = dict(hidden_size=16, num_heads=2, num_kv_heads=1, head_dim=8,
              eps=1e-6, num_experts=256, top_k=8, expert_size=8,
              shared_size=8, scale=2.5)


def _sparse_layer(held=None, first=0, **kw):
    layer = zoo.LagunaLayer(experts_held=held, first_expert=first,
                            **_SHARE, **kw)
    layer.initialize(mx.initializer.Normal(0.3), ctx=mx.cpu())
    return layer


def _apply(layer, x, tables, values=None):
    params = {id(p): jnp.asarray(values[n]) if values else p.data().data
              for n, p in layer._reg_params.items()}
    with ActiveTrace(params, train=False):
        out, stats = layer.forward(jnp.asarray(x), *tables)
    return np.asarray(out), np.asarray(stats)


def test_the_eight_shares_add_up_to_the_uncut_layer(reference):
    """At the published 256 experts and top-8: the parts the 8 shares of
    32 experts give (first_expert 0, 32, ..., 224), with what every chip
    computes alike (attention, shared expert) counted once, add up to
    the uncut layer, which is the plain reference's layer."""
    np.random.seed(13)
    whole = _sparse_layer(prefix="whole_")
    values = {n: np.asarray(p.data().data)
              for n, p in whole._reg_params.items()}
    x = np.random.RandomState(0).randn(2, 24, 16).astype(np.float32)
    rope = {"rope_type": "default", "rope_theta": 10000.0,
            "partial_rotary_factor": 1.0}
    tables = rotary.rotary_tables(rotary.default_inv_freq(10000.0, 8), 24)
    full, stats = _apply(whole, x, tables)
    assert stats[:-1].sum() == 48 * 8 and stats[-1] == 0

    cfg = {"num_key_value_heads": 1, "head_dim": 8, "sliding_window": 512,
           "rope_parameters": {"full_attention": rope},
           "num_experts_per_tok": 8, "moe_routed_scaling_factor": 2.5}
    flat = {"l_" + n: jnp.asarray(v) for n, v in values.items()}

    def plain(row):
        h = row + reference.attention(
            flat, "l_", reference.rms_norm(row, values["norm_weight"], 1e-6),
            2, "full_attention", cfg)
        b = reference.rms_norm(h, values["mlp_norm_weight"], 1e-6)
        alike = h + reference.gated_mlp(
            b, *(values[f"shared_{m}_weight"] for m in ("gate", "up",
                                                        "down")))
        return h + reference.sparse_mlp(flat, "l_", b, cfg), alike

    want, alike = (np.stack(v) for v in zip(*(plain(jnp.asarray(row))
                                              for row in x)))
    np.testing.assert_allclose(full, want, rtol=2e-4, atol=2e-5)

    total = np.zeros_like(full)
    for first in range(0, 256, 32):
        share = _sparse_layer(held=32, first=first, prefix=f"share{first}_")
        cut = dict(values,
                   experts_w1=values["experts_w1"][first:first + 32],
                   experts_w2=values["experts_w2"][first:first + 32])
        part, part_stats = _apply(share, x, tables, cut)
        assert part_stats[-1] == 0
        np.testing.assert_array_equal(part_stats[:32],
                                      stats[first:first + 32])
        total += part - alike
    np.testing.assert_allclose(total + alike, full, rtol=2e-4, atol=2e-5)


# ---- the whole model ---------------------------------------------------------

def test_rehearsal_model_has_every_kind_the_published_one_has(small_config):
    n = small_config["num_hidden_layers"]
    assert set(small_config["layer_types"][:n]) == set(zoo.KINDS)
    assert set(small_config["mlp_layer_types"][:n]) == {"dense", "sparse"}
    assert len(set(small_config["num_attention_heads_per_layer"])) == 2
    assert small_config["num_experts"] < small_config["num_experts_published"]
    rope = small_config["rope_parameters"]
    assert rope["full_attention"]["rope_type"] == "yarn"
    assert rope["full_attention"]["partial_rotary_factor"] == 0.5
    assert rope["sliding_attention"]["rope_type"] == "default"


def _small_model(config, model_py):
    np.random.seed(5)
    mx.random.seed(5)
    step = model_py._step_block(config)
    step.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
    return step


def test_model_matches_the_plain_reference_logits_loss_and_gradients(
        reference, small_config):
    """Both layer kinds, both rotary kinds (YaRN past its original
    context), unequal head counts, the dense layer and a share of the
    experts, S above the window so that the band cuts."""
    model_py = _load("model")
    step = _small_model(small_config, model_py)
    plist = sorted(step.collect_params().items())
    prefix = os.path.commonprefix([n for n, _ in plist])
    prefix = prefix[:prefix.rfind("_") + 1]
    values = {n: p.data().data for n, p in plist}
    named = {n[len(prefix):]: v for n, v in values.items()}
    s = 256
    assert s > small_config["sliding_window"] and s > small_config[
        "rope_parameters"]["full_attention"][
            "original_max_position_embeddings"]
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, small_config["vocab_size"], (2, s)), jnp.int32)

    def system(values):
        trace = ActiveTrace({id(p): values[n] for n, p in plist},
                            train=True)
        with trace:
            return step.forward(tokens)

    def plain(named):
        scores = reference.logits(named, tokens, small_config)
        return reference.loss_of(scores, tokens), scores

    (loss, (logits, stats)), got = jax.jit(jax.value_and_grad(
        lambda v: (lambda out: (out[0], out[1:]))(system(v)),
        has_aux=True))(values)
    (want_loss, want_logits), want = jax.jit(jax.value_and_grad(
        plain, has_aux=True))(named)
    assert (np.asarray(stats)[:, -1] == 0).all()
    np.testing.assert_allclose(logits, want_logits, rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    sparse = small_config["mlp_layer_types"][
        :small_config["num_hidden_layers"]].count("sparse")
    trained = [n for n, p in plist if p.grad_req != "null"]
    assert len(trained) == len(plist) - sparse      # the selection biases
    for n in trained:
        w = np.asarray(want[n[len(prefix):]])
        np.testing.assert_allclose(
            got[n], w, rtol=5e-3, atol=5e-3 * np.abs(w).max() + 1e-9,
            err_msg=n)


def test_step_program_holds_the_new_op_scopes_forward_and_backward(
        small_config):
    """`rotary_embedding`, `sliding_window_attention`, the full layers'
    `dot_product_attention`, `moe_route` and `moe_experts` under both
    `jvp(` and `transpose(jvp(`, inside their layer's block scope, with
    remat on as the cell runs it: what the cell's per-layer metrics are
    read by.  The router's weight and bias stay float32 under the cast."""
    model_py = _load("model")
    np.random.seed(0)
    traffic = {"seq_len": 256, "batch": 1}
    before, kept = pa.route_counts(), residuals.kept_residuals()
    trainer = model_py.build(0, small_config, traffic, 1)
    assert trainer.remat
    for name, value in trainer.params.items():
        want = jnp.float32 if "router_" in name else jnp.bfloat16
        assert value.dtype == want, name
    tokens, = model_py.batch(0, small_config, traffic, np.asarray)
    first = float(trainer.step(tokens).asnumpy())
    assert np.isfinite(first)
    assert float(trainer.step(tokens).asnumpy()) < first
    after = pa.route_counts()
    assert after["splash_window"] == before["splash_window"] + 3
    assert after["flash_causal"] == before["flash_causal"] + 2
    # each layer's segment keeps what its kernel wrote for the backward:
    # o and the logsumexp, of a window layer and of a full one alike
    now = residuals.kept_residuals()
    heads = small_config["num_attention_heads_per_layer"]
    kinds = small_config["layer_types"][:len(heads)]
    rows = {kind: 256 * sum(h for h, k in zip(heads, kinds) if k == kind)
            for kind in set(kinds)}
    width = small_config["head_dim"] * 2      # bfloat16
    for name, kind in (("splash_window", "sliding_attention"),
                       ("flash_causal", "full_attention")):
        grown = {k: now[name][k] - kept[name][k] for k in now[name]}
        assert grown == {"values": 2 * kinds.count(kind),
                         "bytes": rows[kind] * (width + 4)}, name
    names = set(spmd.step_programs()[-1]["ops"].values())

    def holds(*parts):
        return any(all(p in n for p in parts) for n in names)

    for layer, op in (("layer0", "rotary_embedding"),
                      ("layer0", "dot_product_attention"),
                      ("layer1", "rotary_embedding"),
                      ("layer1", "sliding_window_attention"),
                      ("layer1", "moe_route"),
                      ("layer1", "moe_experts"),
                      ("layer4", "dot_product_attention"),
                      ("layer4", "FullyConnected")):
        assert holds("/jvp(", f"/{layer}/{op}/"), (layer, op)
        assert holds("/transpose(jvp(", f"/{layer}/", f"/{op}/"), (layer, op)
    assert not holds("/layer1/dot_product_attention/")
    assert holds("/transpose(jvp(",
                 "rematted_computation/sliding_window_attention/")
