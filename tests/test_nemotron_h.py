"""The hybrid decoder (PR 27): the Mamba-2 scan and convolution, the
latent mixture of experts that holds a share of its experts, causal
grouped-query training attention, and the zoo's Nemotron-H stack against
the benchmark's plain reference."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.block import ActiveTrace
from mxnet_tpu.gluon.model_zoo import nemotron_h as zoo
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import ssm
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel import moe, spmd

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CONFIG_DIR = os.path.join(_REPO, "benchmark", "configs",
                           "nemotron3_super_120b")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "nemotron3_" + name, os.path.join(_CONFIG_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _load("reference")


@pytest.fixture(scope="module")
def small_config():
    with open(os.path.join(_CONFIG_DIR, "config.json")) as f:
        config = json.load(f)
    config.update(config["rehearsal"]["model"])
    return config


# ---- the scan and the convolution -----------------------------------------

def _scan_inputs(rng, s, dtype, b=2, h=4, p=8, g=2, n=16):
    x = rng.randn(b, s, h, p).astype(np.float32)
    dt = rng.randn(b, s, h).astype(np.float32)
    a_log = np.log(rng.uniform(1, 16, h)).astype(np.float32)
    bm = rng.randn(b, s, g, n).astype(np.float32) * 0.5
    cm = rng.randn(b, s, g, n).astype(np.float32) * 0.5
    d = rng.randn(h).astype(np.float32)
    dt_bias = rng.randn(h).astype(np.float32) - 3.0
    cast = [jnp.asarray(v, dtype) for v in (x, dt)]
    return (cast[0], cast[1], jnp.asarray(a_log), jnp.asarray(bm, dtype),
            jnp.asarray(cm, dtype), jnp.asarray(d), jnp.asarray(dt_bias))


@pytest.mark.parametrize("s,chunk", [(64, 16), (48, 8), (32, 32), (8, 128)])
def test_chunked_scan_matches_the_recurrence_values_and_gradients(s, chunk):
    args = _scan_inputs(np.random.RandomState(s + chunk), s, jnp.float32)
    before = ssm.route_counts()["chunked_xla"]

    def chunked(*a):
        return apply_pure("ssd_scan", *a, chunk=chunk)

    weights = jnp.asarray(np.random.RandomState(1).randn(*args[0].shape),
                          jnp.float32)

    def value_and_gradients(f):
        def weighted(*a):
            y = f(*a)
            return (y * weights).sum(), y
        return jax.jit(jax.value_and_grad(
            weighted, argnums=tuple(range(7)), has_aux=True))(*args)

    (_, got), got_grads = value_and_gradients(chunked)
    assert ssm.route_counts()["chunked_xla"] == before + 1
    (_, want), want_grads = value_and_gradients(ssm.ssd_scan_sequential)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4)


def test_chunked_scan_keeps_its_decays_in_float32_under_bfloat16_inputs():
    """bfloat16 x, B, C and dt: the products take bfloat16 operands, but
    the decays and the state carried over 32 chunks stay float32.  A
    bfloat16 cumulative decay alone would be off by a few percent."""
    args = _scan_inputs(np.random.RandomState(7), 512, jnp.bfloat16)
    got = jax.jit(lambda *a: apply_pure("ssd_scan", *a, chunk=16))(*args)
    assert got.dtype == jnp.bfloat16
    want = jax.jit(ssm.ssd_scan_sequential)(
        *(a.astype(jnp.float32) for a in args))
    err = np.linalg.norm(np.asarray(got, np.float32) - want) \
        / np.linalg.norm(want)
    assert err < 0.012, err


def test_scan_refuses_a_sequence_its_chunk_does_not_divide():
    args = _scan_inputs(np.random.RandomState(0), 24, jnp.float32)
    with pytest.raises(mx.MXNetError, match="multiple of the chunk"):
        apply_pure("ssd_scan", *args, chunk=16)


def test_causal_conv1d_is_causal_and_depthwise():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 10, 6).astype(np.float32)
    w = rng.randn(6, 4).astype(np.float32)
    bias = rng.randn(6).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += w[:, j] * x[:, t - 3 + j]
    want += bias
    got = apply_pure("causal_conv1d", jnp.asarray(x), jnp.asarray(w),
                     jnp.asarray(bias))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    later = x.copy()
    later[:, 5:] += 1.0                 # the past does not see the future
    again = apply_pure("causal_conv1d", jnp.asarray(later), jnp.asarray(w),
                       jnp.asarray(bias))
    np.testing.assert_array_equal(np.asarray(again)[:, :5],
                                  np.asarray(got)[:, :5])


# ---- the router and the experts --------------------------------------------

def _moe_weights(rng, t=40, d=16, k=8, n=12, e=8):
    return dict(x=rng.randn(t, d).astype(np.float32),
                u=rng.randn(t, k).astype(np.float32),
                wr=rng.randn(e, d).astype(np.float32) * 0.5,
                b=np.zeros(e, np.float32),
                w1=rng.randn(e, k, n).astype(np.float32) * 0.3,
                w2=rng.randn(e, n, k).astype(np.float32) * 0.3)


def _routed_oracle(w, top_k, scale, held):
    """The routed part, token by token, over the experts in `held`."""
    score = 1.0 / (1.0 + np.exp(-(w["x"] @ w["wr"].T)))
    out = np.zeros_like(w["u"])
    for t in range(len(score)):
        chosen = np.argsort(-(score[t] + w["b"]), kind="stable")[:top_k]
        total = score[t, chosen].sum() + 1e-20
        for e in chosen:
            if e in held:
                hidden = np.maximum(w["u"][t] @ w["w1"][e], 0) ** 2
                out[t] += scale * score[t, e] / total * (hidden @ w["w2"][e])
    return out


def test_router_top_k_normalisation_scale_and_bias():
    rng = np.random.RandomState(3)
    w = _moe_weights(rng)
    x, wr = jnp.asarray(w["x"]), jnp.asarray(w["wr"])
    score = 1.0 / (1.0 + np.exp(-(w["x"] @ w["wr"].T)))

    def table(plan):
        """(T, E) combine weights from a plan over all experts."""
        out = np.zeros((len(score), 8), np.float32)
        sizes = np.asarray(plan.group_sizes)
        expert = np.repeat(np.arange(8), sizes)
        rows = sizes.sum()
        out[np.asarray(plan.token)[:rows], expert] = \
            np.asarray(plan.weight)[:rows]
        return out

    plan = moe.route(x, wr, jnp.zeros(8), top_k=3, scale=5.0)
    got = table(plan)
    assert int(plan.dropped) == 0 and int(plan.group_sizes.sum()) == 40 * 3
    for t in range(40):
        chosen = set(np.argsort(-score[t])[:3])
        assert set(np.nonzero(got[t])[0]) == chosen
        np.testing.assert_allclose(got[t].sum(), 5.0, rtol=1e-5)
        for e in chosen:
            np.testing.assert_allclose(
                got[t, e], 5.0 * score[t, e] / score[t, list(chosen)].sum(),
                rtol=1e-5)
    # a bias on expert 7 moves the selection towards it, and the weights
    # still come from the scores alone
    bias = np.zeros(8, np.float32)
    bias[7] = 10.0
    biased = table(moe.route(x, wr, jnp.asarray(bias), top_k=3, scale=5.0))
    assert (biased[:, 7] > 0).all()
    for t in range(40):
        chosen = np.nonzero(biased[t])[0]
        np.testing.assert_allclose(
            biased[t, 7], 5.0 * score[t, 7] / score[t, chosen].sum(),
            rtol=1e-5)


def _scatter_route(x, w_router, bias, *, top_k, scale=1.0, first_expert=0,
                   n_local=None):
    """`moe.route` as it was until PR 32, the plain reference of its
    layout: two scatter-added (T, n_local) tables, a row's place from a
    cumulative sum over the tokens, the rows scatter-set."""
    t, e = x.shape[0], w_router.shape[0]
    n_local = e if n_local is None else n_local
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,ed->te", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(
        bias.astype(jnp.float32)), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    weights = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    local = chosen - first_expert
    col = jnp.where((local >= 0) & (local < n_local), local, n_local)
    row_of = jnp.arange(t)[:, None]
    hit = jnp.zeros((t, n_local + 1), jnp.int32).at[row_of, col].add(
        1)[:, :n_local]
    wt = jnp.zeros((t, n_local + 1), jnp.float32).at[row_of, col].add(
        weights)[:, :n_local]
    group_sizes = hit.sum(0)
    offsets = jnp.cumsum(group_sizes) - group_sizes
    rows = moe.plan_rows(t, top_k, n_local)
    pos = jnp.where(hit > 0, offsets + jnp.cumsum(hit, axis=0) - hit, rows)
    token = jnp.full((rows,), t, jnp.int32).at[pos].set(
        jnp.broadcast_to(row_of, pos.shape).astype(jnp.int32), mode="drop")
    weight = jnp.zeros((rows,), jnp.float32).at[pos].set(wt, mode="drop")
    dropped = group_sizes.sum() - (token < t).sum().astype(jnp.int32)
    return moe.RoutePlan(token, weight, group_sizes, dropped)


# tokens, experts, top_k, first held expert, experts held (None: all),
# bias on the held experts
_LAYOUTS = {
    "top8_of_256_held_32_from_0": (200, 256, 8, 0, 32, 0.0),
    "top8_of_256_held_32_from_64": (200, 256, 8, 64, 32, 0.0),
    "top22_of_512_held_8_fewer_than_top_k": (120, 512, 22, 0, 8, 0.0),
    "the_whole_layer": (40, 8, 3, 0, None, 0.0),
    "every_token_on_one_held_expert": (300, 16, 3, 2, 2, 10.0),
    "no_token_on_a_held_expert": (300, 16, 3, 2, 2, -10.0),
}


def _layout_case(name):
    t, e, top_k, first, n_local, held_bias = _LAYOUTS[name]
    rng = np.random.RandomState(len(name))
    bias = np.zeros(e, np.float32)
    bias[first:first + (n_local or e)] = held_bias
    return (jnp.asarray(rng.randn(t, 16).astype(np.float32)),
            jnp.asarray(rng.randn(e, 16).astype(np.float32) * 0.3),
            jnp.asarray(bias), first,
            dict(top_k=top_k, scale=2.5, n_local=n_local))


@pytest.mark.parametrize("traced", [False, True],
                         ids=["eager", "first_expert_traced_under_jit"])
@pytest.mark.parametrize("case", list(_LAYOUTS))
def test_sorted_layout_is_the_scatter_form_row_for_row(case, traced):
    x, wr, bias, first, kw = _layout_case(case)

    def plan(route):
        def held_from(first):
            return route(x, wr, bias, first_expert=first, **kw)
        return jax.jit(held_from)(jnp.int32(first)) if traced \
            else held_from(first)

    got, want = plan(moe.route), plan(_scatter_route)
    for field in ("token", "group_sizes", "dropped"):
        np.testing.assert_array_equal(getattr(got, field),
                                      getattr(want, field), err_msg=field)
    if traced:      # a fused sigmoid may round its last bit another way
        np.testing.assert_allclose(got.weight, want.weight, rtol=1e-6)
    else:
        np.testing.assert_array_equal(got.weight, want.weight)
    assert int(got.dropped) == 0
    held = int(got.group_sizes.sum())
    if case == "every_token_on_one_held_expert":
        assert int(got.group_sizes[0]) == x.shape[0]
    if case == "no_token_on_a_held_expert":
        assert held == 0
    # the stated order: expert by expert, token-ascending inside one
    token = np.asarray(got.token)
    assert (token[held:] == x.shape[0]).all()
    for rows in np.split(token[:held],
                         np.cumsum(np.asarray(got.group_sizes))[:-1]):
        assert (np.diff(rows) > 0).all()


@pytest.mark.parametrize("wrap", [jax.jit,
                                  lambda f: jax.jit(jax.checkpoint(f))],
                         ids=["jit", "checkpoint"])
@pytest.mark.parametrize("case", ["top8_of_256_held_32_from_64",
                                  "top22_of_512_held_8_fewer_than_top_k",
                                  "the_whole_layer"])
def test_sorted_layout_gradients_are_the_scatter_forms(case, wrap):
    x, wr, bias, first, kw = _layout_case(case)
    rows = moe.plan_rows(x.shape[0], kw["top_k"],
                         kw["n_local"] or wr.shape[0])
    coef = jnp.asarray(np.random.RandomState(1).randn(rows), jnp.float32)

    def weighted(route):
        return lambda x, wr: (route(x, wr, bias, first_expert=first,
                                    **kw).weight * coef).sum()

    want = jax.grad(weighted(_scatter_route), (0, 1))(x, wr)
    got = wrap(jax.grad(weighted(moe.route), (0, 1)))(x, wr)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 1e-4
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-7)


@pytest.mark.parametrize("cell", ["laguna_xs2_s8192",
                                  "nemotron3_super_s8192"])
def test_route_and_its_gradient_lower_without_a_scatter(cell):
    """At the two cells' shapes, from abstract arrays: one sort lays the
    rows out (under remat once more in the backward pass), another is
    the weights' gradient, and nothing in the routing stage is a scatter (PERF.md, PR 32: ~22 M serial updates a
    step were 290 of laguna_xs2_s8192's 1,445 ms)."""
    t, d, e, top_k, n_local = {
        "laguna_xs2_s8192": (16384, 2048, 256, 8, 32),
        "nemotron3_super_s8192": (8192, 4096, 512, 22, 8)}[cell]
    x = jax.ShapeDtypeStruct((t, d), jnp.bfloat16)
    wr = jax.ShapeDtypeStruct((e, d), jnp.float32)
    bias = jax.ShapeDtypeStruct((e,), jnp.float32)

    def route(x, wr, bias):
        return moe.route(x, wr, bias, top_k=top_k, scale=2.5,
                         n_local=n_local)

    def weighted(x, wr, bias):
        plan = route(x, wr, bias)
        return (plan.weight * jnp.cos(jnp.arange(plan.weight.shape[0],
                                                 dtype=jnp.float32))).sum()

    before = moe.route_counts()["sorted_layout"]
    forward = jax.jit(route).lower(x, wr, bias).as_text()
    assert moe.route_counts()["sorted_layout"] == before + 1
    both = jax.jit(jax.value_and_grad(jax.checkpoint(weighted), (0, 1))
                   ).lower(x, wr, bias).as_text()
    for text, sorts in ((forward, 1), (both, 3)):
        assert "scatter" not in text
        assert text.count("stablehlo.sort") == sorts
    assert f"tensor<{moe.plan_rows(t, top_k, n_local)}xi32>" in forward


# tokens, held experts, top_k, K, N, form, expected_rows
_STAGE_SHAPES = {
    "lfm2_8b_a1b_s8192": (16384, 8, 4, 2048, 1792, "silu_gated", 16384),
    "laguna_xs2_s8192": (16384, 32, 8, 2048, 512, "silu_gated", 16384),
    "joyai_llm_flash_s8192": (16384, 32, 8, 2048, 768, "silu_gated", 16384),
    "nemotron3_super_s8192": (8192, 8, 22, 1024, 2688, "relu2", 0),
}


@pytest.mark.parametrize("cell", list(_STAGE_SHAPES))
def test_the_expert_stage_and_its_gradient_lower_without_a_scatter(
        cell, monkeypatch):
    """At the four cells' shapes, from abstract arrays: a token sums its
    own rows, in the forward's combine and in the backward's transpose of
    the gather of u (one sort each), and nothing in either loop is a
    scatter (PERF.md, PR 44: the two whole-chunk scatter-adds were ~10 of
    a layer's 19-26 ms)."""
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    t, held, top_k, k, n, form, expected = _STAGE_SHAPES[cell]
    rows = moe.plan_rows(t, top_k, held)
    chunk = min(moe.row_chunk(expected), rows)
    stage = moe._Stage(form, chunk, moe.row_block(chunk), False)
    wide = (2 if form == "silu_gated" else 1) * n
    operands = (jax.ShapeDtypeStruct((t, k), jnp.bfloat16),
                jax.ShapeDtypeStruct((rows,), jnp.int32),
                jax.ShapeDtypeStruct((rows,), jnp.float32),
                jax.ShapeDtypeStruct((held,), jnp.int32),
                jax.ShapeDtypeStruct((held, k, wide), jnp.bfloat16),
                jax.ShapeDtypeStruct((held, n, k), jnp.bfloat16))
    before = moe.route_counts()
    forward = moe._forward.lower(*operands, stage).as_text()
    backward = moe._backward.lower(stage, operands, operands[0]).as_text()
    after = moe.route_counts()
    assert after["token_sums"] - before["token_sums"] == 2
    assert (after["expert_stage_traces"]
            - before["expert_stage_traces"]) == 2
    for text in (forward, backward):
        assert "scatter" not in text
        assert text.count("stablehlo.sort") == 1
        assert f"tensor<{t}x{k}xf32>" in text       # the chunk's (T, K) part


def test_four_expert_parallel_shares_equal_one_device_with_gradients():
    """`moe_apply` over four virtual `ep` devices (each sorts its own
    share's rows under a traced `first_expert`) against the layer in one
    piece: the result and its gradients, the router's included."""
    from mxnet_tpu import parallel

    w = _moe_weights(np.random.RandomState(8), t=64)
    args = [jnp.asarray(w[k]) for k in ("x", "u", "wr", "b", "w1", "w2")]
    coef = jnp.asarray(np.random.RandomState(2).randn(*w["u"].shape),
                       jnp.float32)

    def loss(x, u, wr, w1, w2):
        out, dropped = moe.moe_apply(x, u, wr, args[3], w1, w2, top_k=3,
                                     scale=2.0)
        return (out * coef).sum(), dropped

    inputs = [args[i] for i in (0, 1, 2, 4, 5)]
    grad = jax.value_and_grad(loss, (0, 1, 2, 3, 4), has_aux=True)
    (want, _), want_grads = jax.jit(grad)(*inputs)
    with parallel.make_mesh(ep=4, devices=jax.devices()[:4]):
        (got, dropped), got_grads = jax.jit(grad)(*inputs)
    assert int(dropped) == 0
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for g, w_ in zip(got_grads, want_grads):
        assert float(jnp.abs(w_).max()) > 1e-4
        np.testing.assert_allclose(g, w_, rtol=2e-4, atol=2e-5)


def test_every_token_on_one_held_expert_nothing_dropped():
    """The worst imbalance: a bias sends every token to expert 2 (and
    its two next choices elsewhere); the layer holds experts 2 and 3."""
    w = _moe_weights(np.random.RandomState(4))
    w["b"][2] = 10.0
    plan = moe.route(jnp.asarray(w["x"]), jnp.asarray(w["wr"]),
                     jnp.asarray(w["b"]), top_k=3, scale=2.0,
                     first_expert=2, n_local=2)
    assert int(plan.group_sizes[0]) == 40 and int(plan.dropped) == 0
    got = moe.experts(jnp.asarray(w["u"]), plan, jnp.asarray(w["w1"][2:4]),
                      jnp.asarray(w["w2"][2:4]))
    np.testing.assert_allclose(got, _routed_oracle(w, 3, 2.0, {2, 3}),
                               rtol=2e-5, atol=2e-5)


def test_expert_products_count_their_route(monkeypatch):
    w = _moe_weights(np.random.RandomState(5))
    args = [jnp.asarray(w[k]) for k in ("x", "u", "wr", "b", "w1", "w2")]
    before = moe.route_counts()
    moe.moe_apply(*args, top_k=2)
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    moe.moe_apply(*args, top_k=2)
    after = moe.route_counts()
    assert after["grouped_kernel"] == before["grouped_kernel"] + 2
    assert after["ragged_dot"] == before["ragged_dot"] + 2


def _dense_experts(u, token, weight, group_sizes, w1, w2):
    """`moe.experts` row by row in plain jnp: every row through its own
    expert's two matrices, no grouped product, no loop."""
    t = u.shape[0]
    expert = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(group_sizes), jnp.arange(token.shape[0]), side="right"),
        w1.shape[0] - 1)
    x = jnp.take(u, token, axis=0, mode="fill", fill_value=0)
    hidden = jnp.square(jnp.maximum(
        jnp.einsum("rk,rkn->rn", x, w1[expert]), 0))
    out = jnp.einsum("rn,rnk->rk", hidden, w2[expert])
    out = jnp.where((token < t)[:, None], out, 0) * weight[:, None]
    return jnp.zeros_like(u).at[token].add(out, mode="drop")


# bias on the held experts 2 and 3, rows a chunk: the assignments made
# and the chunks that hold them
_LOADS = {"typical_one_chunk": (0.0, 64),
          "every_token_several_chunks": (10.0, 16),
          "no_assignment_no_chunk": (-10.0, 16)}


def _loaded_plan(monkeypatch, load):
    bias, chunk = _LOADS[load]
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")     # the ragged_dot twin
    w = _moe_weights(np.random.RandomState(6))
    w["b"][2:4] = bias
    plan = moe.route(jnp.asarray(w["x"]), jnp.asarray(w["wr"]),
                     jnp.asarray(w["b"]), top_k=3, scale=2.0,
                     first_expert=2, n_local=2)
    return w, plan


@pytest.mark.parametrize("wrap", [jax.jit,
                                  lambda f: jax.jit(jax.checkpoint(f))],
                         ids=["jit", "checkpoint"])
@pytest.mark.parametrize("load", list(_LOADS))
def test_experts_in_chunks_match_the_dense_form(monkeypatch, load, wrap):
    """The expert stage goes over its plan `ROW_CHUNK` rows at a time, as
    often as the assignments made ask for: one chunk, several with a
    group that straddles their boundaries, none.  Result and gradients
    with respect to u, the combine weights, w1 and w2 are the dense
    form's."""
    w, plan = _loaded_plan(monkeypatch, load)
    sizes = np.asarray(plan.group_sizes)
    chunks = int(moe.plan_chunks(plan.group_sizes))
    if load == "typical_one_chunk":
        assert chunks == 1 and 0 < sizes.sum() <= 64
    elif load == "every_token_several_chunks":
        # expert 2's 40 rows end inside the third chunk of 16
        assert sizes.tolist() == [40, 40] and chunks == 5
        assert int(plan.dropped) == 0
    else:
        assert sizes.sum() == 0 and chunks == 0
    ct = jnp.asarray(np.random.RandomState(7).randn(40, 8), jnp.float32)

    def loss(form):
        def f(u, weight, w1, w2):
            out = form(u, weight, w1, w2)
            return (out * ct).sum(), out
        return f

    def chunked(u, weight, w1, w2):
        return moe.experts(u, plan._replace(weight=weight), w1, w2)

    def dense(u, weight, w1, w2):
        return _dense_experts(u, plan.token, weight, plan.group_sizes,
                              w1, w2)

    args = (jnp.asarray(w["u"]), plan.weight, jnp.asarray(w["w1"][2:4]),
            jnp.asarray(w["w2"][2:4]))
    grads, out = wrap(jax.grad(loss(chunked), argnums=(0, 1, 2, 3),
                               has_aux=True))(*args)
    want, want_out = jax.grad(loss(dense), argnums=(0, 1, 2, 3),
                              has_aux=True)(*args)
    np.testing.assert_allclose(out, _routed_oracle(w, 3, 2.0, {2, 3}),
                               rtol=2e-5, atol=2e-5)
    for got, ref in zip((out, *grads), (want_out, *want)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    if chunks == 0:
        assert not any(np.asarray(g).any() for g in (out, *grads))


def test_plan_chunks_and_plan_blocks_are_the_trip_counts_of_the_loops(
        monkeypatch):
    """`plan_chunks` and `plan_blocks` say how often the loops ran: the
    forward weighs a block's rows a trip of the loop in which a token
    sums them, once a row a token can have (two held experts: two
    shifted windows), the backward a block a trip of two such loops (the
    weights' gradient, once; the sums of `dx` into u's gradient, which
    mask only, twice), counted on the host."""
    w, plan = _loaded_plan(monkeypatch, "every_token_several_chunks")
    monkeypatch.setattr(moe, "ROW_BLOCK", 8)
    assert int(plan.dropped) == 0
    rows = plan.token.shape[0]
    assert rows == moe.plan_rows(40, 3, 2)   # the bound
    ran, real = [], moe._weigh

    def counted(*args):
        jax.debug.callback(lambda: ran.append(1))
        return real(*args)

    monkeypatch.setattr(moe, "_weigh", counted)
    jax.clear_caches()      # the stage is traced once a signature
    try:
        args = (jnp.asarray(w["u"]), plan, jnp.asarray(w["w1"][2:4]),
                jnp.asarray(w["w2"][2:4]))
        jax.block_until_ready(jax.jit(moe.experts)(*args))
        jax.effects_barrier()
        chunks = int(moe.plan_chunks(plan.group_sizes))
        blocks = int(moe.plan_blocks(plan.group_sizes, rows=rows))
        assert chunks == 5 and blocks == 10 and len(ran) == 2 * blocks
        jax.block_until_ready(jax.jit(jax.grad(
            lambda u: moe.experts(u, *args[1:]).sum()))(args[0]))
        jax.effects_barrier()
        # forward; forward again, the weights' gradient, the sums of dx
        assert len(ran) == (2 + 2 + 1 + 2) * blocks
    finally:
        jax.clear_caches()  # and no later test binds the counted one


def _layer(held=None, first=0, **kw):
    layer = zoo.LatentMoELayer(16, 8, 3, 8, 12, 24, 2.5, 1e-5,
                               experts_held=held, first_expert=first, **kw)
    layer.initialize(mx.initializer.Normal(0.3), ctx=mx.cpu())
    return layer


def _apply(layer, x, values=None):
    """layer(x) traced on raw values, its parameters from `values` (by
    the names hybrid_forward takes them) where given."""
    params = {id(p): jnp.asarray(values[n]) if values else p.data().data
              for n, p in layer._reg_params.items()}
    with ActiveTrace(params, train=False):
        out, stats = layer.forward(jnp.asarray(x))
    return np.asarray(out), np.asarray(stats)


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """Over all disjoint shares of the experts, the routed parts plus the
    shared expert counted once equal the uncut layer, which equals the
    plain reference's layer."""
    np.random.seed(11)
    whole = _layer(prefix="whole_")
    values = {n: np.asarray(p.data().data)
              for n, p in whole._reg_params.items()}
    x = np.random.RandomState(0).randn(2, 20, 16).astype(np.float32)
    full, stats = _apply(whole, x)
    assert stats[:-1].sum() == 40 * 3 and stats[-1] == 0

    cfg = {"num_experts_per_tok": 3, "routed_scaling_factor": 2.5}
    normed = reference.rms_norm(jnp.asarray(x.reshape(40, 16)),
                                values["norm_weight"], 1e-5)
    flat = {"l_" + n: jnp.asarray(v) for n, v in values.items()}
    want = x.reshape(40, 16) + np.asarray(
        reference.moe(flat, "l_", normed, cfg))
    np.testing.assert_allclose(full.reshape(40, 16), want, rtol=2e-4,
                               atol=2e-5)

    shared = np.asarray(
        reference.relu2(normed @ values["shared_up_weight"].T)
        @ values["shared_down_weight"].T).reshape(x.shape)
    total = np.zeros_like(full)
    for first in (0, 2, 4, 6):
        share = _layer(held=2, first=first, prefix=f"share{first}_")
        cut = dict(values,
                   experts_w1=values["experts_w1"][first:first + 2],
                   experts_w2=values["experts_w2"][first:first + 2])
        part, part_stats = _apply(share, x, cut)
        assert part_stats[-1] == 0
        np.testing.assert_array_equal(part_stats[:2],
                                      stats[first:first + 2])
        total += part - x - shared
    np.testing.assert_allclose(total + x + shared, full, rtol=2e-4,
                               atol=2e-5)


# ---- causal grouped-query attention -----------------------------------------

def test_causal_grouped_query_attention_values_gradients_and_route():
    rng = np.random.RandomState(2)
    b, h, kv, s, d = 2, 4, 2, 128, 128
    q = jnp.asarray(rng.randn(b, s, h * d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kv * d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kv * d), jnp.float32)
    ct = jnp.asarray(rng.randn(b, s, h * d), jnp.float32)

    def op(q, k, v, train):
        return apply_pure("dot_product_attention", q, k, v, None, None,
                          num_heads=h, num_kv_heads=kv, causal=True,
                          _train=train)

    def plain(q, k, v):
        def heads(x, n):
            return x.reshape(b, s, n, d).transpose(0, 2, 1, 3)
        kh, vh = (jnp.repeat(heads(x, kv), h // kv, axis=1) for x in (k, v))
        out = pa.dot_product_attention_ref(
            heads(q, h).reshape(b * h, s, d), kh.reshape(b * h, s, d),
            vh.reshape(b * h, s, d), None, d ** -0.5, causal=True)
        return out.reshape(b, h, s, d).transpose(0, 2, 1, 3).reshape(
            b, s, h * d)

    before = pa.route_counts()
    got = [op(q, k, v, train) for train in (True, False)]
    after = pa.route_counts()
    assert after["flash_causal"] == before["flash_causal"] + 2
    assert after["kernel_infer"] == before["kernel_infer"]
    for o in got:
        np.testing.assert_allclose(o, plain(q, k, v), rtol=2e-5, atol=2e-5)
    grads = [jax.grad(lambda *a: (f(*a) * ct).sum(), argnums=(0, 1, 2))(
        q, k, v) for f in (lambda *a: op(*a, True), plain)]
    for g, w in zip(*grads):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
    # a key mask keeps its old route
    after = pa.route_counts()
    apply_pure("dot_product_attention", q, k, v, jnp.ones((b, s)), None,
               num_heads=h, num_kv_heads=kv, causal=True)
    assert pa.route_counts()["flash_causal"] == after["flash_causal"]
    assert pa.route_counts()["kernel_infer"] == after["kernel_infer"] + 1


# ---- the whole model ---------------------------------------------------------

def _small_model(config, model_py):
    np.random.seed(5)
    mx.random.seed(5)
    step = model_py._step_block(config)
    step.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
    return step


def test_model_matches_the_plain_reference_logits_loss_and_gradients(
        reference, small_config):
    model_py = _load("model")
    step = _small_model(small_config, model_py)
    plist = sorted(step.collect_params().items())
    prefix = os.path.commonprefix([n for n, _ in plist])
    prefix = prefix[:prefix.rfind("_") + 1]
    values = {n: p.data().data for n, p in plist}
    named = {n[len(prefix):]: v for n, v in values.items()}
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, small_config["vocab_size"], (2, 32)), jnp.int32)

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
    trained = [n for n, p in plist if p.grad_req != "null"]
    assert len(trained) == len(plist) - small_config["pattern_held"].count("E")
    for n in trained:
        w = np.asarray(want[n[len(prefix):]])
        np.testing.assert_allclose(
            got[n], w, rtol=5e-3, atol=5e-3 * np.abs(w).max() + 1e-9,
            err_msg=n)


def test_step_program_holds_the_new_op_scopes_forward_and_backward(
        small_config):
    """`ssd_scan`, `causal_conv1d`, `moe_route`, `moe_experts` and the
    causal `dot_product_attention` under both `jvp(` and
    `transpose(jvp(`, inside their layer's block scope, with remat on as
    the cell runs it: what the cell's per-layer metrics are read by."""
    model_py = _load("model")
    np.random.seed(0)
    trainer = model_py.build(0, dict(small_config, dtype="float32"),
                             {"seq_len": 64, "batch": 1}, 1)
    assert trainer.remat
    tokens, = model_py.batch(0, small_config, {"seq_len": 64, "batch": 1},
                             np.asarray)
    first = float(trainer.step(tokens).asnumpy())
    assert np.isfinite(first)
    assert float(trainer.step(tokens).asnumpy()) < first
    names = set(spmd.step_programs()[-1]["ops"].values())

    def holds(*parts):
        return any(all(p in n for p in parts) for n in names)

    for layer, op in (("layer0_mamba", "ssd_scan"),
                      ("layer0_mamba", "causal_conv1d"),
                      ("layer1_moe", "moe_route"),
                      ("layer1_moe", "moe_experts"),
                      ("layer3_attn", "dot_product_attention"),
                      ("layer4_moe", "FullyConnected")):
        assert holds("/jvp(", f"/{layer}/{op}/"), (layer, op)
        # under remat the backward's names repeat the layer's path, with
        # `checkpoint` (and `rematted_computation` for the forward done
        # again) between the layer and the op
        assert holds("/transpose(jvp(", f"/{layer}/", f"/{op}/"), (layer, op)
    assert holds("/transpose(jvp(", "rematted_computation/ssd_scan/")
