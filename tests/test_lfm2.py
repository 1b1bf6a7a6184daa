"""The decoder of gated short convolutions, 64-wide grouped-query
attention and an expert layer without a shared expert (PR 41): the
`short_conv` op, the causal kernel route at head size 64, the norm a
head before the rotation, the tied head, the share of the experts tied
to the model, and the zoo's LFM2 stack against the benchmark's plain
reference."""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.block import F_PURE, ActiveTrace
from mxnet_tpu.gluon.model_zoo import joyai, laguna
from mxnet_tpu.gluon.model_zoo import lfm2 as zoo
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import residuals, rotary
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel import moe, spmd

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "benchmark")
_CONFIG_DIR = os.path.join(_BENCH, "configs", "lfm2_8b_a1b")


def _load(name):
    # model.py finds laguna_xs2's initializer through the harness
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    spec = importlib.util.spec_from_file_location(
        "lfm2_8b_a1b_" + name, os.path.join(_CONFIG_DIR, name + ".py"))
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


# ---- the gated short convolution ---------------------------------------------

@pytest.mark.parametrize("s, taps", [(37, 3), (2, 3), (1, 3), (16, 4)])
def test_short_conv_value_and_four_gradients(reference, s, taps):
    """C * conv(B * x~) and the gradients of B, C, x~ and the taps
    against the reference's shifted sum, at a sequence that is a multiple
    of nothing and at sequences shorter than the filter; positions 0 and
    1 see zeros to their left."""
    rng = np.random.RandomState(s + taps)
    b, d = 2, 8
    bcx = jnp.asarray(rng.randn(b, s, 3 * d), jnp.float32)
    w = jnp.asarray(rng.randn(d, taps), jnp.float32)
    ct = jnp.asarray(rng.randn(b, s, d), jnp.float32)
    got = apply_pure("short_conv", bcx, w)
    want = jnp.stack([reference.short_conv(row, w) for row in bcx])
    assert got.shape == (b, s, d) and got.dtype == bcx.dtype
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the left edge by hand: tap L-1 on the current position, L-2 on the
    # one before it, zeros before position 0
    gate, c, x = np.split(np.asarray(bcx), 3, axis=-1)
    z, wn = gate * x, np.asarray(w)
    np.testing.assert_allclose(got[:, 0], c[:, 0] * wn[:, -1] * z[:, 0],
                               rtol=1e-5, atol=1e-6)
    if s > 1:
        np.testing.assert_allclose(
            got[:, 1], c[:, 1] * (wn[:, -1] * z[:, 1] + wn[:, -2] * z[:, 0]),
            rtol=1e-5, atol=1e-6)
    grads = [jax.grad(lambda bcx, w: (f(bcx, w) * ct).sum(), argnums=(0, 1))(
        bcx, w) for f in (
            lambda bcx, w: apply_pure("short_conv", bcx, w),
            lambda bcx, w: jnp.stack([reference.short_conv(row, w)
                                      for row in bcx]))]
    (d_bcx, d_w), (want_bcx, want_w) = grads
    for part, want_part in zip(jnp.split(d_bcx, 3, -1),
                               jnp.split(want_bcx, 3, -1)):    # B, C, x~
        assert np.abs(np.asarray(want_part)).max() > 0
        np.testing.assert_allclose(part, want_part, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(d_w, want_w, rtol=1e-5, atol=1e-5)


def test_short_conv_rounds_once_and_refuses_other_shapes():
    """bfloat16 in, bfloat16 out and bfloat16 gradients, float32 between;
    streams that are not three times the taps' channels are refused."""
    rng = np.random.RandomState(0)
    bcx = jnp.asarray(rng.randn(1, 9, 24), jnp.bfloat16)
    w = jnp.asarray(rng.randn(8, 3), jnp.bfloat16)
    got, pull = jax.vjp(lambda *a: apply_pure("short_conv", *a), bcx, w)
    exact = apply_pure("short_conv", bcx.astype(jnp.float32),
                       w.astype(jnp.float32))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(exact.astype(jnp.bfloat16),
                                             np.float32))
    d_bcx, d_w = pull(jnp.ones_like(got))
    assert d_bcx.dtype == d_w.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="short_conv: streams"):
        apply_pure("short_conv", bcx, w[:7])


# ---- the causal core at head size 64 -----------------------------------------

def _oracle(q, k, v, heads, kv_heads):
    """Dense-masked float32 grouped-query attention, packed in and out."""
    b, s, _ = q.shape
    q = q.reshape(b, s, heads, -1)
    k, v = (jnp.repeat(x.reshape(b, s, kv_heads, -1), heads // kv_heads, 2)
            for x in (k, v))
    score = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(score, -1), v)
    return out.reshape(b, s, -1)


# (S, query heads, key/value heads, the route, interpreter)
_CORE = {
    "kernels_grouped_4_to_1": (256, 8, 2, "flash_causal", True),
    "kernels_s_not_a_multiple_of_1024": (384, 4, 1, "flash_causal", True),
    "kernel_twin_on_cpu": (128, 4, 2, "flash_causal", False),
    "s_no_multiple_of_128_takes_the_dense_form": (40, 4, 2, "kernel_infer",
                                                  False),
}


@pytest.mark.parametrize("case", list(_CORE))
def test_causal_core_at_head_size_64_matches_the_dense_oracle(monkeypatch,
                                                              case):
    """Value and the three gradients of `dot_product_attention(causal)`
    at 64-wide heads, grouped: the splash multi-query kernels under the
    Pallas interpreter (operands 64 lanes wide, as they come), their XLA
    twin in a program lowered for the CPU, and the dense form for shapes
    the kernels do not take."""
    s, h, kv, route, interpret = _CORE[case]
    if interpret:
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(s + h)
    b, d = 2, 64
    q, k, v, ct = (jnp.asarray(rng.randn(b, s, n * d), jnp.float32)
                   for n in (h, kv, kv, h))

    def op(q, k, v):
        return apply_pure("dot_product_attention", q, k, v, None,
                          causal=True, num_heads=h, num_kv_heads=kv)

    before = pa.route_counts()
    got = op(q, k, v)
    after = pa.route_counts()
    assert after[route] == before[route] + 1
    assert sum(after.values()) == sum(before.values()) + 1
    np.testing.assert_allclose(got, _oracle(q, k, v, h, kv), rtol=2e-5,
                               atol=2e-5)
    grads = [jax.grad(lambda *a: (f(*a) * ct).sum(), argnums=(0, 1, 2))(
        q, k, v) for f in (op, lambda *a: _oracle(*a, h, kv))]
    for g, w in zip(*grads):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


def test_the_64_wide_route_leaves_the_other_shapes_where_they_were():
    """Whole 128-lane values with whole or half queries, as before, and
    now 64 + 64; nothing else: 192-wide values, 64-wide queries beside
    128-wide values the other way round, 32."""
    assert pa._causal_flash_shape(32, 8, 8192, 8192, 64)
    assert pa._causal_flash_shape(48, 8, 8192, 8192, 128)
    assert pa._causal_flash_shape(32, 32, 8192, 8192, 192, 128)
    assert pa._causal_flash_shape(32, 32, 8192, 8192, 64, 128)
    assert not pa._causal_flash_shape(48, 8, 8192, 8192, 192)
    assert not pa._causal_flash_shape(32, 8, 8192, 8192, 128, 64)
    assert not pa._causal_flash_shape(32, 8, 8192, 8192, 32)
    assert not pa._causal_flash_shape(32, 8, 8200, 8200, 64)
    assert not pa._causal_flash_shape(32, 5, 8192, 8192, 64)


# ---- layers ------------------------------------------------------------------

_WIDTHS = dict(hidden_size=128, eps=1e-5, num_heads=2, num_kv_heads=1,
               conv_taps=3)
_SPARSE = dict(num_experts=16, top_k=3, expert_size=24, scale=1.0)
_CFG = {"num_attention_heads": 2, "num_key_value_heads": 1,
        "norm_eps": 1e-5, "rope_theta": 1e6, "num_experts_per_tok": 3,
        "routed_scaling_factor": 1.0}


def _layer(kind, sparse, held=None, first=0, **kw):
    mlp = dict(_SPARSE, experts_held=held, first_expert=first) if sparse \
        else dict(mlp_size=40)
    layer = zoo.Lfm2Layer(kind=kind, **_WIDTHS, **mlp, **kw)
    layer.initialize(mx.initializer.Normal(0.3), ctx=mx.cpu())
    return layer


def _apply(layer, x, tables, values=None):
    params = {id(p): jnp.asarray(values[n]) if values else p.data().data
              for n, p in layer._reg_params.items()}
    with ActiveTrace(params, train=False):
        out = layer.forward(jnp.asarray(x), *tables)
    return [np.asarray(o) for o in out] if isinstance(out, (list, tuple)) \
        else [np.asarray(out)]


def _tables(s, d=64):
    return rotary.rotary_tables(rotary.default_inv_freq(1e6, d), s)


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("kind", zoo.KINDS)
def test_a_layer_hands_out_its_operators_own_output(reference, kind, sparse):
    """`operator_output`: the layer's result is what it was and the
    operator's, operator(RMSNorm(h)), comes last beside it."""
    np.random.seed(3)
    plain = _layer(kind, sparse, prefix="l_")
    np.random.seed(3)
    probed = _layer(kind, sparse, prefix="l_", operator_output=True)
    values = {n: p.data().data for n, p in plain._reg_params.items()}
    s = 20
    x = np.random.RandomState(1).randn(2, s, 128).astype(np.float32)
    want = _apply(plain, x, _tables(s))
    got = _apply(probed, x, _tables(s), values)
    assert len(got) == len(want) + 1
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    flat = {"l_" + n: v for n, v in values.items()}
    operator = reference.convolution if kind == "conv" \
        else reference.attention
    np.testing.assert_allclose(got[-1], np.stack([operator(
        flat, "l_", reference.rms_norm(jnp.asarray(row),
                                       flat["l_norm_weight"], 1e-5), _CFG)
        for row in x]), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("kind", zoo.KINDS)
def test_a_layer_is_the_plain_references_layer(reference, kind, sparse):
    """One conv layer and one attention layer, over the dense MLP and
    over the expert layer (all 16 experts held, no shared expert)."""
    np.random.seed(3)
    layer = _layer(kind, sparse, prefix="l_")
    names = set(layer._reg_params)
    assert ("conv_weight" in names) == (kind == "conv")
    assert ("q_norm_weight" in names) == (kind != "conv")
    assert not any(n.startswith("shared_") for n in names)
    assert ("experts_w1" in names) == sparse
    s = 20
    x = np.random.RandomState(1).randn(2, s, 128).astype(np.float32)
    got = _apply(layer, x, _tables(s))
    flat = {"l_" + n: p.data().data for n, p in layer._reg_params.items()}
    want = np.stack([reference.layer(flat, "l_", jnp.asarray(row), kind,
                                     sparse, _CFG) for row in x])
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    if sparse:
        assert got[1][:-1].sum() == 2 * s * 3 and got[1][-1] == 0


def test_the_norm_a_head_comes_before_the_rotation(reference, monkeypatch):
    saved_rotate = reference.rotate
    """q and k pass an RMSNorm over each head's 64 dimensions, one gain
    for q and one for k, and are rotated afterwards: the layer agrees
    with the reference, and a reference that leaves the norms out, or
    norms after the rotation with a gain that is not constant over a
    pair, does not."""
    np.random.seed(4)
    layer = _layer("full_attention", False, prefix="l_")
    rng = np.random.RandomState(2)
    values = {n: np.asarray(p.data().data)
              for n, p in layer._reg_params.items()}
    for name in ("q_norm_weight", "k_norm_weight"):     # gains that matter
        values[name] = rng.uniform(0.5, 1.5, 64).astype(np.float32)
    s = 24
    u = jnp.asarray(rng.randn(1, s, 128), jnp.float32)
    got = np.asarray(layer.attend(
        F_PURE, u, *_tables(s),
        *(jnp.asarray(values[n]) for n in layer._operator)))[0]
    flat = {"l_" + n: jnp.asarray(v) for n, v in values.items()}

    def attention(**patched):
        with monkeypatch.context() as patch:
            for name, function in patched.items():
                patch.setattr(reference, name, function)
            return np.asarray(reference.attention(flat, "l_", u[0], _CFG))

    np.testing.assert_allclose(got, attention(), rtol=2e-4, atol=2e-5)
    for wrong in (
            dict(head_norm=lambda x, w, eps: x),
            dict(rotate=lambda x, theta: x),
            dict(head_norm=lambda x, w, eps: x,
                 rotate=lambda x, theta: reference.rms_norm(
                     saved_rotate(x, theta), flat["l_q_norm_weight"],
                     1e-5))):
        assert np.abs(got - attention(**wrong)).max() > 1e-2, list(wrong)


# ---- the share tied to the model ---------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer(reference):
    """16 experts, top-3, 4 shares of 4 (first_expert 0, 4, 8, 12): the
    routed parts the shares give add up to the uncut layer, which is the
    plain reference's layer.  There is no shared expert, so nothing but
    what goes in (the residual after the operator) is counted once."""
    np.random.seed(13)
    whole = _layer("conv", True, prefix="whole_")
    values = {n: np.asarray(p.data().data)
              for n, p in whole._reg_params.items()}
    s = 24
    x = np.random.RandomState(0).randn(2, s, 128).astype(np.float32)
    full, stats = _apply(whole, x, _tables(s))
    assert stats[:-1].sum() == 2 * s * 3 and stats[-1] == 0

    flat = {"l_" + n: jnp.asarray(v) for n, v in values.items()}
    want = np.stack([reference.layer(flat, "l_", jnp.asarray(row), "conv",
                                     True, _CFG) for row in x])
    np.testing.assert_allclose(full, want, rtol=2e-4, atol=2e-5)
    alike = np.stack([row + reference.convolution(
        flat, "l_", reference.rms_norm(jnp.asarray(row),
                                       flat["l_norm_weight"], 1e-5), _CFG)
        for row in x])

    total = np.zeros_like(full)
    for first in range(0, 16, 4):
        share = _layer("conv", True, held=4, first=first,
                       prefix=f"share{first}_")
        cut = dict(values,
                   experts_w1=values["experts_w1"][first:first + 4],
                   experts_w2=values["experts_w2"][first:first + 4])
        part, part_stats = _apply(share, x, _tables(s), cut)
        assert part_stats[-1] == 0
        np.testing.assert_array_equal(part_stats[:4],
                                      stats[first:first + 4])
        # the reference's share is the same share
        want_part = np.stack([reference.layer(
            {**flat, "l_experts_w1": jnp.asarray(cut["experts_w1"]),
             "l_experts_w2": jnp.asarray(cut["experts_w2"])}, "l_",
            jnp.asarray(row), "conv", True, _CFG, first) for row in x])
        np.testing.assert_allclose(part, want_part, rtol=2e-4, atol=2e-5)
        total += part - alike
    assert np.abs(total).max() > 1e-3
    np.testing.assert_allclose(total + alike, full, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("seed", [0, 1, 7, 2147500001])
def test_a_sparse_layer_takes_one_trip_at_the_cells_load(seed):
    """The cell's routed shape (16,384 tokens, top-4 of 32, experts 0-7
    held, the load stated as the layer states it): whatever the seed
    draws, the rows fit the one chunk `row_chunk` makes of the expected
    16,384, so the expert loop runs once a layer."""
    rng = np.random.RandomState(seed % 2 ** 32)
    tokens, width = 16384, 32
    plan = moe.route(jnp.asarray(rng.randn(tokens, width), jnp.float32),
                     jnp.asarray(rng.randn(32, width) * 0.02, jnp.float32),
                     jnp.zeros((32,), jnp.float32), top_k=4, scale=1.0,
                     first_expert=0, n_local=8)
    layer = zoo.Lfm2Layer(2048, 1e-5, "conv", 32, 8, num_experts=32,
                          top_k=4, expert_size=1792, experts_held=8)
    expected = int(tokens * layer._held_share)
    assert expected == 16384 and moe.row_chunk(expected) == 32768
    rows = int(np.asarray(plan.group_sizes).sum())
    assert 0 < rows <= 32768 and int(plan.dropped) == 0
    assert int(moe.plan_chunks(plan.group_sizes, expected)) == 1
    # without the stated load the same rows would take several trips
    assert int(moe.plan_chunks(plan.group_sizes)) == -(-rows // moe.ROW_CHUNK)


# ---- the whole model ---------------------------------------------------------

def _small_model(config, model_py):
    np.random.seed(5)
    mx.random.seed(5)
    step = model_py._step_block(config)
    step.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
    return step


def _named(step):
    plist = sorted(step.collect_params().items())
    prefix = os.path.commonprefix([n for n, _ in plist])
    prefix = prefix[:prefix.rfind("_") + 1]
    return plist, prefix


def test_model_matches_the_plain_reference_logits_loss_and_gradients(
        reference, small_config):
    """A dense conv layer, an attention layer and two conv layers over a
    share of the experts at the rehearsal size, the head tied: logits,
    the loss, every gradient."""
    model_py = _load("model")
    step = _small_model(small_config, model_py)
    plist, prefix = _named(step)
    values = {n: p.data().data for n, p in plist}
    named = {n[len(prefix):]: v for n, v in values.items()}
    assert {"embed_weight", "head_norm_weight", "layer0_conv_weight",
            "layer1_q_norm_weight", "layer2_experts_w1"} <= set(named)
    assert "head_weight" not in named           # one array, the embedding's
    assert not any("shared_" in n for n in named)
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, small_config["vocab_size"], (2, 256)), jnp.int32)

    def system(values):
        trace = ActiveTrace({id(p): values[n] for n, p in plist},
                            train=True)
        with trace:
            loss, logits, stats, operator = step.forward(tokens)
        return loss, (logits, stats, operator)

    def plain(named):
        scores = reference.logits(named, tokens, small_config)
        return reference.loss_of(scores, tokens), (
            scores, reference.operator_outputs(named, tokens, small_config,
                                               model_py._probed_layer(
                                                   small_config)))

    (loss, (logits, stats, operator)), got = jax.jit(
        jax.value_and_grad(system, has_aux=True))(values)
    (want_loss, (want_logits, want_operator)), want = jax.jit(
        jax.value_and_grad(plain, has_aux=True))(named)
    # the probe `reference_check` compares: the first attention layer's
    np.testing.assert_allclose(operator, want_operator, rtol=2e-3,
                               atol=2e-6)
    sparse = len(small_config["layer_types"]) \
        - small_config["num_dense_layers"]
    assert stats.shape == (sparse, small_config["num_experts"] + 1)
    assert (np.asarray(stats)[:, -1] == 0).all()
    np.testing.assert_allclose(logits, want_logits, rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    trained = [n for n, p in plist if p.grad_req != "null"]
    assert len(trained) == len(plist) - sparse      # the selection biases
    for n in trained:
        w = np.asarray(want[n[len(prefix):]])
        assert np.abs(w).max() > 0, n
        np.testing.assert_allclose(
            got[n], w, rtol=5e-3, atol=5e-3 * np.abs(w).max() + 1e-9,
            err_msg=n)


def test_the_tied_arrays_gradient_is_the_sum_of_both_uses(small_config):
    """The head reads the embedding's Parameter: one array in the model,
    and its gradient is the lookup's plus the projection's."""
    model_py = _load("model")
    step = _small_model(small_config, model_py)
    model = step.model
    assert model.head.weight is model.embed.weight
    plist, prefix = _named(step)
    assert sum(n.endswith("embed_weight") for n, _ in plist) == 1
    assert len({id(p) for _, p in plist}) == len(plist)
    assert not any(n.endswith("head_weight") for n, _ in plist)
    values = {n: p.data().data for n, p in plist}
    tokens = jnp.asarray(np.random.RandomState(1).randint(
        0, small_config["vocab_size"], (1, 256)), jnp.int32)
    tied = prefix + "embed_weight"

    def loss(values, lookup, projection):
        """The step's loss with the array's two uses fed apart."""
        trace = ActiveTrace({id(p): values[n] for n, p in plist},
                            train=True)
        with trace:
            h = jnp.take(lookup, tokens, axis=0)
            tables = _tables(256, small_config["hidden_size"]
                             // small_config["num_attention_heads"])
            for layer in model.layers._children.values():
                out = layer(h, *tables)
                h = out[0] if isinstance(out, (list, tuple)) else out
            from mxnet_tpu.ops.nn import _rms_norm
            scores = _rms_norm(h, values[prefix + "head_norm_weight"],
                               eps=small_config["norm_eps"]) @ projection.T
        logp = jax.nn.log_softmax(scores[:, :-1].astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

    def whole(values):
        with ActiveTrace({id(p): values[n] for n, p in plist}, train=True):
            return step.forward(tokens)[0]

    want_loss, got = jax.value_and_grad(whole)(values)
    apart_loss, (by_lookup, by_projection) = jax.value_and_grad(
        loss, argnums=(1, 2))(values, values[tied], values[tied])
    np.testing.assert_allclose(apart_loss, want_loss, rtol=1e-6)
    assert np.abs(by_lookup).max() > 0 and np.abs(by_projection).max() > 0
    np.testing.assert_allclose(got[tied], by_lookup + by_projection,
                               rtol=1e-4, atol=1e-7)


def test_step_program_holds_the_new_scopes_forward_and_backward(
        small_config):
    """`short_conv` under the conv operator's scope with its two
    projections, `rotary_embedding`, `dot_product_attention`,
    `moe_route` and `moe_experts`, under both `jvp(` and
    `transpose(jvp(`, inside their layer's block scope, with remat on as
    the cell runs it: what the cell's per-layer metrics are read by.
    The attention layer's segment keeps what its kernel wrote, the conv
    layers' keep nothing; the router's weight and bias stay float32
    under the cast; the tied array is placed once."""
    model_py = _load("model")
    traffic = {"seq_len": 256, "batch": 1}
    before, kept = pa.route_counts(), residuals.kept_residuals()
    turned = rotary.route_counts()
    trainer = model_py.build(0, small_config, traffic, 1)
    assert trainer.remat
    for name, value in trainer.params.items():
        want = jnp.float32 if "router_" in name else jnp.bfloat16
        assert value.dtype == want, name
    assert not any("head_weight" in n for n in trainer.params)
    tokens, = model_py.batch(0, small_config, traffic, np.asarray)
    first = float(trainer.step(tokens).asnumpy())
    assert np.isfinite(first)
    assert float(trainer.step(tokens).asnumpy()) < first
    after = pa.route_counts()
    attentions = small_config["layer_types"].count("full_attention")
    assert after["flash_causal"] == before["flash_causal"] + attentions
    assert after["reference"] == before["reference"]
    assert after["kernel_infer"] == before["kernel_infer"]
    assert rotary.route_counts() == {"kernel": turned["kernel"]
                                     + 2 * attentions, "xla": turned["xla"]}
    now = residuals.kept_residuals()
    heads = small_config["num_attention_heads"]
    head = small_config["hidden_size"] // heads
    grown = {k: now["flash_causal"][k] - kept["flash_causal"][k]
             for k in now["flash_causal"]}
    # o in bfloat16 and float32 rows of logsumexp, an attention layer
    assert grown == {"values": 2 * attentions,
                     "bytes": attentions * 256 * heads * (2 * head + 4)}
    assert {k: v for k, v in now.items() if k != "flash_causal"} == \
        {k: v for k, v in kept.items() if k != "flash_causal"}
    names = set(spmd.step_programs()[-1]["ops"].values())

    def holds(*parts):
        return any(all(p in n for p in parts) for n in names)

    conv = zoo.CONV_NAME
    for layer, op in (("layer0", f"{conv}/short_conv"),
                      ("layer0", f"{conv}/FullyConnected"),
                      ("layer2", f"{conv}/short_conv"),
                      ("layer1", "RMSNorm"),
                      ("layer1", "rotary_embedding"),
                      ("layer1", "dot_product_attention"),
                      ("layer1", "moe_route"),
                      ("layer2", "moe_experts")):
        # (the CPU's fusions take the forward pass's few elementwise
        # instructions of `short_conv` into the projection after them)
        assert holds("/jvp(", f"/{layer}/{op}/") or "short_conv" in op, \
            (layer, op)
        assert holds("/transpose(jvp(", f"/{layer}/", f"/{op}/"), (layer, op)
    # the MLP half and the attention operator are outside the conv scope
    assert not holds(f"/{conv}/moe_experts/")
    assert not holds(f"/{conv}/dot_product_attention/")
    assert not holds("/layer1/", f"/{conv}/")
    assert holds("/transpose(jvp(", "rematted_computation/", "short_conv/")


# ---- what the shared code still does for the other decoders -------------------

def test_the_shared_expert_and_the_untied_head_are_where_they_were():
    """`MLPLayer` with a shared expert builds the parameters it built, in
    the order it built them, and adds the shared expert's part; `Head`
    without `tied` owns its array."""
    sparse = dict(num_experts=8, top_k=2, expert_size=8, shared_size=8,
                  scale=2.0)
    layers = {
        "laguna": laguna.LagunaLayer(16, 2, 1, 8, 1e-6, **sparse),
        "joyai": joyai.LatentLayer(16, 2, 12, 8, 8, 4, 8, 1e-6, **sparse),
    }
    tail = ["mlp_norm_weight", "router_weight", "router_bias", "experts_w1",
            "experts_w2", "shared_gate_weight", "shared_up_weight",
            "shared_down_weight"]
    for name, layer in layers.items():
        assert list(layer._reg_params)[-8:] == tail, name
    model = laguna.LagunaModel(
        vocab_size=32, hidden_size=16, intermediate_size=24,
        num_attention_heads_per_layer=[2], num_key_value_heads=1,
        head_dim=8, layer_types=["full_attention"],
        mlp_layer_types=["dense"], sliding_window=8,
        rope_parameters={"full_attention": {"rope_theta": 1e4}},
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=8,
        shared_expert_intermediate_size=8, moe_routed_scaling_factor=1.0)
    names = list(model.collect_params())
    assert sum(n.endswith("head_weight") for n in names) == 1
    assert model.head.weight is not model.embed.weight
    # the shared expert's part is added: zero its down projection and the
    # layer's output moves
    np.random.seed(2)
    layer = layers["laguna"]
    layer.initialize(mx.initializer.Normal(0.3), ctx=mx.cpu())
    x = np.random.RandomState(0).randn(1, 8, 16).astype(np.float32)
    tables = rotary.rotary_tables(rotary.default_inv_freq(1e4, 8), 8)
    values = {n: np.asarray(p.data().data)
              for n, p in layer._reg_params.items()}
    with_shared, _ = _apply(layer, x, tables, values)
    without, _ = _apply(layer, x, tables, dict(
        values, shared_down_weight=0 * values["shared_down_weight"]))
    assert np.abs(with_shared - without).max() > 1e-3


def _experts_before_pr41(self, F, u, router_weight, router_bias, experts_w1,
                         experts_w2, shared_gate_weight, shared_up_weight,
                         shared_down_weight):
    """`MLPLayer.experts` as PR 40 had it: the shared expert always."""
    from mxnet_tpu.gluon.model_zoo._decoder import gated_mlp

    b, s = u.shape[0], u.shape[1]
    tokens = F.reshape(u, shape=(b * s, self._hidden))
    token, weight, group_sizes, dropped = F.moe_route(
        tokens, router_weight, router_bias, top_k=self._top_k,
        scale=self._scale, first_expert=self._first,
        num_local=self._held)
    out = F.moe_experts(tokens, token, weight, group_sizes, experts_w1,
                        experts_w2, form="silu_gated",
                        expected_rows=int(b * s * self._held_share)) \
        + gated_mlp(F, tokens, shared_gate_weight, shared_up_weight,
                    shared_down_weight)
    stats = F.concat(group_sizes, F.reshape(dropped, shape=(1,)), dim=0)
    return F.reshape(out, shape=(b, s, self._hidden)), stats


@pytest.mark.parametrize("family", ["laguna", "joyai"])
def test_a_layer_with_a_shared_expert_lowers_to_the_program_it_did(
        family, monkeypatch):
    """A sparse layer of `laguna.py` and of `joyai.py`, value and
    gradients at 128-wide heads (the causal kernel route as before): the
    lowered program is, character for character, the one the layer
    lowered to with PR 40's `experts`."""
    from mxnet_tpu.gluon.model_zoo import _decoder

    sparse = dict(num_experts=8, top_k=2, expert_size=16, shared_size=16,
                  scale=2.0, experts_held=4)

    def lowered():
        if family == "laguna":
            layer = laguna.LagunaLayer(256, 2, 1, 128, 1e-6, prefix="l_",
                                       **sparse)
            tables = rotary.rotary_tables(
                rotary.default_inv_freq(1e4, 128), 128)
        else:
            layer = joyai.LatentLayer(256, 2, 48, 32, 128, 64, 128, 1e-6,
                                      prefix="l_", **sparse)
            tables = rotary.rotary_tables(
                rotary.default_inv_freq(1e4, 64), 128, interleaved=True)
        np.random.seed(1)
        layer.initialize(mx.initializer.Normal(0.1), ctx=mx.cpu())
        names = list(layer._reg_params)
        values = [p.data().data for p in layer._reg_params.values()]

        def loss(values, x, cos, sin):      # no array closed over
            params = {id(p): v for p, v in zip(layer._reg_params.values(),
                                               values)}
            with ActiveTrace(params, train=True):
                out, _stats = layer.forward(x, cos, sin)
            return (out ** 2).sum()

        before = pa.route_counts()
        text = jax.jit(jax.value_and_grad(loss)).lower(
            values, jnp.ones((1, 128, 256), jnp.float32), *tables).as_text()
        route = "flash_causal" if family == "laguna" else "latent_splash"
        assert pa.route_counts()[route] == before[route] + 1
        return names, text

    names, text = lowered()
    monkeypatch.setattr(_decoder.MLPLayer, "experts", _experts_before_pr41)
    assert lowered() == (names, text)
    assert "shared_down_weight" in names
