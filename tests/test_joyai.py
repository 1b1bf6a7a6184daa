"""The latent-attention decoder with a multi-token-prediction module
(PR 39): the `latent_attention` routes, the interleaved rotary pairing,
the share of the experts tied to the model, and the zoo's JoyAI stack
against the benchmark's plain reference."""
import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.block import ActiveTrace
from mxnet_tpu.gluon.model_zoo import joyai as zoo
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import residuals, rotary
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel import moe, spmd

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "benchmark")
_CONFIG_DIR = os.path.join(_BENCH, "configs", "joyai_llm_flash")


def _load(name):
    # model.py finds laguna_xs2's initializer through the harness
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    spec = importlib.util.spec_from_file_location(
        "joyai_llm_flash_" + name, os.path.join(_CONFIG_DIR, name + ".py"))
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


# ---- the latent core ---------------------------------------------------------

def _latent_oracle(q, k_nope, k_rope, v, heads):
    """Dense-masked float32 attention: every head's key is its own part
    beside the one all heads share."""
    b, s, _ = q.shape
    rope = k_rope.shape[-1]
    q = q.reshape(b, s, heads, -1)
    k = jnp.concatenate(
        [k_nope.reshape(b, s, heads, -1),
         jnp.broadcast_to(k_rope[:, :, None], (b, s, heads, rope))], -1)
    score = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(score, -1),
                     v.reshape(b, s, heads, -1))
    return out.reshape(b, s, -1)


# (S, nope, rope, value size, the route the call is counted under,
# interpreter)
_LATENT = {
    "kernel_at_the_published_head_sizes": (256, 128, 64, 128,
                                           "latent_splash", True),
    "kernel_twin_on_cpu": (128, 128, 64, 128, "latent_splash", False),
    "kernel_s_not_a_multiple_of_1024": (384, 64, 64, 128, "latent_splash",
                                        True),
    "odd_shape_takes_xla": (40, 16, 8, 24, "latent_xla", False),
}


@pytest.mark.parametrize("case", list(_LATENT))
def test_latent_attention_matches_the_dense_oracle(monkeypatch, case):
    """Value and the four gradients (q, every head's keys, the one shared
    rotary key, v) against a dense mask: the splash kernels with a value
    size of their own under the Pallas interpreter, their XLA twin in a
    program lowered for the CPU, and the XLA route for shapes the kernels
    do not take."""
    s, nope, rope, vd, route, interpret = _LATENT[case]
    if interpret:
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(s + rope)
    b, h = 2, 3
    q, k_nope, k_rope, v, ct = (
        jnp.asarray(rng.randn(b, s, width), jnp.float32)
        for width in (h * (nope + rope), h * nope, rope, h * vd, h * vd))

    def op(*arrays):
        return apply_pure("latent_attention", *arrays, num_heads=h)

    before = pa.route_counts()
    got = op(q, k_nope, k_rope, v)
    after = pa.route_counts()
    assert after[route] == before[route] + 1
    assert sum(after.values()) == sum(before.values()) + 1
    assert got.shape == (b, s, h * vd)
    np.testing.assert_allclose(got, _latent_oracle(q, k_nope, k_rope, v, h),
                               rtol=2e-5, atol=2e-5)
    grads = [jax.grad(lambda *a: (f(*a) * ct).sum(), argnums=(0, 1, 2, 3))(
        q, k_nope, k_rope, v)
        for f in (op, lambda *a: _latent_oracle(*a, h))]
    for g, w in zip(*grads):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


def test_latent_attention_refuses_keys_that_do_not_fit_the_queries():
    x = jnp.zeros((1, 128, 2 * 192), jnp.float32)
    with pytest.raises(ValueError, match="latent_attention: keys"):
        apply_pure("latent_attention", x, x, jnp.zeros((1, 128, 64)), x,
                   num_heads=2)
    assert set(residuals.NAMES) <= set(pa.ROUTES)
    assert pa._causal_flash_shape(32, 32, 8192, 8192, 192, 128)
    # one size as before: whole 128-lane blocks, or (PR 41) 64 + 64
    assert pa._causal_flash_shape(48, 8, 8192, 8192, 128)
    assert not pa._causal_flash_shape(48, 8, 8192, 8192, 192)
    assert pa._causal_flash_shape(48, 8, 8192, 8192, 64)
    assert not pa._causal_flash_shape(48, 8, 8192, 8192, 128, 64)


def test_latent_projection_is_the_two_low_rank_chains(reference):
    """q, k_nope, the unrotated shared key and v against the plain
    formulas, with both inner norms; the weights' rows in the published
    order ([nope ; rope] a query head, [latent ; rope], [key ; value] a
    head)."""
    rng = np.random.RandomState(4)
    b, s, d, h, q_rank, kv_rank, nope, rope, vd = 2, 6, 16, 3, 12, 8, 4, 2, 5
    x = rng.randn(b, s, d).astype(np.float32)
    w = dict(q_a=rng.randn(q_rank, d), q_norm=rng.rand(q_rank) + 0.5,
             q_b=rng.randn(h * (nope + rope), q_rank),
             kv_a=rng.randn(kv_rank + rope, d),
             kv_norm=rng.rand(kv_rank) + 0.5,
             kv_b=rng.randn(h * (nope + vd), kv_rank))
    w = {k: jnp.asarray(a, jnp.float32) for k, a in w.items()}
    q, k_nope, k_rope, v = apply_pure(
        "latent_projection", jnp.asarray(x), *w.values(), num_heads=h,
        nope_dim=nope, rope_dim=rope, eps=1e-6)
    c_q = reference.rms_norm(x @ w["q_a"].T, w["q_norm"], 1e-6)
    latent = x @ w["kv_a"].T
    kv = (reference.rms_norm(latent[..., :kv_rank], w["kv_norm"], 1e-6)
          @ w["kv_b"].T).reshape(b, s, h, nope + vd)
    for got, want in ((q, c_q @ w["q_b"].T),
                      (k_nope, kv[..., :nope].reshape(b, s, -1)),
                      (k_rope, latent[..., kv_rank:]),
                      (v, kv[..., nope:].reshape(b, s, -1))):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="latent_projection"):
        apply_pure("latent_projection", jnp.asarray(x), *w.values(),
                   num_heads=h, nope_dim=nope + 1, rope_dim=rope)


# ---- the interleaved pairing -------------------------------------------------

def test_interleaved_rotation_is_the_complex_number_form():
    """The last 64 dimensions of every query head and the one 64-wide
    key, pairs (2i, 2i + 1) as complex numbers times e^{j p f_i}; the
    128 before them in a query pass through."""
    rng = np.random.RandomState(7)
    b, s, h, nope, r = 2, 10, 3, 128, 64
    q = rng.randn(b, s, h * (nope + r)).astype(np.float32)
    k = rng.randn(b, s, r).astype(np.float32)
    inv_freq = rotary.default_inv_freq(32e6, r)
    cos, sin = rotary.rotary_tables(inv_freq, s, interleaved=True)
    assert cos.shape == (s, r)
    np.testing.assert_array_equal(cos[:, 0::2], cos[:, 1::2])
    got_q, got_k = apply_pure(
        "rotary_embedding", jnp.asarray(q), jnp.asarray(k), cos, sin,
        num_heads=h, num_kv_heads=1, interleaved=True, rotate_last=True)
    turn = np.exp(1j * np.arange(s)[:, None]
                  * np.asarray(inv_freq, np.float32)[None])      # (S, r/2)

    def turned(x):      # (..., S, heads, r) real -> complex -> real
        z = (x[..., 0::2] + 1j * x[..., 1::2]) * turn[:, None, :]
        return np.stack([z.real, z.imag], -1).reshape(x.shape)

    q4 = q.reshape(b, s, h, nope + r)
    got_q = np.asarray(got_q).reshape(b, s, h, nope + r)
    np.testing.assert_array_equal(got_q[..., :nope], q4[..., :nope])
    np.testing.assert_allclose(got_q[..., nope:], turned(q4[..., nope:]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(got_k).reshape(b, s, 1, r), turned(k.reshape(b, s, 1, r)),
        rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="tables"):   # r over the key's size
        apply_pure("rotary_embedding", jnp.asarray(q), jnp.asarray(k[..., :32]),
                   cos, sin, num_heads=h, num_kv_heads=1)


@pytest.mark.parametrize("r", [64, 128])
def test_default_pairing_is_bit_for_bit_what_it_was(r):
    """Rotate-half over the first r dimensions, as laguna_xs2 and evabyte
    trace it: the same signed permutation, the same tables and the same
    values as the form the op had before it learned a second pairing."""
    d, s, h = 128, 9, 2
    old = np.zeros((d, d), np.float32)          # PR 31's `_half_turn`
    for i in range(r // 2):
        old[i + r // 2, i] = -1.0
        old[i, i + r // 2] = 1.0
    np.testing.assert_array_equal(rotary._partner(d, r), old)
    inv_freq = rotary.default_inv_freq(10000.0, r)
    cos, sin = rotary.rotary_tables(inv_freq, s)
    angle = (np.arange(s, dtype=np.float32)[:, None]
             * np.asarray(inv_freq, np.float32)[None])
    np.testing.assert_array_equal(
        cos, jnp.cos(jnp.concatenate([angle, angle], -1)))
    x = jnp.asarray(np.random.RandomState(r).randn(2, s, h * d), jnp.bfloat16)

    def before(x):
        x4 = x.reshape(2, s, h, d)
        partner = jnp.einsum("bshd,de->bshe", x4, jnp.asarray(old, x.dtype),
                             preferred_element_type=jnp.float32)
        c = jnp.pad(cos, ((0, 0), (0, d - r)), constant_values=1.0)
        sn = jnp.pad(sin, ((0, 0), (0, d - r)))
        out = x4.astype(jnp.float32) * c[:, None] + partner * sn[:, None]
        return out.astype(x.dtype).reshape(x.shape)

    got, _ = apply_pure("rotary_embedding", x, x, cos, sin, num_heads=h)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(jax.jit(before)(x), np.float32))


# ---- the share tied to the model ---------------------------------------------

_SHARE = dict(hidden_size=16, num_heads=2, q_rank=12, kv_rank=8, nope_dim=8,
              rope_dim=4, v_dim=8, eps=1e-6, num_experts=16, top_k=3,
              expert_size=8, shared_size=8, scale=2.5)


def _sparse_layer(held=None, first=0, **kw):
    layer = zoo.LatentLayer(experts_held=held, first_expert=first, **_SHARE,
                            **kw)
    layer.initialize(mx.initializer.Normal(0.3), ctx=mx.cpu())
    return layer


def _apply(layer, x, tables, values=None):
    params = {id(p): jnp.asarray(values[n]) if values else p.data().data
              for n, p in layer._reg_params.items()}
    with ActiveTrace(params, train=False):
        out, stats = layer.forward(jnp.asarray(x), *tables)
    return np.asarray(out), np.asarray(stats)


def test_the_four_shares_add_up_to_the_uncut_layer(reference):
    """16 experts, top-3, 4 shares of 4 (first_expert 0, 4, 8, 12): the
    routed parts the shares give, with what every chip computes alike
    (latent attention, shared expert) counted once, add up to the uncut
    layer, which is the plain reference's layer."""
    np.random.seed(13)
    whole = _sparse_layer(prefix="whole_")
    values = {n: np.asarray(p.data().data)
              for n, p in whole._reg_params.items()}
    s = 24
    x = np.random.RandomState(0).randn(2, s, 16).astype(np.float32)
    tables = rotary.rotary_tables(rotary.default_inv_freq(32e6, 4), s,
                                  interleaved=True)
    full, stats = _apply(whole, x, tables)
    assert stats[:-1].sum() == 2 * s * 3 and stats[-1] == 0

    cfg = {"num_attention_heads": 2, "rms_norm_eps": 1e-6,
           "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "kv_lora_rank": 8,
           "rope_theta": 32e6, "num_experts_per_tok": 3,
           "routed_scaling_factor": 2.5}
    flat = {"l_" + n: jnp.asarray(v) for n, v in values.items()}

    def plain(row):
        out = reference.layer(flat, "l_", row, True, cfg)
        h = row + reference.attention(
            flat, "l_", reference.rms_norm(row, values["norm_weight"], 1e-6),
            cfg)
        b = reference.rms_norm(h, values["mlp_norm_weight"], 1e-6)
        return out, h + reference.shared_expert(flat, "l_", b)

    want, alike = (np.stack(v) for v in zip(*(plain(jnp.asarray(row))
                                              for row in x)))
    np.testing.assert_allclose(full, want, rtol=2e-4, atol=2e-5)

    total = np.zeros_like(full)
    for first in range(0, 16, 4):
        share = _sparse_layer(held=4, first=first, prefix=f"share{first}_")
        cut = dict(values,
                   experts_w1=values["experts_w1"][first:first + 4],
                   experts_w2=values["experts_w2"][first:first + 4])
        part, part_stats = _apply(share, x, tables, cut)
        assert part_stats[-1] == 0
        np.testing.assert_array_equal(part_stats[:4],
                                      stats[first:first + 4])
        total += part - alike
    np.testing.assert_allclose(total + alike, full, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("seed", [0, 1, 2147500001])
def test_a_sparse_layer_takes_one_trip_at_the_cells_load(seed):
    """The cell's routed shape (16,384 tokens, top-8 of 256, experts 0-31
    held, the load stated as the layer states it): whatever the seed
    draws, the rows fit the one chunk `row_chunk` makes of the expected
    16,384, so the expert loop runs once a layer."""
    rng = np.random.RandomState(seed % 2 ** 32)
    tokens, width = 16384, 32
    plan = moe.route(jnp.asarray(rng.randn(tokens, width), jnp.float32),
                     jnp.asarray(rng.randn(256, width) * 0.02, jnp.float32),
                     jnp.zeros((256,), jnp.float32), top_k=8, scale=2.5,
                     first_expert=0, n_local=32)
    expected = int(tokens * 8 * 32 / 256)
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


def test_model_matches_the_plain_reference_logits_loss_and_gradients(
        reference, small_config):
    """The dense layer, the sparse layers with a share of the experts and
    the prediction module at the rehearsal size: both sets of logits,
    both terms of the loss, every gradient; the embedding and the head,
    one array each, get the gradients of both losses."""
    model_py = _load("model")
    step = _small_model(small_config, model_py)
    plist = sorted(step.collect_params().items())
    prefix = os.path.commonprefix([n for n, _ in plist])
    prefix = prefix[:prefix.rfind("_") + 1]
    values = {n: p.data().data for n, p in plist}
    named = {n[len(prefix):]: v for n, v in values.items()}
    assert {"embed_weight", "head_weight", "norm_weight", "mtp_norm_weight",
            "mtp_join_proj_weight", "mtp_layer_q_b_proj_weight"} <= set(named)
    assert not any("mtp" in n and ("embed_weight" in n or "head" in n)
                   for n in named)          # no copy in the module
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, small_config["vocab_size"], (2, 256)), jnp.int32)
    weight = small_config["mtp_loss_weight"]

    def system(values, which):
        trace = ActiveTrace({id(p): values[n] for n, p in plist},
                            train=True)
        with trace:
            loss, logits, ahead, stats, main, second = step.forward(tokens)
        return {"both": loss, "main": main, "mtp": second}[which], (
            logits, ahead, stats, main, second)

    def plain(named, which):
        main, ahead = reference.logits(named, tokens, small_config)
        first, second = reference.loss_terms(main, ahead, tokens)
        return {"both": first + weight * second, "main": first,
                "mtp": second}[which], (main, ahead, first, second)

    def gradients(fn, arg, which):
        return jax.jit(jax.value_and_grad(
            lambda v: fn(v, which), has_aux=True))(arg)

    (loss, (logits, ahead, stats, main, second)), got = gradients(
        system, values, "both")
    (want_loss, (want_logits, want_ahead, first, want_second)), want = \
        gradients(plain, named, "both")
    assert stats.shape == (3, small_config["n_routed_experts"] + 1)
    assert (np.asarray(stats)[:, -1] == 0).all()
    np.testing.assert_allclose(logits, want_logits, rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(ahead, want_ahead, rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(main, first, rtol=1e-5)
    np.testing.assert_allclose(second, want_second, rtol=1e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(loss, main + weight * second, rtol=1e-6)
    sparse = small_config["num_hidden_layers"] \
        - small_config["first_k_dense_replace"] + 1
    trained = [n for n, p in plist if p.grad_req != "null"]
    assert len(trained) == len(plist) - sparse      # the selection biases
    for n in trained:
        w = np.asarray(want[n[len(prefix):]])
        np.testing.assert_allclose(
            got[n], w, rtol=5e-3, atol=5e-3 * np.abs(w).max() + 1e-9,
            err_msg=n)
    # each loss alone reaches the two shared arrays; the main loss alone
    # reaches nothing of the module's own
    for which in ("main", "mtp"):
        _, alone = gradients(system, values, which)
        for shared in ("embed_weight", "head_weight"):
            assert np.abs(np.asarray(alone[prefix + shared])).max() > 0, \
                (which, shared)
        own = np.asarray(alone[prefix + "mtp_join_proj_weight"]).any()
        assert own == (which == "mtp")


def test_step_program_holds_the_new_op_scopes_forward_and_backward(
        small_config):
    """`latent_projection`, `rotary_embedding`, `latent_attention`,
    `moe_route` and `moe_experts` under both `jvp(` and
    `transpose(jvp(`, inside their layer's block scope and, for the
    module, under `mtp` (with the second pass through embedding and head
    and the module's loss term), with remat on as the cell runs it: what
    the cell's per-layer metrics are read by.  Every layer's segment
    keeps what its kernel wrote; the router's weight and bias stay
    float32 under the cast."""
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
    layers = small_config["num_hidden_layers"] + 1      # the module's
    assert after["latent_splash"] == before["latent_splash"] + layers
    assert after["latent_xla"] == before["latent_xla"]
    now = residuals.kept_residuals()["latent_splash"]
    grown = {k: now[k] - kept["latent_splash"][k] for k in now}
    heads, vd = small_config["num_attention_heads"], \
        small_config["v_head_dim"]
    # o in bfloat16 and float32 rows of logsumexp, a layer
    assert grown == {"values": 2 * layers,
                     "bytes": layers * 256 * heads * (2 * vd + 4)}
    names = set(spmd.step_programs()[-1]["ops"].values())

    def holds(*parts):
        return any(all(p in n for p in parts) for n in names)

    for layer, op in (("layer0", "latent_projection"),
                      ("layer0", "rotary_embedding"),
                      ("layer0", "latent_attention"),
                      ("layer1", "latent_attention"),
                      ("layer1", "moe_route"),
                      ("layer1", "moe_experts"),
                      ("mtp/layer", "latent_projection"),
                      ("mtp/layer", "latent_attention"),
                      ("mtp/layer", "moe_experts"),
                      ("mtp/join", "FullyConnected"),
                      ("mtp/norm", "RMSNorm")):
        assert holds("/jvp(", f"/{layer}/{op}/"), (layer, op)
        assert holds("/transpose(jvp(", f"/{layer}/", f"/{op}/"), (layer, op)
    # the shared blocks a second time, and the module's loss term
    assert holds("/mtp/", "embed/Embedding/")
    assert holds("/mtp/", "head/FullyConnected/")
    assert holds(f")/{zoo.MTP_NAME}/") or holds(f"))/{zoo.MTP_NAME}/")
    assert not holds("/layer1/dot_product_attention/")
    assert holds("/transpose(jvp(", "rematted_computation/latent_projection/")
