"""The byte-level decoder of EVA layers (PR 34): `eva_chunk_summary` and
`eva_attention` against a dense masked oracle and against the attention
the repo already has, and the zoo's EvaByte stack against the
benchmark's plain reference."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.block import ActiveTrace
from mxnet_tpu.ops import eva_attention as ea
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import residuals
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel import spmd

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "benchmark")
_CONFIG_DIR = os.path.join(_BENCH, "configs", "evabyte")


def _load(name):
    import sys
    if _BENCH not in sys.path:      # model.py imports the harness
        sys.path.insert(0, _BENCH)
    spec = importlib.util.spec_from_file_location(
        "evabyte_" + name, os.path.join(_CONFIG_DIR, name + ".py"))
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


# ---- the two ops -------------------------------------------------------------

def _split(x, h):
    b, s, u = x.shape
    return x.reshape(b, s, h, u // h).transpose(0, 2, 1, 3)


def _summary_oracle(k, v, phi, mu, h, chunk):
    """(B, H, S / C, D) pooled keys and values, float32 jnp."""
    kh, vh = _split(k, h), _split(v, h)
    b, _, s, d = kh.shape
    kc = kh.reshape(b, h, s // chunk, chunk, d)
    vc = vh.reshape(b, h, s // chunk, chunk, d)
    alpha = jax.nn.softmax(
        jnp.einsum("bhncd,hd->bhnc", kc, phi) * d ** -0.5, -1)
    return (jnp.einsum("bhnc,bhncd->bhnd", alpha, kc) + mu[None, :, None],
            jnp.einsum("bhnc,bhncd->bhnd", alpha, vc))


def _eva_oracle(q, k, v, phi, mu, h, window, chunk):
    """Both ops the dense way: every query against ALL keys and ALL
    summaries under a mask written from the definition."""
    b, s, u = q.shape
    d = u // h
    ks, vs = _summary_oracle(k, v, phi, mu, h, chunk)
    keys = jnp.concatenate([_split(k, h), ks], axis=2)
    values = jnp.concatenate([_split(v, h), vs], axis=2)
    i = jnp.arange(s)[:, None]
    col = jnp.arange(s + s // chunk)[None]
    local = (col < s) & (col // window == i // window) & (col <= i)
    remote = (col >= s) & (col - s < (i // window) * (window // chunk))
    score = jnp.einsum("bhqd,bhkd->bhqk", _split(q, h), keys) * d ** -0.5
    prob = jax.nn.softmax(jnp.where(local | remote, score, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bhkd->bhqd", prob, values)
    return out.transpose(0, 2, 1, 3).reshape(b, s, u)


def _eva(q, k, v, phi, mu, h, window, chunk):
    ks, vs = apply_pure("eva_chunk_summary", k, v, phi, mu, num_heads=h,
                        chunk=chunk)
    return apply_pure("eva_attention", q, k, v, ks, vs, num_heads=h,
                      window=window, chunk=chunk)


def _inputs(seed, b, s, h, d):
    rng = np.random.RandomState(seed)
    wide = [jnp.asarray(rng.randn(b, s, h * d), jnp.float32)
            for _ in range(4)]
    narrow = [jnp.asarray(rng.randn(h, d), jnp.float32) for _ in range(2)]
    return (*wide[:3], *narrow), wide[3]


# (S, window, chunk, head size, the route counted, under the interpreter)
_SHAPES = {
    "xla_4_windows": (128, 32, 4, 16, "eva_xla", False),
    "xla_3_windows_chunk_16": (192, 64, 16, 16, "eva_xla", False),
    "xla_chunk_is_the_window": (96, 32, 32, 16, "eva_xla", False),
    "kernel_twin_on_cpu": (512, 128, 4, 128, "eva_splash", False),
    "kernel_under_the_interpreter": (512, 128, 4, 128, "eva_splash", True),
    "kernel_3_windows_interpreter": (768, 256, 2, 128, "eva_splash", True),
}


@pytest.mark.parametrize("case", list(_SHAPES))
def test_eva_ops_match_the_dense_masked_oracle(monkeypatch, case):
    """Value and all five gradients (q, k, v, phi, mu) through every way
    the op can take: the windowed XLA form, the splash kernels' XLA twin
    in a program lowered for the CPU, and the kernels themselves under
    the Pallas interpreter.  The route counter counts the one call."""
    s, window, chunk, d, route, interpret = _SHAPES[case]
    if interpret:
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    h = 2
    args, ct = _inputs(s + window + chunk, 2, s, h, d)
    before = pa.route_counts()
    got = _eva(*args, h, window, chunk)
    after = pa.route_counts()
    assert after[route] == before[route] + 1
    assert sum(after.values()) == sum(before.values()) + 1
    np.testing.assert_allclose(got, _eva_oracle(*args, h, window, chunk),
                               rtol=2e-5, atol=2e-5)
    grads = [jax.grad(lambda *a: (f(*a, h, window, chunk) * ct).sum(),
                      argnums=(0, 1, 2, 3, 4))(*args)
             for f in (_eva, _eva_oracle)]
    for name, g, w in zip(("q", "k", "v", "phi", "mu"), *grads):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("chunk", [4, 16])
def test_chunk_summary_matches_its_oracle_and_mu_moves_only_the_key(chunk):
    h, d = 2, 16
    (_, k, v, phi, mu), _ = _inputs(chunk, 2, 64, h, d)
    ks, vs = apply_pure("eva_chunk_summary", k, v, phi, mu, num_heads=h,
                        chunk=chunk)
    want_k, want_v = _summary_oracle(k, v, phi, mu, h, chunk)
    assert ks.shape == vs.shape == (2, 64 // chunk, h * d)
    np.testing.assert_allclose(_split(ks, h), want_k, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(_split(vs, h), want_v, rtol=1e-5, atol=1e-5)
    ks0, vs0 = apply_pure("eva_chunk_summary", k, v, phi, 0 * mu,
                          num_heads=h, chunk=chunk)
    np.testing.assert_allclose(vs0, vs)
    np.testing.assert_allclose(
        _split(ks - ks0, h), jnp.broadcast_to(mu[None, :, None],
                                              want_k.shape),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s, window, chunk", [(100, 32, 4), (128, 32, 5),
                                              (96, 64, 16), (128, 0, 4)])
def test_sizes_that_do_not_divide_raise(s, window, chunk):
    """S % W or W % C not zero is an error, not a padded guess."""
    h, d = 2, 16
    (q, k, v, phi, mu), _ = _inputs(0, 1, s, h, d)
    summaries = jnp.zeros((1, max(s // chunk, 1), h * d), jnp.float32)
    with pytest.raises(ValueError, match="eva_attention"):
        apply_pure("eva_attention", q, k, v, summaries, summaries,
                   num_heads=h, window=window, chunk=chunk)


def test_chunks_that_do_not_divide_the_sequence_raise():
    (_, k, v, phi, mu), _ = _inputs(0, 1, 100, 2, 16)
    with pytest.raises(ValueError, match="eva_chunk_summary"):
        apply_pure("eva_chunk_summary", k, v, phi, mu, num_heads=2, chunk=8)


@pytest.mark.parametrize("window", [128, 512])
def test_a_window_that_covers_the_sequence_is_causal_attention(window):
    """W >= S: nothing is remote, and the op IS the repo's causal
    attention (it hands the call to `dot_product_attention`'s routes)."""
    h, d, s = 2, 128, 128
    args, _ = _inputs(1, 2, s, h, d)
    q, k, v = args[:3]
    before = pa.route_counts()
    got = _eva(*args, h, window, 4)
    after = pa.route_counts()
    assert after["flash_causal"] == before["flash_causal"] + 1
    assert after["eva_splash"] == before["eva_splash"]
    assert after["eva_xla"] == before["eva_xla"]
    causal = apply_pure("dot_product_attention", q, k, v, None, None,
                        num_heads=h, causal=True)
    np.testing.assert_allclose(got, causal, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("s, window, d", [(128, 32, 16), (512, 128, 128)])
def test_chunks_of_one_with_mu_zero_are_full_causal_attention(s, window, d):
    """C = 1, mu = 0: a chunk's summary is its one key and value, so the
    keys of the own window and the summaries of every earlier one are
    all the keys up to the query: full causal attention, whatever phi."""
    h = 2
    (q, k, v, phi, mu), ct = _inputs(2, 2, s, h, d)
    causal = apply_pure("dot_product_attention", q, k, v, None, None,
                        num_heads=h, causal=True)
    np.testing.assert_allclose(_eva(q, k, v, phi, 0 * mu, h, window, 1),
                               causal, rtol=2e-5, atol=2e-5)
    grads = [jax.grad(lambda q, k, v: (f(q, k, v) * ct).sum(),
                      argnums=(0, 1, 2))(q, k, v)
             for f in (lambda q, k, v: _eva(q, k, v, phi, 0 * mu, h,
                                            window, 1),
                       lambda q, k, v: apply_pure(
                           "dot_product_attention", q, k, v, None, None,
                           num_heads=h, causal=True))]
    for g, w in zip(*grads):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


def test_the_kernels_under_the_interpreter_equal_the_xla_form():
    """The two bodies of the `eva_splash` route, side by side: what a
    program lowered for the TPU runs (here through the interpreter) and
    what one lowered for anything else runs."""
    b, h, s, d, window, chunk = 1, 2, 512, 128, 128, 4
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
               for _ in range(3))
    ks, vs = (jnp.asarray(rng.randn(b, h, s // chunk, d), jnp.float32)
              for _ in range(2))
    sizes = dict(scale=d ** -0.5, window=window, chunk=chunk)
    kernel = ea._eva_splash(q, k, v, ks, vs, interpret=True, **sizes)
    np.testing.assert_allclose(kernel, ea._eva_xla(q, k, v, ks, vs, **sizes),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s, window, chunk, blk", [
    (512, 128, 4, 128), (2048, 256, 4, 256), (1024, 64, 4, 256),
    (2048, 512, 16, 128)])
def test_mask_blocks_classified_by_their_corners_are_the_mask(
        s, window, chunk, blk):
    """The kernel's mask answers the host's block queries from a block's
    corners where it can; every block equals the mask function's own."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sa_mask)
    made = []
    real = sa_mask._ComputableMask.__init__

    def spy(self, *args, **kwargs):
        real(self, *args, **kwargs)
        made.append(self)

    sa_mask._ComputableMask.__init__ = spy
    try:
        ea._splash_kernel(2, s, window, chunk, blk, False)
    finally:
        sa_mask._ComputableMask.__init__ = real
    mask, by_corners = made[0], 0
    for r in range(0, s, blk):
        for c in range(0, s + s // chunk, blk):
            idx = (slice(r, r + blk), slice(c, c + blk))
            got = mask[idx]
            want = sa_mask._ComputableMask.__getitem__(mask, idx)
            assert got.shape == want.shape and (got == want).all(), idx
            by_corners += got.strides == (0, 0)
    assert by_corners > 0


def test_eva_routes_are_counted_in_telemetry():
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import instruments
    assert {"eva_splash", "eva_xla"} <= set(pa.ROUTES)
    args, _ = _inputs(4, 1, 128, 2, 16)
    telemetry.enable()
    try:
        child = instruments.attention_route_total("eva_xla")
        before = child.value
        _eva(*args, 2, 32, 4)
        assert child.value == before + 1
    finally:
        telemetry.disable()


# ---- the whole model ---------------------------------------------------------

def _small_model(config, model_py):
    np.random.seed(5)
    mx.random.seed(5)
    step = model_py._step_block(config)
    step.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
    return step


def test_model_matches_the_plain_reference_logits_loss_and_gradients(
        reference, small_config):
    """Four windows, so that the last one sees three windows' summaries;
    the norms' gains and the pooling vectors moved off their initial
    values so that the unit offset and mu are seen; logits (S, 8, V),
    the 8-head loss, and the gradient of every parameter."""
    model_py = _load("model")
    step = _small_model(small_config, model_py)
    plist = sorted(step.collect_params().items())
    prefix = os.path.commonprefix([n for n, _ in plist])
    prefix = prefix[:prefix.rfind("_") + 1]
    rng = np.random.RandomState(1)
    values = {n: p.data().data + (
        0.1 * jnp.asarray(rng.randn(*p.shape), jnp.float32)
        if "norm" in n else 0.0) for n, p in plist}
    named = {n[len(prefix):]: v for n, v in values.items()}
    s = 512
    assert s == 4 * small_config["window_size"]
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

    (loss, logits), got = jax.jit(jax.value_and_grad(
        system, has_aux=True))(values)
    (want_loss, want_logits), want = jax.jit(jax.value_and_grad(
        plain, has_aux=True))(named)
    assert logits.shape == (2, s, 8, 320) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want_logits, rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert len(plist) == 2 * 11 + 3
    for n, p in plist:
        assert p.grad_req != "null", n
        w = np.asarray(want[n[len(prefix):]])
        np.testing.assert_allclose(
            got[n], w, rtol=5e-3, atol=5e-3 * np.abs(w).max() + 1e-9,
            err_msg=n)


@pytest.mark.parametrize("s", [7, 8, 64])
def test_multibyte_loss_counts_only_the_targets_that_exist(reference, s):
    """Head p of position t predicts byte t + 1 + p; with S <= 8 some
    heads have no target at all."""
    from mxnet_tpu.gluon.model_zoo.evabyte import multibyte_loss
    rng = np.random.RandomState(s)
    logits = jnp.asarray(rng.randn(2, s, 8, 11), jnp.float32)
    tokens = jnp.asarray(rng.randint(0, 11, (2, s)), jnp.int32)
    want, count = 0.0, 0
    logp = np.asarray(jax.nn.log_softmax(logits, -1))
    for b in range(2):
        for t in range(s):
            for p in range(8):
                if t + 1 + p < s:
                    want -= logp[b, t, p, tokens[b, t + 1 + p]]
                    count += 1
    assert count == 2 * sum(max(s - 1 - p, 0) for p in range(8))
    np.testing.assert_allclose(multibyte_loss(logits, tokens), want / count,
                               rtol=1e-5)
    np.testing.assert_allclose(reference.loss_of(logits, tokens),
                               want / count, rtol=1e-5)


def test_unit_offset_norm_is_identity_gain_at_zero():
    x = jnp.asarray(np.random.RandomState(0).randn(4, 32), jnp.float32)
    zero, one = jnp.zeros(32), jnp.ones(32)
    np.testing.assert_allclose(
        apply_pure("RMSNorm", x, zero, eps=1e-5, offset=1.0),
        apply_pure("RMSNorm", x, one, eps=1e-5), rtol=1e-6)
    # the gain is added in float32: a bfloat16 1 + 2^-10 would be 1
    small = jnp.full((32,), 2.0 ** -10, jnp.bfloat16)
    got = apply_pure("RMSNorm", x, small, eps=1e-5, offset=1.0)
    want = apply_pure("RMSNorm", x, one, eps=1e-5) * (1 + 2.0 ** -10)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_step_program_holds_both_op_scopes_forward_and_backward(
        small_config):
    """`eva_chunk_summary` and `eva_attention` (and `rotary_embedding`)
    under both `jvp(` and `transpose(jvp(`, inside their layer's block
    scope, with remat on as the cell runs it: what the cell's per-layer
    metrics are read by.  Every parameter is bfloat16 under the cast,
    the logits float32; the route counter counts a call a layer."""
    model_py = _load("model")
    traffic = {"seq_len": 512, "batch": 1}
    before, kept = pa.route_counts(), residuals.kept_residuals()
    trainer = model_py.build(0, small_config, traffic, 1)
    assert trainer.remat
    for name, value in trainer.params.items():
        assert value.dtype == jnp.bfloat16, name
    tokens, = model_py.batch(0, small_config, traffic, np.asarray)
    first = float(trainer.step(tokens).asnumpy())
    assert np.isfinite(first)
    assert float(trainer.step(tokens).asnumpy()) < first
    after = pa.route_counts()
    layers = small_config["num_hidden_layers"]
    assert after["eva_splash"] == before["eva_splash"] + layers
    assert after["eva_xla"] == before["eva_xla"]
    assert after["reference"] == before["reference"]
    # each layer's segment keeps its kernel's o (bfloat16) and logsumexp
    now = residuals.kept_residuals()["eva_splash"]
    heads, width = small_config["num_attention_heads"], \
        small_config["hidden_size"] * 2
    assert {k: now[k] - kept["eva_splash"][k] for k in now} == {
        "values": 2 * layers, "bytes": layers * 512 * (width + 4 * heads)}
    names = set(spmd.step_programs()[-1]["ops"].values())

    def holds(*parts):
        return any(all(p in n for p in parts) for n in names)

    for layer in ("layer0", "layer1"):
        for op in ("rotary_embedding", "eva_chunk_summary", "eva_attention",
                   "RMSNorm", "FullyConnected"):
            assert holds("/jvp(", f"/{layer}/{op}/"), (layer, op)
            assert holds("/transpose(jvp(", f"/{layer}/", f"/{op}/"), \
                (layer, op)
    assert holds("/transpose(jvp(", "rematted_computation/eva_attention/")
    assert not holds("dot_product_attention")
    _loss, logits = trainer.forward(tokens)
    assert logits.dtype == np.float32 and logits.shape == (1, 512, 8, 320)
