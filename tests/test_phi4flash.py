"""Phi-4-mini-flash (PR 49): the layer rule, differential attention as
one grouped call against the four published products, the window form at
d 64 / d_v 128, the zoo's stack against the benchmark's plain reference
(logits, memory, loss, every gradient), the cotangents of the shared
memory and keys summed over their readers, remat, LayerNorm in
`_decoder.py`, and the six other families' step text."""
import contextlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu import random as rnd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.block import F_PURE, ActiveTrace
from mxnet_tpu.gluon.model_zoo import _decoder
from mxnet_tpu.gluon.model_zoo import phi4flash as zoo
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import residuals
from mxnet_tpu.ops.registry import apply_pure

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "benchmark")
_NAME = "phi4_mini_flash"


def _load(config, name):
    # a model.py finds laguna_xs2's initializer through the harness
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    spec = importlib.util.spec_from_file_location(
        f"{config}_{name}",
        os.path.join(_BENCH, "configs", config, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _small_config(config, **changed):
    with open(os.path.join(_BENCH, "configs", config, "config.json")) as f:
        published = json.load(f)
    published.update(published["rehearsal"]["model"])
    published.update(changed)
    return published


@pytest.fixture(scope="module")
def reference():
    return _load(_NAME, "reference")


@pytest.fixture(scope="module")
def model_py():
    return _load(_NAME, "model")


@contextlib.contextmanager
def _traced(plist, values, train=True, mirror=False):
    trace = ActiveTrace({id(p): values[n] for n, p in plist}, train=train)
    trace.mirror = mirror
    with trace, rnd.key_provider(rnd.KeyProvider(jax.random.PRNGKey(0))):
        yield trace


def _small_model(config, model_py):
    """The step block at a small size, every norm's gain and bias, D and
    the sub-norm's gain away from one and zero."""
    np.random.seed(5)
    mx.random.seed(5)
    step = model_py._step_block(config)
    step.initialize(mx.initializer.Normal(0.05), ctx=mx.cpu())
    plist = sorted(step.collect_params().items())
    prefix = os.path.commonprefix([n for n, _ in plist])
    prefix = prefix[:prefix.rfind("_") + 1]
    values = {n: p.data().data for n, p in plist}
    rng = np.random.RandomState(6)
    for n, v in values.items():
        if "norm_" in n or n.endswith(("_D", "subln_weight")):
            values[n] = v + jnp.asarray(0.3 * rng.randn(*v.shape), v.dtype)
    return step, plist, prefix, values


def _tokens(config, batch=1, seq_len=256, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, config["vocab_size"], (batch, seq_len)), jnp.int32)


# ---- the rule ----------------------------------------------------------------

@pytest.mark.parametrize("n, kinds", [
    (8, "MWMWMFGX"), (12, "MWMWMWMFGXGX"), (4, "MWMF"),
    (32, "MW" * 8 + "MF" + "GX" * 7)])
def test_layer_kinds_are_the_published_rule(reference, n, kinds):
    letters = {"M": "mamba", "W": "window", "F": "full", "G": "gmu",
               "X": "cross"}
    want = tuple(letters[c] for c in kinds)
    assert zoo.layer_kinds(n) == want
    assert tuple(reference.layer_kinds(
        {"num_hidden_layers": n, "mb_per_layer": 2})) == want


@pytest.mark.parametrize("n", [2, 6, 9, 10])
def test_other_depths_are_refused(n):
    with pytest.raises(MXNetError, match="layers"):
        zoo.layer_kinds(n)


def test_lambda_init_by_layer(reference):
    for i, want in ((0, 0.2), (5, 0.8 - 0.6 * np.exp(-1.5)), (31, 0.79994)):
        assert abs(zoo.lambda_init(i) - want) < 1e-5
        assert zoo.lambda_init(i) == reference.lambda_init(i)


# ---- differential attention --------------------------------------------------

def _core_operands(s, heads=8, kv=4, d=64, seed=0):
    rng = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    return (f(1, s, heads * d), f(1, s, kv * d), f(1, s, kv * d),
            *(0.3 * f(d) for _ in range(4)), 1.0 + 0.3 * f(2 * d))


def _published(reference, operands, index, window, heads=8, kv=4):
    """reference.py's four products on the op's operands: W_q and W_o
    the identity."""
    q, k, v, lq1, lk1, lq2, lk2, gain = operands
    s, u = q.shape[1:]
    eye = jnp.eye(u, dtype=jnp.float32)
    p = {"q_proj_weight": eye, "o_proj_weight": eye, "lambda_q1": lq1,
         "lambda_k1": lk1, "lambda_q2": lq2, "lambda_k2": lk2,
         "subln_weight": gain}
    cfg = {"num_attention_heads": heads, "num_key_value_heads": kv,
           "layer_norm_eps": 1e-5}
    with jax.default_matmul_precision("highest"):
        return reference.differential_attention(
            p, "", q[0], k[0].reshape(s, kv, -1), v[0].reshape(s, kv, -1),
            index, cfg, window=window)[None]


@pytest.mark.parametrize("interpret", [False, True], ids=["twin", "kernels"])
@pytest.mark.parametrize("window", [0, 128], ids=["full", "window"])
def test_one_grouped_call_is_the_four_published_products(
        reference, monkeypatch, window, interpret):
    """8 query heads over 4 key/value heads of 64 (two query pairs a key
    pair, as published), values 128 wide in the grouped call: value and
    the gradients of q, k, v, the lambdas and the gain, through the XLA
    twin and through the splash kernels under the interpreter.  The
    reference pairs heads as published, so the permutation is held."""
    if interpret:
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    index, s = 3, 256
    operands = _core_operands(s)
    weight = jnp.asarray(np.random.RandomState(9).randn(1, s, 512),
                         jnp.float32)
    route = "diff_window_splash" if window else "diff_splash"
    before = pa.route_counts()

    def system(*operands):
        return (apply_pure(
            "differential_attention", *operands, num_heads=8,
            num_kv_heads=4, window=window,
            lambda_init=zoo.lambda_init(index)) * weight).sum()

    def plain(*operands):
        return (_published(reference, operands, index, window)
                * weight).sum()

    argnums = tuple(range(8))
    got, got_grads = jax.jit(jax.value_and_grad(system, argnums))(*operands)
    want, want_grads = jax.jit(jax.value_and_grad(plain, argnums))(*operands)
    after = pa.route_counts()
    assert after[route] == before[route] + 1
    assert after["diff_xla"] == before["diff_xla"]
    tol = 2e-2 if interpret else 2e-4       # the kernels' bfloat16 passes
    np.testing.assert_allclose(got, want, rtol=tol)
    for g, w in zip(got_grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(g, w, rtol=tol,
                                   atol=tol * float(np.abs(w).max()))


def test_other_shapes_take_the_twin_and_odd_heads_are_refused(reference):
    """Heads of 32 do not fill the kernels' lanes: `diff_xla`, the same
    function."""
    operands = _core_operands(96, heads=4, kv=2, d=32, seed=2)
    before = pa.route_counts()
    got = apply_pure("differential_attention", *operands, num_heads=4,
                     num_kv_heads=2, window=40, lambda_init=0.5)
    assert pa.route_counts()["diff_xla"] == before["diff_xla"] + 1
    # lambda_init 0.5 is no layer's: hold it through the reference's own
    saved = reference.lambda_init
    reference.lambda_init = lambda layer: 0.5
    try:
        want = _published(reference, operands, 0, 40, heads=4, kv=2)
    finally:
        reference.lambda_init = saved
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="differential_attention"):
        apply_pure("differential_attention", *_core_operands(
            64, heads=6, kv=3, d=32), num_heads=6, num_kv_heads=3)


@pytest.mark.parametrize("s, window", [(256, 64), (200, 64), (96, 40)])
def test_window_form_equals_the_dense_mask_at_a_value_size_of_its_own(
        s, window):
    """`_window_xla` with 64-wide queries and keys and 128-wide values,
    4 query heads over 2: the banded form against dense masked scores."""
    rng = np.random.RandomState(s)
    q = jnp.asarray(rng.randn(2, 4, s, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, s, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, s, 128), jnp.float32)
    got = pa._window_xla(q, k, v, 0.125, window)
    score = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, 1)) * 0.125
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None]
    score = jnp.where((ahead >= 0) & (ahead < window), score, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(score, -1),
                      jnp.repeat(v, 2, 1))
    assert got.shape == (2, 4, s, 128)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_cells_cores_are_admitted_and_named():
    assert pa._causal_flash_shape(40, 20, 16384, 16384, 64, 128)
    assert pa._fused_backward(512, 16384, 64, 128, 2) == "band"
    assert pa._fused_backward(None, 16384, 64, 128, 2) == "fused"
    assert {"diff_splash", "diff_window_splash"} <= set(residuals.NAMES)
    assert pa.ROUTES[-3:] == ("diff_splash", "diff_window_splash",
                              "diff_xla")


# ---- LayerNorm in _decoder.py ------------------------------------------------

def test_a_layer_norm_brings_its_bias_and_is_float32_inside():
    layer = zoo.GMULayer(64, 96, 1e-5, prefix="g_")
    assert {"norm_weight", "norm_bias", "mlp_norm_weight",
            "mlp_norm_bias"} <= set(layer._reg_params)
    plain = _decoder.Layer(64, 1e-5, prefix="p_")
    assert set(plain._reg_params) == {"norm_weight"}
    with pytest.raises(MXNetError, match="norm"):
        _decoder.Layer(64, 1e-5, norm="batch", prefix="q_")
    with pytest.raises(MXNetError, match="norm"):
        _decoder.Head(64, 100, 1e-5, norm="batch", prefix="h_")
    rng = np.random.RandomState(0)
    x = jnp.asarray(100.0 + rng.randn(4, 2560), jnp.bfloat16)
    gain = jnp.asarray(1.0 + 0.1 * rng.randn(2560), jnp.bfloat16)
    bias = jnp.asarray(0.1 * rng.randn(2560), jnp.bfloat16)
    got = _decoder.normed(F_PURE, x, gain, 1e-5, bias=bias)
    assert got.dtype == jnp.bfloat16
    x32 = np.asarray(x, np.float64)
    want = ((x32 - x32.mean(-1, keepdims=True))
            / np.sqrt(x32.var(-1, keepdims=True) + 1e-5)
            * np.asarray(gain, np.float64) + np.asarray(bias, np.float64))
    # a bfloat16 mean of 2,560 numbers near 100 is not a mean
    assert np.abs(np.asarray(got, np.float64) - want).max() < 0.03


# ---- the whole model ---------------------------------------------------------

@pytest.mark.parametrize("layers", [8, 12])
def test_model_matches_the_plain_reference_logits_memory_loss_gradients(
        reference, model_py, layers):
    """Every kind of layer at the rehearsal size (n = 12: two readers of
    the memory and of the keys), the head tied: logits, the memory, the
    loss, every gradient."""
    config = _small_config(_NAME, num_hidden_layers=layers)
    step, plist, prefix, values = _small_model(config, model_py)
    named = {n[len(prefix):]: v for n, v in values.items()}
    half = layers // 2
    assert {"embed_weight", "head_norm_bias", "layer0_A_log",
            "layer1_lambda_q1", f"layer{half + 1}_k_proj_weight",
            f"layer{half + 2}_gmu_in_proj_weight",
            f"layer{half + 3}_subln_weight"} <= set(named)
    assert "head_weight" not in named           # one array, the embedding's
    assert f"layer{half + 3}_k_proj_weight" not in named    # no keys
    assert f"layer{half + 2}_A_log" not in named            # no scan
    tokens = _tokens(config)

    def system(values):
        with _traced(plist, values):
            loss, logits, memory, *_probe = step.forward(tokens)
        return loss, (logits, memory)

    def plain(named):
        scores, memory = reference.forward(named, tokens, config)
        return reference.loss_of(scores, tokens), (scores, memory)

    (loss, (logits, memory)), got = jax.jit(
        jax.value_and_grad(system, has_aux=True))(values)
    (want_loss, (want_logits, want_memory)), want = jax.jit(
        jax.value_and_grad(plain, has_aux=True))(named)
    assert memory.shape == (1, 256, 2 * config["hidden_size"])
    np.testing.assert_allclose(memory, want_memory, rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(logits, want_logits, rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for n, _ in plist:
        w = np.asarray(want[n[len(prefix):]])
        assert np.abs(w).max() > 0, n
        np.testing.assert_allclose(
            got[n], w, rtol=5e-3, atol=5e-3 * np.abs(w).max() + 1e-9,
            err_msg=n)


def test_cotangents_of_memory_and_keys_are_sums_over_their_readers(
        model_py):
    """n = 12 under remat: two Gated Memory Units read m and two cross
    layers read k, v, each an argument of a recomputed segment.  The
    gradients of the layers that MAKE them, with both readers live, are
    those with one reader live plus those with the other, less those
    with neither (the path through the residual stream is in all
    three)."""
    config = _small_config(_NAME, num_hidden_layers=12)
    step, plist, prefix, values = _small_model(config, model_py)
    model, tokens = step.model, _tokens(config, seed=1)
    layers = list(model.layers._children.values())
    weight = jnp.asarray(np.random.RandomState(2).randn(
        1, 256, config["vocab_size"]), jnp.float32)

    def run(values, live):
        """The model's own loop, a reader outside `live` reading through
        a stop_gradient."""
        with _traced(plist, values, mirror=True):
            h = model.embed(tokens)
            memory = keys = None
            for i, (kind, layer) in enumerate(zip(model.kinds, layers)):
                read = (lambda v: v) if i in live else lax.stop_gradient
                if kind == "gmu":
                    h = layer(h, read(memory))
                elif kind == "cross":
                    h = layer(h, *map(read, keys))
                elif kind == "full":
                    h, *keys = layer(h)
                elif getattr(layer, "_memory_output", False):
                    h, memory, *_probe = layer(h)
                else:
                    h = layer(h)
            return (model.head(h) * weight).sum()

    grads = {live: jax.jit(jax.grad(lambda v, live=live: run(v, live)))(
        values) for live in [(8, 9, 10, 11), (8, 9, 11), (10, 9, 11),
                             (9, 11), (8, 10, 9), (8, 10, 11), (8, 10)]}
    both = grads[8, 9, 10, 11]
    for makes, one, other, neither in (
            ("layer6_", (8, 9, 11), (10, 9, 11), (9, 11)),
            ("layer7_", (8, 10, 9), (8, 10, 11), (8, 10))):
        for n in (n for n, _ in plist if n.startswith(prefix + makes)):
            parts = [np.asarray(grads[k][n]) for k in (one, other, neither)]
            total = parts[0] + parts[1] - parts[2]
            if n.endswith(("A_log", "x_proj_weight", "conv_weight",
                           "k_proj_weight", "v_proj_weight")):
                # upstream of what is read: a reader moves the gradient,
                # and the sum is not one reader's
                assert np.abs(parts[0] - parts[2]).max() > 0, n
                assert np.abs(parts[1] - parts[2]).max() > 0, n
            np.testing.assert_allclose(
                both[n], total, rtol=1e-4,
                atol=1e-5 * np.abs(total).max() + 1e-9, err_msg=n)


def test_remat_on_and_off_give_one_loss_and_one_gradient(model_py):
    config = _small_config(_NAME)
    step, plist, _prefix, values = _small_model(config, model_py)
    tokens = _tokens(config, seed=3)

    def whole(values, remat):
        with _traced(plist, values, mirror=remat):
            return step.forward(tokens)[0]

    before = residuals.kept_residuals()
    on = jax.jit(jax.value_and_grad(lambda v: whole(v, True)))(values)
    kept = residuals.kept_residuals()
    off = jax.jit(jax.value_and_grad(lambda v: whole(v, False)))(values)
    assert residuals.kept_residuals() == kept       # no segment, no note
    np.testing.assert_allclose(on[0], off[0], rtol=1e-6)
    for n in values:
        np.testing.assert_allclose(
            on[1][n], off[1][n], rtol=1e-4,
            atol=1e-5 * np.abs(np.asarray(off[1][n])).max() + 1e-9,
            err_msg=n)
    # o and logsumexp of the full and the cross core, of the two window
    # cores; nothing of the scan
    for name, cores in (("diff_splash", 2), ("diff_window_splash", 2)):
        assert kept[name]["values"] - before[name]["values"] == 2 * cores
    heads, s = config["num_attention_heads"], 256
    assert kept["diff_splash"]["bytes"] - before["diff_splash"]["bytes"] \
        == 2 * heads * s * (128 * 4 + 4)


def test_cast_keeps_the_scan_and_lambda_parameters_float32(model_py):
    config = _small_config(_NAME)
    step, plist, prefix, _values = _small_model(config, model_py)
    step.cast("bfloat16")
    kept = ("A_log", "_D", "dt_bias", "lambda_q1", "lambda_k1",
            "lambda_q2", "lambda_k2", "subln_weight")
    for n, p in plist:
        want = "float32" if n.endswith(kept) else "bfloat16"
        assert str(np.dtype(p.dtype)) == want, n


def test_trains_through_spmd_trainer_with_remat(model_py):
    """The normal path: one step program, remat by block, Adam,
    bfloat16; the loss falls on a resident batch."""
    config = _small_config(_NAME, hidden_size=128, intermediate_size=192,
                           num_attention_heads=4, num_key_value_heads=2,
                           dt_rank=8, vocab_size=64, sliding_window=16)
    np.random.seed(0)
    mx.random.seed(0)
    step = model_py._step_block(config)
    step.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
    step.cast("bfloat16")
    trainer = parallel.SPMDTrainer(
        step, lambda loss: loss, "adam", {"learning_rate": 1e-3},
        mesh=parallel.make_mesh(dp=1), n_labels=0, remat=True)
    tokens = np.random.RandomState(0).randint(0, 64, (2, 64)).astype(
        np.int32)
    losses = [float(trainer.step(tokens).asnumpy()) for _ in range(8)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.05
    # the step block also hands out the memory's scan inputs
    loss, logits, memory, x, delta, b, c = trainer.forward(tokens)
    assert logits.shape == (2, 64, 64) and memory.shape == (2, 64, 256)
    assert x.shape == delta.shape == memory.shape
    assert b.shape == c.shape == (2, 64, 16)


# ---- the six other families --------------------------------------------------

def _norm_residual_before_pr49(F, x, norm_weight, eps, mix, *args,
                               offset=0.0, post=None, **params):
    mixed = mix(F, F.RMSNorm(x, norm_weight, eps=eps, offset=offset),
                *args, **params)
    mixed, *stats = mixed if isinstance(mixed, (list, tuple)) else (mixed,)
    if post is not None:
        mixed = F.RMSNorm(mixed, post, eps=eps, offset=offset)
    return (x + mixed, *stats) if stats else x + mixed


def _head_forward_before_pr49(self, F, x, norm_weight, weight):
    x = F.RMSNorm(x, norm_weight, eps=self._eps, offset=self._offset)
    if self._dtype is not None:
        x, weight = (F.cast(a, dtype=self._dtype) for a in (x, weight))
    return _decoder.project(F, x, weight)


def _mlp_before_pr49(self, F, h, mlp_norm_weight, **params):
    return _norm_residual_before_pr49(
        F, h, mlp_norm_weight, self._eps,
        self.experts if self._sparse else self.dense, **params)


_FAMILIES = {"laguna": "laguna_xs2", "joyai": "joyai_llm_flash",
             "lfm2": "lfm2_8b_a1b", "nemotron_h": "nemotron3_super_120b",
             "evabyte": "evabyte", "ouro": "ouro_2_6b"}


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_the_norm_choice_leaves_the_other_families_step_text_alone(
        family, monkeypatch):
    """The six other decoder families' step blocks at their rehearsal
    sizes, value and gradients: the lowered program is, character for
    character, the one they lowered to with PR 48's `norm_residual`,
    `MLPLayer.mlp` and `Head`, and their parameters are the same."""
    from mxnet_tpu.gluon.model_zoo import evabyte, joyai, laguna, ouro

    name = _FAMILIES[family]
    config, model = _small_config(name), _load(name, "model")
    small = config["rehearsal"]["traffic"]
    traffic = {"seq_len": small["seq_len"], "batch": small["batch_per_chip"]}

    def lowered():
        np.random.seed(1)
        mx.random.seed(1)
        step = model._step_block(config)
        step.initialize(mx.initializer.Normal(0.05), ctx=mx.cpu())
        plist = sorted(step.collect_params().items())
        names = [n.split("_", 1)[1] for n, _ in plist]
        values = [p.data().data for _, p in plist]
        batch = model.batch(0, config, traffic, jnp.asarray)

        def loss(values, *batch):       # no array closed over
            with _traced(plist, dict(zip((n for n, _ in plist), values))):
                out = step.forward(*batch)
            return out[0] if isinstance(out, (list, tuple)) else out

        return names, jax.jit(jax.value_and_grad(loss)).lower(
            values, *batch).as_text()

    names, text = lowered()
    for module in (_decoder, laguna, joyai, evabyte, ouro):
        if hasattr(module, "norm_residual"):
            monkeypatch.setattr(module, "norm_residual",
                                _norm_residual_before_pr49)
    monkeypatch.setattr(_decoder.Head, "hybrid_forward",
                        _head_forward_before_pr49)
    monkeypatch.setattr(_decoder.MLPLayer, "mlp", _mlp_before_pr49)
    assert lowered() == (names, text)
    assert any("norm_weight" in n for n in names)
    assert not any(n.endswith("norm_bias") for n in names)
