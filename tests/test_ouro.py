"""The decoder whose layers run four times (PR 46): sub-layers normed on
both sides, one Parameter read by four recomputed segments of one
program, four exits over one head under the expected-exit objective, and
the zoo's Ouro stack against the benchmark's plain reference."""
import contextlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

import mxnet_tpu as mx
from mxnet_tpu import random as rnd
from mxnet_tpu.gluon.block import ActiveTrace
from mxnet_tpu.gluon.model_zoo import _decoder, evabyte, joyai, laguna
from mxnet_tpu.gluon.model_zoo import ouro as zoo
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import residuals, rotary
from mxnet_tpu.parallel import spmd

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "benchmark")


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


def _small_config(config):
    with open(os.path.join(_BENCH, "configs", config, "config.json")) as f:
        published = json.load(f)
    published.update(published["rehearsal"]["model"])
    return published


@pytest.fixture(scope="module")
def reference():
    return _load("ouro_2_6b", "reference")


@pytest.fixture(scope="module")
def model_py():
    return _load("ouro_2_6b", "model")


@pytest.fixture(scope="module")
def small_config():
    return _small_config("ouro_2_6b")


def _small_model(config, model_py, gains=True):
    """The step block at the rehearsal size; with `gains` every norm gain
    and the gate's bias away from their initial one and zero, so that a
    gain in the wrong place shows."""
    np.random.seed(5)
    mx.random.seed(5)
    step = model_py._step_block(config)
    step.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
    plist = sorted(step.collect_params().items())
    prefix = os.path.commonprefix([n for n, _ in plist])
    prefix = prefix[:prefix.rfind("_") + 1]
    values = {n: p.data().data for n, p in plist}
    if gains:
        rng = np.random.RandomState(6)
        for n, v in values.items():
            if "norm_weight" in n or n.endswith("gate_bias"):
                values[n] = v + jnp.asarray(
                    0.3 * rng.randn(*v.shape), v.dtype)
    return step, plist, prefix, values


@contextlib.contextmanager
def _traced(plist, values, train, mirror=False):
    """The trace a program reads `values` through, with a key stream of
    its own (the attention op asks for a key it does not use)."""
    trace = ActiveTrace({id(p): values[n] for n, p in plist}, train=train)
    trace.mirror = mirror
    with trace, rnd.key_provider(rnd.KeyProvider(jax.random.PRNGKey(0))):
        yield trace


def _tokens(config, batch=2, seq_len=256, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, config["vocab_size"], (batch, seq_len)), jnp.int32)


# ---- a layer ------------------------------------------------------------------

_CFG = {"num_attention_heads": 2, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-6, "rope_theta": 1e6}


def test_a_layer_is_the_plain_references_layer(reference):
    """Attention and the gated MLP, each normed before AND after, with
    four gains that differ: the post-norms are on the sub-layers'
    outputs, not on the sums."""
    np.random.seed(3)
    layer = zoo.OuroLayer(128, 2, 2, 64, 40, 1e-6, prefix="l_")
    layer.initialize(mx.initializer.Normal(0.3), ctx=mx.cpu())
    assert list(layer._reg_params) == [
        "norm_weight", "post_norm_weight", "mlp_norm_weight",
        "mlp_post_norm_weight", "q_proj_weight", "k_proj_weight",
        "v_proj_weight", "o_proj_weight", "mlp_gate_weight",
        "mlp_up_weight", "mlp_down_weight"]
    rng = np.random.RandomState(4)
    values = {n: p.data().data + (jnp.asarray(rng.randn(128), jnp.float32)
                                  if "norm" in n else 0.0)
              for n, p in layer._reg_params.items()}
    s = 20
    x = rng.randn(2, s, 128).astype(np.float32)
    tables = rotary.rotary_tables(rotary.default_inv_freq(1e6, 64), s)
    with ActiveTrace({id(p): values[n]
                      for n, p in layer._reg_params.items()}, train=False):
        got = layer.forward(jnp.asarray(x), *tables)
    flat = {"l_" + n: v for n, v in values.items()}
    want = np.stack([reference.layer(flat, "l_", jnp.asarray(row), _CFG)
                     for row in x])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # without the post-norms it is another layer
    plain = np.stack([
        row + reference.attention(flat, "l_", reference.rms_norm(
            jnp.asarray(row), flat["l_norm_weight"], 1e-6), _CFG)
        for row in x])
    assert np.abs(plain - want).max() > 0.1


# ---- the exit distribution and the objective -----------------------------------

@pytest.mark.parametrize("steps", [1, 2, 4])
def test_exit_pdf_sums_to_one_and_the_last_pass_takes_the_remainder(
        reference, steps):
    gates = jnp.asarray(np.random.RandomState(steps).randn(steps, 2, 9) * 2,
                        jnp.float32)
    p = np.asarray(zoo.exit_pdf(gates))
    assert p.shape == (2, 9, steps)
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-6)
    leave = 1 / (1 + np.exp(-np.asarray(gates, np.float64)))
    left = np.ones((2, 9))
    for t in range(steps - 1):
        np.testing.assert_allclose(p[..., t], leave[t] * left, rtol=1e-5)
        left = left * (1 - leave[t])
    np.testing.assert_allclose(p[..., -1], left, rtol=1e-5)
    np.testing.assert_allclose(p, reference.exit_pdf(gates), rtol=1e-5,
                               atol=1e-7)


@pytest.mark.parametrize("beta", [0.0, 0.05, 0.7])
def test_the_objective_is_the_expected_loss_less_the_entropy_bonus(
        reference, beta):
    rng = np.random.RandomState(2)
    nll = jnp.asarray(rng.rand(4, 2, 7) * 5, jnp.float32)
    gates = jnp.asarray(rng.randn(4, 2, 8), jnp.float32)
    p = np.asarray(zoo.exit_pdf(gates), np.float64)[:, :-1]
    by_hand = ((p * np.moveaxis(np.asarray(nll, np.float64), 0, -1)).sum(-1)
               + beta * (p * np.log(p)).sum(-1)).mean()
    got = zoo.exit_loss(nll, gates, beta)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, by_hand, rtol=1e-5)
    np.testing.assert_allclose(got, reference.objective(
        jnp.moveaxis(nll, 0, -1), reference.exit_pdf(gates)[:, :-1], beta),
        rtol=1e-5)
    # the bonus is beta times the entropy, and an entropy is positive
    np.testing.assert_allclose(
        zoo.exit_loss(nll, gates, 0.0) - got,
        beta * -(p * np.log(p)).sum(-1).mean(), rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("beta", [0.0, 0.05])
def test_one_pass_is_the_plain_cross_entropy(reference, model_py,
                                             small_config, beta):
    """`total_ut_steps` 1: p = [1], entropy 0, and the objective is the
    mean cross-entropy of the one exit whatever the gate says."""
    config = dict(small_config, total_ut_steps=1, exit_entropy_beta=beta)
    step, plist, prefix, values = _small_model(config, model_py)
    tokens = _tokens(config, 1)
    with _traced(plist, values, train=False):
        logits, pdf = step.forward(tokens)
    np.testing.assert_array_equal(pdf, np.ones((1, 256, 1), np.float32))
    with _traced(plist, values, train=True):
        loss = step.forward(tokens)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    plain = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()
    np.testing.assert_allclose(loss, plain, rtol=1e-6)
    named = {n[len(prefix):]: v for n, v in values.items()}
    np.testing.assert_allclose(loss, reference.loss(named, tokens, config),
                               rtol=1e-5)


# ---- the whole model -----------------------------------------------------------

@pytest.mark.parametrize("beta", [0.0, 0.05], ids=["beta0", "beta"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_matches_the_plain_reference_exits_objective_and_gradients(
        reference, model_py, small_config, remat, beta):
    """Two layers run four times at the rehearsal size: the four exits'
    logits, the exit distribution, the objective and every parameter's
    gradient against `jax.grad` of the plain reference, as a training
    step's segments recompute it and without them."""
    config = dict(small_config, exit_entropy_beta=beta)
    step, plist, prefix, values = _small_model(config, model_py)
    named = {n[len(prefix):]: v for n, v in values.items()}
    layers = config["num_hidden_layers"]
    assert len(plist) == 11 * layers + 5
    assert {"embed_weight", "final_norm_weight", "exit_head_weight",
            "exit_gate_weight", "exit_gate_bias",
            "layer1_mlp_post_norm_weight"} <= set(named)
    tokens = _tokens(config)

    def exits(values):
        with _traced(plist, values, train=False):
            return step.forward(tokens)

    *logits, pdf = jax.jit(exits)(values)
    z, gate_logits = reference.exits(named, tokens, config)
    assert len(logits) == 4 and pdf.shape == (2, 256, 4)
    for t, got in enumerate(logits):
        np.testing.assert_allclose(
            got, reference.exit_logits(named, z[:, t]), rtol=2e-3,
            atol=2e-5, err_msg=f"exit {t + 1}")
    want_pdf = reference.exit_pdf(jnp.moveaxis(gate_logits, 1, 0))
    np.testing.assert_allclose(pdf, want_pdf, rtol=1e-4, atol=1e-6)
    assert np.abs(np.asarray(want_pdf) - 0.25).max() > 0.05

    def system(values):
        with _traced(plist, values, train=True, mirror=remat):
            return step.forward(tokens)

    loss, got = jax.jit(jax.value_and_grad(system))(values)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda named: reference.loss(named, tokens, config)))(named)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for n, _ in plist:
        w = np.asarray(want[n[len(prefix):]])
        assert np.abs(w).max() > 0, n
        np.testing.assert_allclose(
            got[n], w, rtol=5e-3, atol=5e-3 * np.abs(w).max() + 1e-9,
            err_msg=n)


def test_a_looped_parameters_gradient_is_the_sum_over_its_four_uses(
        model_py, small_config):
    """Against 4 N + 4 + 4 distinct blocks that start as copies, the
    passes unrolled by hand: every pass reads its own copy of the
    layers', the final norm's and the exit's arrays, and the copies'
    gradients, summed, are the looped model's (the scan's, for the pass);
    in the parameter's own dtype, as a gradient of one use is."""
    step, plist, prefix, values = _small_model(small_config, model_py)
    model = step.model
    tokens = _tokens(small_config, 1, seed=1)
    steps = small_config["total_ut_steps"]
    embed = prefix + "embed_weight"
    looped = [n for n, _ in plist if n != embed]

    def apart(copies):
        """The step's objective with each pass's reads fed apart."""
        tables = rotary.rotary_tables(
            rotary.default_inv_freq(small_config["rope_theta"],
                                    small_config["head_dim"]), 256)
        z = jnp.take(copies[0][embed], tokens, axis=0)
        exits = []
        for copy in copies:
            with _traced(plist, copy, train=True, mirror=True):
                for layer in model.layers._children.values():
                    z = layer(z, *tables)
                z = model.norm(z)
                exits.append(model.exit(z, tokens[:, 1:]))
        nll, gates = (jnp.stack(x) for x in zip(*exits))
        return zoo.exit_loss(nll, gates,
                             small_config["exit_entropy_beta"])

    def whole(values):
        with _traced(plist, values, train=True, mirror=True) as trace:
            loss = step.forward(tokens)
        assert trace.use_counts() == {1: 1, steps: len(looped)}
        return loss

    want_loss, got = jax.jit(jax.value_and_grad(whole))(values)
    apart_loss, by_use = jax.jit(jax.value_and_grad(apart))(
        [dict(values) for _ in range(steps)])
    np.testing.assert_allclose(apart_loss, want_loss, rtol=1e-6)
    for n in looped:
        uses = [np.asarray(g[n]) for g in by_use]
        if "exit_gate_" in n:
            # the last exit takes the remainder: its gate moves nothing
            assert np.abs(uses.pop()).max() == 0, n
        assert all(np.abs(u).max() > 0 for u in uses), n
        # the uses differ: a sum is not four times the first
        assert np.abs(uses[0] - uses[-1]).max() > 0, n
        assert got[n].dtype == values[n].dtype
        np.testing.assert_allclose(
            got[n], sum(uses), rtol=1e-4,
            atol=1e-5 * np.abs(sum(uses)).max() + 1e-9, err_msg=n)
    assert all(np.abs(g[embed]).max() == 0 for g in by_use[1:])
    np.testing.assert_allclose(
        got[embed], by_use[0][embed], rtol=1e-4,
        atol=1e-5 * np.abs(by_use[0][embed]).max())


def test_bfloat16_gradients_of_four_uses_are_bfloat16(model_py,
                                                      small_config):
    """Cast as the cell casts it: a looped parameter's gradient has the
    parameter's dtype, the gate's two stay float32."""
    step, plist, _prefix, _ = _small_model(small_config, model_py,
                                           gains=False)
    step.cast("bfloat16")
    values = {n: p.data().data for n, p in plist}
    tokens = _tokens(small_config, 1)

    def whole(values):
        with _traced(plist, values, train=True, mirror=True):
            return step.forward(tokens)

    grads = jax.jit(jax.grad(whole))(values)
    for n, g in grads.items():
        want = jnp.float32 if "exit_gate_" in n else jnp.bfloat16
        assert g.dtype == values[n].dtype == want, n
        assert np.isfinite(np.asarray(g, np.float32)).all(), n


def test_no_array_with_a_vocabulary_dimension_outlives_its_exit(
        model_py, small_config):
    """What the training step's segments keep: the inputs of the N layer
    segments and of the final norm's, and o and logsumexp of the N
    kernels, each stacked over the four trips of the scan; the four
    normed states (the exit segments' inputs), the targets and the gate
    logits; nothing (tokens, vocabulary)."""
    config = dict(small_config, vocab_size=640)
    step, plist, _prefix, values = _small_model(config, model_py)
    tokens = _tokens(config, 1)
    steps, layers = config["total_ut_steps"], config["num_hidden_layers"]
    hidden = config["hidden_size"]

    def whole(values):
        with _traced(plist, values, train=True, mirror=True):
            return step.forward(tokens)

    kept = [aval for aval, why in saved_residuals(whole, values)
            if "from the argument" not in why]
    assert not [a.shape for a in kept if config["vocab_size"] in a.shape]
    stacked = [a for a in kept if a.shape == (steps, 1, 256, hidden)]
    assert len(stacked) == layers + 1
    assert len([a for a in kept if a.shape == (1, 256, hidden)]) == steps
    heads = config["num_attention_heads"]
    named = [a for a in kept if a.ndim >= 5
             and a.shape[:3] == (steps, 1, heads)]
    assert len(named) == 2 * layers
    # without the training form the logits are what an exit hands out
    def exits(values):
        with _traced(plist, values, train=False):
            return step.forward(tokens)

    logits = jax.eval_shape(exits, values)[0]
    assert logits.shape == (1, 256, config["vocab_size"])


def test_step_program_holds_the_pass_and_the_exits_forward_and_backward(
        model_py, small_config):
    """With remat on as the cell runs it: the one traced pass under its
    scope inside the scan's loops and the exits under theirs, forward
    and backward; the trace read the embedding once and every other
    parameter four times (the pass's once a trip); N kernels traced and
    their residuals kept a trip; the gate stays float32 under the
    cast."""
    traffic = {"seq_len": 256, "batch": 1}
    before, kept = pa.route_counts(), residuals.kept_residuals()
    turned = rotary.route_counts()
    trainer = model_py.build(0, small_config, traffic, 1)
    assert trainer.remat
    for name, value in trainer.params.items():
        want = jnp.float32 if "exit_gate_" in name else jnp.bfloat16
        assert value.dtype == want, name
    tokens, = model_py.batch(0, small_config, traffic, np.asarray)
    first = float(trainer.step(tokens).asnumpy())
    assert np.isfinite(first)
    assert float(trainer.step(tokens).asnumpy()) < first
    steps, layers = (small_config["total_ut_steps"],
                     small_config["num_hidden_layers"])
    after = pa.route_counts()
    assert after["flash_causal"] == before["flash_causal"] + layers
    assert after["reference"] == before["reference"]
    assert rotary.route_counts() == {
        "kernel": turned["kernel"] + 2 * layers, "xla": turned["xla"]}
    now = residuals.kept_residuals()["flash_causal"]
    heads, head = small_config["num_attention_heads"], small_config["head_dim"]
    # o in bfloat16 and float32 rows of logsumexp, a layer and trip
    assert {k: now[k] - kept["flash_causal"][k] for k in now} == {
        "values": 2 * layers,
        "bytes": layers * 256 * heads * (2 * head + 4)}
    program = spmd.step_programs()[-1]
    assert program["param_uses"] == {1: 1, steps: 11 * layers + 4}
    names = set(program["ops"].values())

    def holds(*parts):
        return any(all(p in n for p in parts) for n in names)

    exit_name, scope = zoo.EXIT_NAME, f"/{zoo.PASS_NAME}/"
    for op in ("layer0/RMSNorm", "layer1/dot_product_attention",
               "layer1/rotary_embedding", "final/RMSNorm"):
        block, name = op.split("/")
        assert holds("/jvp(", scope, "/while/body/", f"/{op}/"), op
        assert holds("/transpose(jvp(", scope, "/while/body/",
                     f"/{block}/", f"/{name}/"), op
    assert not holds(scope, f"/{exit_name}/")
    assert not holds(f"/{zoo.PASS_NAME}0/")
    # (the CPU's fusions take the exit's forward product into the
    # reductions after it)
    assert holds("/jvp(", f"/{exit_name}/sum/")
    assert holds("/transpose(jvp(", f"/{exit_name}/", "/FullyConnected/")
    # the objective over the four exits, traced under the exits' name
    assert holds("/jvp(", f"/{exit_name}/", "log_sigmoid")
    # a layer application's forward, run again inside the pass's backward
    assert holds("/transpose(jvp(", scope, "rematted_computation/")


def test_a_model_that_is_not_traced_is_refused(small_config):
    model = zoo.OuroModel(**{k: small_config[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "rope_theta", "total_ut_steps")})
    model.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
    tokens = mx.nd.array(np.zeros((1, 128)), dtype="int32")
    with pytest.raises(mx.base.MXNetError, match="hybridize"):
        model(tokens)
    model.hybridize()
    *logits, gates = model(tokens)
    assert len(logits) == small_config["total_ut_steps"]
    assert logits[-1].shape == (1, 128, small_config["vocab_size"])
    assert gates.shape == (small_config["total_ut_steps"], 1, 128)


# ---- what the shared code still does for the other decoders --------------------

def _norm_residual_before_pr46(F, x, norm_weight, eps, mix, *args,
                               offset=0.0, **params):
    """`_decoder.norm_residual` as PR 45 had it: one gain."""
    mixed = mix(F, F.RMSNorm(x, norm_weight, eps=eps, offset=offset),
                *args, **params)
    if isinstance(mixed, (list, tuple)):
        return (x + mixed[0], *mixed[1:])
    return x + mixed


_FAMILIES = {"laguna": "laguna_xs2", "joyai": "joyai_llm_flash",
             "lfm2": "lfm2_8b_a1b", "nemotron_h": "nemotron3_super_120b",
             "evabyte": "evabyte"}


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_norm_residual_without_a_second_gain_lowers_to_the_program_it_did(
        family, monkeypatch):
    """The five other decoder families' step blocks at their rehearsal
    sizes, value and gradients: the lowered program is, character for
    character, the one they lowered to with PR 45's `norm_residual`."""
    name = _FAMILIES[family]
    config, model_py = _small_config(name), _load(name, "model")
    small = config["rehearsal"]["traffic"]
    traffic = {"seq_len": small["seq_len"], "batch": small["batch_per_chip"]}

    def lowered():
        np.random.seed(1)
        mx.random.seed(1)
        step = model_py._step_block(config)
        step.initialize(mx.initializer.Normal(0.05), ctx=mx.cpu())
        plist = sorted(step.collect_params().items())
        names = [n.split("_", 1)[1] for n, _ in plist]
        values = [p.data().data for _, p in plist]
        batch = model_py.batch(0, config, traffic, jnp.asarray)

        def loss(values, *batch):       # no array closed over
            with _traced(plist, dict(zip((n for n, _ in plist), values)),
                         train=True):
                out = step.forward(*batch)
            return out[0] if isinstance(out, (list, tuple)) else out

        return names, jax.jit(jax.value_and_grad(loss)).lower(
            values, *batch).as_text()

    names, text = lowered()
    for module in (_decoder, laguna, joyai, evabyte):
        monkeypatch.setattr(module, "norm_residual",
                            _norm_residual_before_pr46)
    assert lowered() == (names, text)
    assert any("norm_weight" in n for n in names)
