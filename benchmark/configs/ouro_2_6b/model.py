"""ouro_2_6b as the benchmark runs it: one pipeline stage's layers of the
zoo's Ouro stack, looped four times on their own output with the final
norm and an exit after every pass, beside the embedding, the gate and
the untied head over the whole vocabulary, under the expected-exit
training objective; the resident batch, and the FLOPs the model needs.
"""
from __future__ import annotations

import functools
import json
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import lookup

SAMPLES_UNIT = "sequences"
_ARGUMENTS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "rope_theta",
              "total_ut_steps", "rms_norm_eps", "tie_word_embeddings",
              "hidden_act")


def _step_block(config):
    """The training step as one block, as the other decoders': the loss
    is computed inside, so SPMDTrainer takes it with n_labels=0.  A
    training trace takes the model's training form (an exit hands back a
    token's loss and gate logit, never its logits) and returns the
    objective, traced under the exits' name; an inference trace
    (`SPMDTrainer.forward`) returns the four exits' logits and the exit
    distribution (B, S, 4)."""
    import jax

    from mxnet_tpu.gluon.block import HybridBlock, current_trace
    from mxnet_tpu.gluon.model_zoo import ouro

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = ouro.OuroModel(
                    **{k: config[k] for k in _ARGUMENTS})

        def hybrid_forward(self, F, tokens):
            if not current_trace().train:
                *logits, gates = self.model(tokens)
                return (*logits, ouro.exit_pdf(gates))
            nll, gates = self.model(tokens, tokens[:, 1:])
            with jax.named_scope(ouro.EXIT_NAME):
                return ouro.exit_loss(nll, gates,
                                      config["exit_entropy_beta"])

    return Step()


def _seeded_normal(sigma, seed, pool):
    """laguna_xs2's threaded float32 draws (its model.py: 16 streams a
    matrix, the same weights on any number of cores), as the other
    decoders' model.py take them."""
    return lookup._module(lookup.BENCH_DIR, "configs", "laguna_xs2",
                          "model.py")._seeded_normal(sigma, seed, pool)


def build(seed, config, traffic, chips):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    np.random.seed(seed % 2 ** 32)
    mx.random.seed(seed)
    step = _step_block(config)
    with ThreadPoolExecutor(16) as pool:
        step.initialize(_seeded_normal(config["init_std"], seed, pool),
                        ctx=mx.cpu())
    step.cast(config["dtype"])
    opt = dict(config["optimizer"])
    return parallel.SPMDTrainer(
        step, lambda loss: loss, opt.pop("name"), opt,
        mesh=parallel.make_mesh(dp=chips), n_labels=0,
        remat=config["remat"])


def _draw(rng, n, config, traffic):
    """`n` sequences of token ids uniform over the whole vocabulary: no
    padding, no document boundary."""
    return (rng.randint(0, config["vocab_size"],
                        (n, traffic["seq_len"])).astype(np.int32),)


def batch(seed, config, traffic, put):
    """The resident batch, in the order Step.hybrid_forward takes it; the
    targets are the tokens shifted by one, inside the step."""
    rng = np.random.RandomState(seed % 2 ** 32)
    return tuple(put(a) for a in _draw(rng, traffic["batch"], config,
                                       traffic))


def sample(seed, config, traffic):
    rng = np.random.RandomState((seed + 1) % 2 ** 32)
    return _draw(rng, config["reference_check"]["sample"], config, traffic)


def _exit_names(config):
    return [f"exit{t + 1}" for t in range(config["total_ut_steps"])]


def system_logits(trainer, sample, config):
    """Five entries, all held to the one tolerance: every exit's logits
    and the exit distribution."""
    *logits, pdf = trainer.forward(*sample)
    _counted.update(_counters())     # the inference program's, so far
    out = {"exit_pdf": np.asarray(pdf.data, np.float32)}
    for name in _exit_names(config):        # an exit at a time, off the chip
        out[name] = np.asarray(logits.pop(0).data).astype(np.float32)
    return out


@functools.lru_cache(maxsize=1)
def _reference_programs(reference, frozen_config):
    """The reference's two programs, compiled once a run for one
    sequence: the four normed states, their gate logits and the
    objective; and one exit's logits from its states."""
    import jax

    config = json.loads(frozen_config)

    def run(params, tokens):
        z, gate_logits = reference.exits(params, tokens, config)
        return z, gate_logits, reference.loss_of(params, z, gate_logits,
                                                 tokens, config)
    return jax.jit(run), jax.jit(reference.exit_logits)


def _reference(reference, params, tokens, config):
    """-> (the five entries `reference_logits` hands out, the objective)
    of `tokens` (B, S): one run of the states' program, then an exit's
    logits at a time."""
    import jax.numpy as jnp

    run, exit_logits = _reference_programs(
        reference, json.dumps(config, sort_keys=True))
    z, gate_logits, loss = run(params, np.asarray(tokens))
    out = {"exit_pdf": np.asarray(reference.exit_pdf(
        jnp.moveaxis(gate_logits, 1, 0)), np.float32)}
    for t, name in enumerate(_exit_names(config)):
        out[name] = np.asarray(exit_logits(params, z[:, t]), np.float32)
    return out, float(loss)


def _reference_loss(reference, params, tokens, config):
    run, _ = _reference_programs(reference,
                                 json.dumps(config, sort_keys=True))
    return float(run(params, np.asarray(tokens))[2])


def reference_logits(reference, params, sample, config):
    return _reference(reference, params, sample[0], config)[0]


def reference_first_loss(reference, params, batch, config):
    """No dropout anywhere, so step 1's objective has a deterministic
    reference: the reference's on the resident batch, a sequence at a
    time through the sample's program (every sequence predicts the same
    number of positions, so the batch's mean is the mean of theirs)."""
    losses = [_reference_loss(reference, params, row[None], config)
              for row in np.asarray(batch[0])]
    print("[info] " + json.dumps(
        {"reference_loss_by_sequence": losses}), flush=True)
    return float(np.mean(losses))


def _causal_pairs(seq_len):
    """(query, key) pairs with key <= query."""
    return seq_len * (seq_len + 1) // 2


def forward_macs_per_token(config, seq_len):
    """Multiply-adds a token of the forward pass, every pass counted:
    {"projections", "mlp", "attention_cores", "exits"}; the embedding
    lookup, the norms, rotary, activations, softmax, the gate's one
    column and the objective not counted.  The cores count their causal
    pairs exactly, at 128 for the scores and 128 for the values."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    kv, head = config["num_key_value_heads"], config["head_dim"]
    applications = config["total_ut_steps"] * config["num_hidden_layers"]
    return {
        # q, k, v and the output projection
        "projections": applications * (2 * d * heads * head
                                       + 2 * d * kv * head),
        "mlp": applications * 3 * d * config["intermediate_size"],
        "attention_cores": applications * heads * 2 * head
        * _causal_pairs(seq_len) / seq_len,
        "exits": config["total_ut_steps"] * d * config["vocab_size"]}


def _trained_flops(config, traffic, *parts):
    """2 a multiply-add, backward = 2 x forward, no recomputation."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    return 3 * 2 * sum(macs[p] for p in parts or macs) * traffic["seq_len"]


def flops_per_sample(config, traffic):
    """Trained FLOPs per sequence of seq_len tokens, every pass of the
    looped stack and every exit counted, no optimizer.  run.py's one
    call of this file after the step program is built, so the `loop`
    info line is said from here."""
    _say_loop(config)
    return _trained_flops(config, traffic)


def attention_flops_per_sample(config, traffic):
    """The share of flops_per_sample that is the causal cores' own two
    products over the causal pairs, in every application of every layer:
    what `mha128_attention_roofline_pct` holds the
    `dot_product_attention` scope's time against."""
    return _trained_flops(config, traffic, "attention_cores")


def exit_flops_per_sample(config, traffic):
    """The share of flops_per_sample that is the exits' products with
    the head's array (hidden x vocabulary a token and exit), forward and
    backward, no recomputation: what `exit_roofline_pct` holds the
    `exit` scope's time against."""
    return _trained_flops(config, traffic, "exits")


_counted = {}       # the program's counters when the step's trace began
_said = []


def _counters():
    """What the program counts at trace time, since import: the named
    values its recomputed segments keep and the routes attention and the
    rotation took."""
    from mxnet_tpu.ops import pallas_attention, residuals, rotary

    return {"kept_residuals": residuals.kept_residuals()["flash_causal"],
            "attention_routes": pallas_attention.route_counts(),
            "rotary_routes": rotary.route_counts()}


def _say_loop(config):
    """`[info] {"loop": ...}` once a process, from what the program
    counted while it traced the STEP (the counters less what they read
    after the inference program of `system_logits`): how often the trace
    read each Parameter (the pass's reads once a trip of its scan), what
    the N layer segments of the one traced pass keep by name, a trip and
    over the four (an exit's segment names nothing and keeps its input
    alone), and the routes the layers' attention and rotation took.
    Nothing where no step program exists yet."""
    from mxnet_tpu.parallel import spmd

    programs = spmd.step_programs()
    if _said or not programs:
        return
    _said.append(True)
    steps, layers = config["total_ut_steps"], config["num_hidden_layers"]
    grown = {name: {k: v - _counted.get(name, {}).get(k, 0)
                    for k, v in now.items()}
             for name, now in _counters().items()}
    kept = grown.pop("kept_residuals")
    print("[info] " + json.dumps({"loop": {
        "ut_steps": steps, "layers_traced": layers,
        "layer_applications": steps * layers,
        "param_uses": programs[-1].get("param_uses"),
        "param_uses_expected": {1: 1, steps: 11 * layers + 4},
        "kept_residuals_a_trip": kept,
        "kept_bytes_all_trips": steps * kept["bytes"],
        **grown}}), flush=True)
