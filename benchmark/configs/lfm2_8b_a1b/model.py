"""lfm2_8b_a1b as the benchmark runs it: one chip's share of the zoo's
LFM2 stack (the leading dense layer and three periods of attention and
gated short-convolution layers, the experts and the vocabulary slice
config.json says are held, the head tied to the embedding) under
next-token training, the resident batch, and the FLOPs and bytes the
model needs.
"""
from __future__ import annotations

import functools
import json
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import lookup

SAMPLES_UNIT = "sequences"
# config.json key -> Lfm2Model argument, where they differ
_RENAMED = {"num_experts": "experts_held",
            "num_experts_published": "num_experts"}
_ARGUMENTS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_dense_layers",
              "num_attention_heads", "num_key_value_heads", "layer_types",
              "conv_L_cache", "conv_bias", "rope_theta",
              "num_experts_per_tok", "norm_topk_prob",
              "routed_scaling_factor", "norm_eps", *_RENAMED)


def _step_block(config):
    """The training step as one block, as the other decoders': the loss
    is computed inside, so SPMDTrainer takes it with n_labels=0.  Returns
    (loss, logits, expert statistics, the probed layer's operator
    output); the trainer differentiates the first and drops the rest,
    `forward` reads them."""
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.lfm2 import Lfm2Model

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = Lfm2Model(
                    operator_outputs=(_probed_layer(config),),
                    **{_RENAMED.get(k, k): config[k] for k in _ARGUMENTS})

        def hybrid_forward(self, F, tokens):
            import jax
            import jax.numpy as jnp

            logits, stats, operator = self.model(tokens)
            lsm = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(
                lsm, tokens[:, 1:, None].astype(jnp.int32), -1)
            return nll.mean(), logits, stats, operator

    return Step()


def _probed_layer(config):
    """The first attention layer held: where `reference_check` also
    compares an operator's own output (config.json says why)."""
    return config["layer_types"].index("full_attention")


def _seeded_normal(sigma, seed, pool):
    """laguna_xs2's threaded float32 draws (its model.py: 16 streams a
    matrix, the same weights on any number of cores), as evabyte's and
    joyai_llm_flash's model.py take them."""
    return lookup._module(lookup.BENCH_DIR, "configs", "laguna_xs2",
                          "model.py")._seeded_normal(sigma, seed, pool)


def build(seed, config, traffic, chips):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    # the taps draw from numpy's global stream (the block's own Uniform)
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
    """`n` sequences of token ids uniform over the vocabulary held: no
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


def _routes():
    """Which route the computations with several took, counted by the
    program at trace time."""
    from mxnet_tpu.ops import pallas_attention, rotary
    from mxnet_tpu.parallel import moe

    return {"attention": pallas_attention.route_counts(),
            "rotary": rotary.route_counts(),
            "moe_experts": moe.route_counts()}


def system_logits(trainer, sample, config):
    from mxnet_tpu.parallel import moe

    _loss, logits, stats, operator = trainer.forward(*sample)
    stats = np.asarray(stats.data)          # (sparse layers, held + 1)
    rows, dropped = stats[:, :-1], int(stats[:, -1].sum())
    # a layer's assignments on the held experts under even routing
    expected = (sample[0].size * config["num_experts_per_tok"]
                * config["num_experts"] // config["num_experts_published"])
    print("[info] " + json.dumps({"moe": {
        "assignments_on_held_experts": int(rows.sum()),
        "expected": float(expected * rows.shape[0]),
        "assignments_a_layer": [int(r.sum()) for r in rows],
        "tokens_per_held_expert": {"min": int(rows.min()),
                                   "mean": float(rows.mean()),
                                   "max": int(rows.max())},
        "row_chunk": moe.row_chunk(expected),
        "plan_chunks_a_layer": [int(moe.plan_chunks(r, expected))
                                for r in rows],
        "dropped": dropped}, "routes": _routes()}), flush=True)
    if dropped:
        raise RuntimeError(f"the expert layers dropped {dropped} assignments")
    return {"lm": np.asarray(logits.data, np.float32),
            "attention_operator": np.asarray(operator.data, np.float32)}


@functools.lru_cache(maxsize=1)
def _reference_program(reference, frozen_config):
    """One program for both uses of the reference (the sample's logits
    and probed operator output, each of the batch's sequences' loss): it
    is compiled once a run, for one sequence."""
    import jax

    config = json.loads(frozen_config)

    def run(params, tokens):
        scores = reference.logits(params, tokens, config)
        operator = reference.operator_outputs(params, tokens, config,
                                              _probed_layer(config))
        return scores, operator, reference.loss_of(scores, tokens)
    return jax.jit(run)


def _reference(reference, params, tokens, config):
    return _reference_program(reference, json.dumps(config, sort_keys=True))(
        params, np.asarray(tokens))


def reference_logits(reference, params, sample, config):
    scores, operator, _loss = _reference(reference, params, sample[0],
                                         config)
    return {"lm": np.asarray(scores, np.float32),
            "attention_operator": np.asarray(operator, np.float32)}


def reference_first_loss(reference, params, batch, config):
    """No dropout anywhere, so step 1's loss has a deterministic
    reference: the reference's loss on the resident batch, a sequence at
    a time through the sample's program (every sequence predicts the same
    number of positions, so the batch's mean is the mean of theirs).  The
    info line keeps each sequence's: half their distance is what a step
    that trained on one sequence of the two would read off the batch's."""
    losses = [float(_reference(reference, params, row[None], config)[2])
              for row in np.asarray(batch[0])]
    print("[info] " + json.dumps(
        {"reference_loss_by_sequence": losses}), flush=True)
    return float(np.mean(losses))


def _causal_pairs(seq_len):
    """(query, key) pairs with key <= query."""
    return seq_len * (seq_len + 1) // 2


def forward_macs_per_token(config, seq_len):
    """Multiply-adds a token of the forward pass: {"conv_projections",
    "attention_projections", "attention_cores", "experts", "dense",
    "head"}; embedding lookups, norms, the taps, rotary, activations,
    softmax and the top-k not counted.  The cores count their causal
    pairs exactly, at the published 64 for the scores and 64 for the
    values whatever the kernel multiplies."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    kv, head = config["num_key_value_heads"], d // heads
    kinds = config["layer_types"]
    convs = kinds.count("conv")
    attentions = len(kinds) - convs
    sparse = len(kinds) - config["num_dense_layers"]
    held_per_token = (config["num_experts_per_tok"] * config["num_experts"]
                      / config["num_experts_published"])
    return {
        # W_in (d -> 3d) and W_out
        "conv_projections": convs * 4 * d * d,
        # q, k, v and the output projection
        "attention_projections": attentions * (2 * d * d
                                               + 2 * d * kv * head),
        # scores and weighted values
        "attention_cores": attentions * heads * 2 * head
        * _causal_pairs(seq_len) / seq_len,
        "experts": sparse * (
            d * config["num_experts_published"]             # router
            + held_per_token * 3 * d * config["moe_intermediate_size"]),
        "dense": config["num_dense_layers"] * 3 * d
        * config["intermediate_size"],
        "head": d * config["vocab_size"]}


def flops_per_sample(config, traffic):
    """Trained FLOPs per sequence of seq_len tokens: 2 per multiply-add,
    backward = 2 x forward, no recomputation, no optimizer; the routed
    experts at their expected share of a token's 4 assignments."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    return 3 * 2 * sum(macs.values()) * traffic["seq_len"]


def attention_flops_per_sample(config, traffic):
    """The share of flops_per_sample that is the causal cores' own two
    products over the causal pairs: what `head64_attention_roofline_pct`
    holds the `dot_product_attention` scope's time against."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    return 3 * 2 * macs["attention_cores"] * traffic["seq_len"]


def short_conv_block_flops_per_sample(config, traffic):
    """The share of flops_per_sample that is the conv operators' two
    projections (W_in 2048 -> 3 x 2048, W_out): the other term of
    `short_conv_block_roofline_pct`'s floor; the op's own ~12 FLOPs a
    channel and position are not counted, its bytes are."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    return 3 * 2 * macs["conv_projections"] * traffic["seq_len"]


def short_conv_bytes_per_sample(config, traffic):
    """HBM bytes the `short_conv` ops of one sequence cannot do without,
    in the configuration's 2-byte dtype, no recomputation: a conv layer's
    forward reads the three streams and the taps and writes y; its
    backward reads the three streams, the taps and y's cotangent and
    writes the three streams' cotangents and the taps'.  One term of
    what `short_conv_block_roofline_pct` holds the `conv` scope's time
    against."""
    stream = traffic["seq_len"] * config["hidden_size"]
    taps = config["hidden_size"] * config["conv_L_cache"]
    convs = config["layer_types"].count("conv")
    return convs * ((3 + 1 + 3 + 1 + 3) * stream + 3 * taps) * 2
