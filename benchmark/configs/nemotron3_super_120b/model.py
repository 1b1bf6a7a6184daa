"""nemotron3_super_120b as the benchmark runs it: one chip's share of the
zoo's Nemotron-H stack (one period of the layer pattern, the experts and
the vocabulary slice config.json says are held) under next-token
training, the resident batch, and the FLOPs the model needs.
"""
from __future__ import annotations

import functools
import json

import numpy as np

SAMPLES_UNIT = "sequences"
# config.json key -> NemotronHModel argument, where they differ
_RENAMED = {"pattern_held": "pattern", "n_routed_experts": "experts_held",
            "n_routed_experts_published": "n_routed_experts"}
_WIDTHS = ("vocab_size", "hidden_size", "mamba_num_heads", "mamba_head_dim",
           "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
           "num_attention_heads", "num_key_value_heads", "head_dim",
           "num_experts_per_tok", "moe_latent_size", "moe_intermediate_size",
           "moe_shared_expert_intermediate_size", "routed_scaling_factor",
           "layer_norm_epsilon", "time_step_min", "time_step_max",
           "time_step_floor", *_RENAMED)


def _step_block(config):
    """The training step as one block, as bert_base's: the loss is
    computed inside, so SPMDTrainer takes it with n_labels=0.  Returns
    (loss, logits, expert statistics); the trainer differentiates the
    first and `forward` reads the rest."""
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.nemotron_h import NemotronHModel

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = NemotronHModel(**{
                    _RENAMED.get(k, k): config[k] for k in _WIDTHS})

        def hybrid_forward(self, F, tokens):
            import jax
            import jax.numpy as jnp

            logits, stats = self.model(tokens)
            lsm = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(
                lsm, tokens[:, 1:, None].astype(jnp.int32), -1)
            return nll.mean(), logits, stats

    return Step()


def build(seed, config, traffic, chips):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    np.random.seed(seed)
    mx.random.seed(seed)
    step = _step_block(config)
    step.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
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
    labels are the tokens shifted by one, inside the step."""
    rng = np.random.RandomState(seed)
    return tuple(put(a) for a in _draw(rng, traffic["batch"], config,
                                       traffic))


def sample(seed, config, traffic):
    rng = np.random.RandomState(seed + 1)
    return _draw(rng, config["reference_check"]["sample"], config, traffic)


def _routes():
    """Which route the three new computations took, counted by the
    program at trace time."""
    from mxnet_tpu.ops import pallas_attention, ssm
    from mxnet_tpu.parallel import moe

    return {"attention": pallas_attention.route_counts(),
            "ssd_scan": ssm.route_counts(),
            "moe_experts": moe.route_counts()}


def system_logits(trainer, sample, config):
    _loss, logits, stats = trainer.forward(*sample)
    stats = np.asarray(stats.data)          # (expert layers, held + 1)
    rows, dropped = stats[:, :-1], int(stats[:, -1].sum())
    tokens = sample[0].size
    print("[info] " + json.dumps({"moe": {
        "assignments_on_held_experts": int(rows.sum()),
        "expected": tokens * rows.shape[0] * config["num_experts_per_tok"]
        * config["n_routed_experts"] / config["n_routed_experts_published"],
        "tokens_per_held_expert": {"min": int(rows.min()),
                                   "mean": float(rows.mean()),
                                   "max": int(rows.max())},
        "dropped": dropped}, "routes": _routes()}), flush=True)
    if dropped:
        raise RuntimeError(f"the expert layers dropped {dropped} assignments")
    return {"lm": np.asarray(logits.data, np.float32)}


@functools.lru_cache(maxsize=1)
def _reference_program(reference, frozen_config):
    """One program for both uses of the reference (the sample's logits,
    the batch's loss): it is compiled once a run."""
    import jax

    config = json.loads(frozen_config)

    def run(params, tokens):
        scores = reference.logits(params, tokens, config)
        return scores, reference.loss_of(scores, tokens)
    return jax.jit(run)


def _reference(reference, params, tokens, config):
    return _reference_program(reference, json.dumps(config, sort_keys=True))(
        params, np.asarray(tokens))


def reference_logits(reference, params, sample, config):
    scores, _loss = _reference(reference, params, sample[0], config)
    return {"lm": np.asarray(scores, np.float32)}


def reference_first_loss(reference, params, batch, config):
    """No dropout anywhere, so step 1's loss has a deterministic
    reference: the reference's loss on the resident batch."""
    return float(_reference(reference, params, batch[0], config)[1])


def forward_macs_per_token(config, seq_len):
    """Multiply-adds a token of the forward pass, by layer kind and for
    the head: {"M": ..., "*": ..., "E": ..., "head": ...}; embedding
    lookups, norms, activations, softmax and the top-k not counted."""
    d = config["hidden_size"]
    heads, hd = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    inner = heads * hd
    conv_dim = inner + 2 * groups * n
    mamba = (d * (inner + conv_dim + heads) + inner * d     # in, out
             + conv_dim * config["conv_kernel"]
             + ssd_macs_per_token(config))
    qkv = config["head_dim"] * (config["num_attention_heads"] * 2
                                + config["num_key_value_heads"] * 2)
    attention = (d * qkv        # q, k, v and the output projection
                 # scores and weighted values over the seq_len / 2 keys a
                 # causal query sees on average
                 + 2 * (seq_len // 2) * config["num_attention_heads"]
                 * config["head_dim"])
    latent, expert = config["moe_latent_size"], config["moe_intermediate_size"]
    held_per_token = (config["num_experts_per_tok"]
                      * config["n_routed_experts"]
                      / config["n_routed_experts_published"])
    moe = (d * config["n_routed_experts_published"]         # router
           + 2 * d * latent                                 # down, up
           + 2 * d * config["moe_shared_expert_intermediate_size"]
           + held_per_token * 2 * latent * expert)
    return {"M": mamba, "*": attention, "E": moe,
            "head": d * config["vocab_size"]}


def ssd_macs_per_token(config):
    """The scan's four products, a token and layer, as the chunked
    algorithm needs them (whole chunk x chunk blocks, the state's
    recurrence over chunks left out: 1/chunk of the rest)."""
    heads, hd = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    chunk = config["chunk_size"]
    return (groups * chunk * n          # scores C B^T inside a chunk
            + heads * chunk * hd        # decayed scores x values
            + heads * n * hd            # the state a chunk leaves behind
            + heads * n * hd)           # the entering state read through C


def flops_per_sample(config, traffic):
    """Trained FLOPs per sequence of seq_len tokens: 2 per multiply-add,
    backward = 2 x forward, no recomputation, no optimizer; the routed
    experts at their expected share of a token's 22 assignments."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    per_token = sum(macs[kind] for kind in config["pattern_held"]) \
        + macs["head"]
    return 3 * 2 * per_token * traffic["seq_len"]


def ssd_flops_per_sample(config, traffic):
    """The share of flops_per_sample that is the scan's own products:
    what `ssd_roofline_pct` holds the `ssd_scan` scope's time against."""
    return (3 * 2 * ssd_macs_per_token(config)
            * config["pattern_held"].count("M") * traffic["seq_len"])
