"""joyai_llm_flash as the benchmark runs it: one chip's share of the zoo's
JoyAI-LLM-Flash stack (the leading dense layer, the sparse layers, the
experts and the vocabulary slice config.json says are held, and the
multi-token-prediction module) under next-token training with the
module's second loss, the resident batch, and the FLOPs the model needs.
"""
from __future__ import annotations

import functools
import json
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import lookup

SAMPLES_UNIT = "sequences"
# config.json key -> JoyAIModel argument, where they differ
_RENAMED = {"n_routed_experts": "experts_held",
            "n_routed_experts_published": "n_routed_experts"}
_ARGUMENTS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "first_k_dense_replace",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "rope_theta", "num_experts_per_tok", "moe_intermediate_size",
              "n_shared_experts", "routed_scaling_factor",
              "num_nextn_predict_layers", "rms_norm_eps", *_RENAMED)


def _mean_cross_entropy(logits, targets):
    """Float32 mean cross-entropy of logits (B, n, V) against targets
    (B, n).  Plain on purpose: a recomputed segment around it, blocks of
    positions, a mask in place of the slices or log-sum-exp less the
    target's score each made the step program plan 0.02-4.1 GiB MORE
    temporaries for a described v5e (PERF.md, PR 39)."""
    import jax
    import jax.numpy as jnp

    lsm = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.take_along_axis(
        lsm, targets[..., None].astype(jnp.int32), -1).mean()


def _step_block(config):
    """The training step as one block, as the other decoders': the loss
    is computed inside, so SPMDTrainer takes it with n_labels=0.  Returns
    (loss, main logits, the module's logits, expert statistics, the
    loss's two terms); the trainer differentiates the first and `forward`
    reads the rest.  The module's term is traced under the module's name
    (`mtp_device_ms` reads it with the module's block)."""
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.joyai import MTP_NAME, JoyAIModel

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = JoyAIModel(**{_RENAMED.get(k, k): config[k]
                                           for k in _ARGUMENTS})

        def hybrid_forward(self, F, tokens):
            import jax

            logits, ahead, stats = self.model(tokens)
            main = _mean_cross_entropy(logits[:, :-1], tokens[:, 1:])
            with jax.named_scope(MTP_NAME):
                second = _mean_cross_entropy(ahead[:, :-2], tokens[:, 2:])
            return (main + config["mtp_loss_weight"] * second, logits,
                    ahead, stats, main, second)

    return Step()


def _seeded_normal(sigma, seed, pool):
    """laguna_xs2's threaded float32 draws (its model.py: 16 streams a
    matrix, the same weights on any number of cores), as evabyte's
    model.py takes them; one copy of that code until the harness owns it
    (PERF.md section 7)."""
    return lookup._module(lookup.BENCH_DIR, "configs", "laguna_xs2",
                          "model.py")._seeded_normal(sigma, seed, pool)


def build(seed, config, traffic, chips):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    np.random.seed(seed)
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
    targets (the next token for the main head, the one after it for the
    module) are the inputs shifted, inside the step."""
    rng = np.random.RandomState(seed)
    return tuple(put(a) for a in _draw(rng, traffic["batch"], config,
                                       traffic))


def sample(seed, config, traffic):
    rng = np.random.RandomState(seed + 1)
    return _draw(rng, config["reference_check"]["sample"], config, traffic)


def _routes():
    """Which route the computations with several took, counted by the
    program at trace time."""
    from mxnet_tpu.ops import pallas_attention
    from mxnet_tpu.parallel import moe

    return {"attention": pallas_attention.route_counts(),
            "moe_experts": moe.route_counts()}


def system_logits(trainer, sample, config):
    from mxnet_tpu.parallel import moe

    _loss, logits, ahead, stats, main, second = trainer.forward(*sample)
    stats = np.asarray(stats.data)      # (sparse layers + module, held + 1)
    rows, dropped = stats[:, :-1], int(stats[:, -1].sum())
    # a layer's assignments on the held experts under even routing
    expected = (sample[0].size * config["num_experts_per_tok"]
                * config["n_routed_experts"]
                // config["n_routed_experts_published"])
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
        "dropped": dropped}, "routes": _routes(),
        "sample_loss_terms": {"main": float(main.asnumpy()),
                              "mtp": float(second.asnumpy())}}), flush=True)
    if dropped:
        raise RuntimeError(f"the expert layers dropped {dropped} assignments")
    return {"lm": np.asarray(logits.data, np.float32),
            "mtp": np.asarray(ahead.data, np.float32)}


@functools.lru_cache(maxsize=1)
def _reference_program(reference, frozen_config):
    """One program for both uses of the reference (the sample's two sets
    of logits, each of the batch's sequences' loss terms): it is compiled
    once a run, for one sequence."""
    import jax

    config = json.loads(frozen_config)

    def run(params, tokens):
        main, ahead = reference.logits(params, tokens, config)
        return main, ahead, reference.loss_terms(main, ahead, tokens)
    return jax.jit(run)


def _reference(reference, params, tokens, config):
    return _reference_program(reference, json.dumps(config, sort_keys=True))(
        params, np.asarray(tokens))


def reference_logits(reference, params, sample, config):
    main, ahead, _terms = _reference(reference, params, sample[0], config)
    return {"lm": np.asarray(main, np.float32),
            "mtp": np.asarray(ahead, np.float32)}


def reference_first_loss(reference, params, batch, config):
    """No dropout anywhere, so step 1's loss has a deterministic
    reference: the reference's two terms on the resident batch, a
    sequence at a time through the sample's program (every sequence has
    the same number of targets, so the batch's means are the means of
    theirs), joined as the step joins them.  The info line keeps each
    sequence's terms: half the sequences' distance is what a step that
    trained on one sequence of the two would read off, and
    `mtp_loss_weight` x the module's term what a step without the
    module's loss would."""
    terms = np.asarray([
        [float(t) for t in _reference(reference, params, row[None],
                                      config)[2]]
        for row in np.asarray(batch[0])])           # (sequences, 2)
    print("[info] " + json.dumps({
        "reference_loss_terms_by_sequence": terms.tolist()}), flush=True)
    main, second = terms.mean(0)
    return float(main + config["mtp_loss_weight"] * second)


def _causal_pairs(seq_len):
    """(query, key) pairs with key <= query."""
    return seq_len * (seq_len + 1) // 2


def forward_macs_per_token(config, seq_len):
    """Multiply-adds a token of the forward pass: {"latent_projections",
    "latent_cores", "experts", "dense", "join", "heads"}; embedding
    lookups, norms, rotary, activations, softmax and the top-k not
    counted.  The module's layer is one more sparse layer, its pass
    through the head one more head.  The cores count their causal pairs
    exactly, at the published 192 for the scores and 128 for the values
    whatever the kernel multiplies."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, q_rank = config["v_head_dim"], config["q_lora_rank"]
    kv_rank = config["kv_lora_rank"]
    modules = config["num_nextn_predict_layers"]
    layers = config["num_hidden_layers"] + modules
    sparse = layers - config["first_k_dense_replace"]
    held_per_token = (config["num_experts_per_tok"]
                      * config["n_routed_experts"]
                      / config["n_routed_experts_published"])
    width = config["moe_intermediate_size"]
    return {
        # W_qa, W_qb, W_kva, W_kvb, W_o
        "latent_projections": layers * (
            d * q_rank + q_rank * heads * (nope + rope)
            + d * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + heads * v * d),
        # scores and weighted values
        "latent_cores": layers * heads * (nope + rope + v)
        * _causal_pairs(seq_len) / seq_len,
        "experts": sparse * (
            d * config["n_routed_experts_published"]        # router
            + 3 * d * config["n_shared_experts"] * width
            + held_per_token * 3 * d * width),
        "dense": config["first_k_dense_replace"] * 3 * d
        * config["intermediate_size"],
        "join": modules * 2 * d * d,
        "heads": (1 + modules) * d * config["vocab_size"]}


def flops_per_sample(config, traffic):
    """Trained FLOPs per sequence of seq_len tokens: 2 per multiply-add,
    backward = 2 x forward, no recomputation, no optimizer; both heads
    and the module counted, the routed experts at their expected share
    of a token's 8 assignments."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    return 3 * 2 * sum(macs.values()) * traffic["seq_len"]


def latent_attention_flops_per_sample(config, traffic):
    """The share of flops_per_sample that is the latent cores' own two
    products over the causal pairs: what `mla_attention_roofline_pct`
    holds the `latent_attention` scope's time against."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    return 3 * 2 * macs["latent_cores"] * traffic["seq_len"]
