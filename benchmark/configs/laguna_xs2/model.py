"""laguna_xs2 as the benchmark runs it: one chip's share of the zoo's
Laguna stack (the leading dense layer and two periods of window and full
attention layers, the experts and the vocabulary slice config.json says
are held) under next-token training, the resident batch, and the FLOPs
the model needs.
"""
from __future__ import annotations

import functools
import json
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SAMPLES_UNIT = "sequences"
# config.json key -> LagunaModel argument, where they differ
_RENAMED = {"num_experts": "experts_held",
            "num_experts_published": "num_experts"}
_PER_LAYER = ("layer_types", "mlp_layer_types",
              "num_attention_heads_per_layer")
_WIDTHS = ("vocab_size", "hidden_size", "intermediate_size",
           "num_key_value_heads", "head_dim", "sliding_window",
           "rope_parameters", "num_experts_per_tok",
           "moe_intermediate_size", "shared_expert_intermediate_size",
           "moe_routed_scaling_factor", "rms_norm_eps", *_RENAMED)


def _model_arguments(config):
    """The published per-layer lists cut to the layers held."""
    depth = config["num_hidden_layers"]
    return {**{_RENAMED.get(k, k): config[k] for k in _WIDTHS},
            **{k: config[k][:depth] for k in _PER_LAYER}}


def _step_block(config):
    """The training step as one block, as bert_base's: the loss is
    computed inside, so SPMDTrainer takes it with n_labels=0.  Returns
    (loss, logits, expert statistics); the trainer differentiates the
    first and `forward` reads the rest."""
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.laguna import LagunaModel

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = LagunaModel(**_model_arguments(config))

        def hybrid_forward(self, F, tokens):
            import jax
            import jax.numpy as jnp

            logits, stats = self.model(tokens)
            lsm = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(
                lsm, tokens[:, 1:, None].astype(jnp.int32), -1)
            return nll.mean(), logits, stats

    return Step()


# How many streams a matrix is drawn from: a constant, so that a seed
# gives the same weights on any number of cores.
_SLABS = 16


def _seeded_normal(sigma, seed, pool):
    """Normal(0, sigma) for every matrix, in float32 from numpy's
    Generator, a matrix in _SLABS slabs with a stream each (spawned in
    order from `seed`) on `pool`'s threads: 1.25 B draws take seconds
    where `mx.initializer.Normal` (numpy's legacy float64 normals, one
    thread) took a minute of every run's set-up."""
    import mxnet_tpu as mx

    streams = np.random.SeedSequence(seed)

    def draw(slab, stream):
        rng = np.random.Generator(np.random.SFC64(stream))
        rng.standard_normal(slab.size, dtype=np.float32, out=slab)
        slab *= sigma

    class SeededNormal(mx.initializer.Normal):
        def _init_weight(self, name, arr):
            slabs = np.array_split(arr.reshape(-1), _SLABS)
            list(pool.map(draw, slabs, streams.spawn(_SLABS)))

    return SeededNormal(sigma)


def build(seed, config, traffic, chips):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    np.random.seed(seed)
    mx.random.seed(seed)
    step = _step_block(config)
    with ThreadPoolExecutor(_SLABS) as pool:
        step.initialize(_seeded_normal(0.02, seed, pool), ctx=mx.cpu())
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
    """Which route the computations with several took, counted by the
    program at trace time."""
    from mxnet_tpu.ops import pallas_attention, ssm
    from mxnet_tpu.parallel import moe

    return {"attention": pallas_attention.route_counts(),
            "ssd_scan": ssm.route_counts(),
            "moe_experts": moe.route_counts()}


def system_logits(trainer, sample, config):
    from mxnet_tpu.parallel import moe

    _loss, logits, stats = trainer.forward(*sample)
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
    return {"lm": np.asarray(logits.data, np.float32)}


@functools.lru_cache(maxsize=1)
def _reference_program(reference, frozen_config):
    """One program for both uses of the reference (the sample's logits,
    each of the batch's sequences' loss): it is compiled once a run, for
    one sequence."""
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
    reference: the reference's loss on the resident batch, a sequence at
    a time through the sample's program (every sequence predicts the same
    number of positions, so the batch's mean is the mean of theirs).  The
    info line keeps each sequence's: half their distance is what a step
    that trained on one sequence of the two would read off the batch's."""
    losses = [float(_reference(reference, params, row[None], config)[1])
              for row in np.asarray(batch[0])]
    print("[info] " + json.dumps(
        {"reference_loss_by_sequence": losses}), flush=True)
    return float(np.mean(losses))


def _layers(config):
    depth = config["num_hidden_layers"]
    return list(zip(*(config[k][:depth] for k in _PER_LAYER)))


def _causal_pairs(seq_len, reach):
    """(query, key) pairs with 0 <= query - key < reach."""
    reach = min(reach, seq_len)
    return reach * (reach + 1) // 2 + (seq_len - reach) * reach


def forward_macs_per_token(config, seq_len):
    """Multiply-adds a token of the forward pass: {"projections",
    "full_cores", "window_cores", "experts", "dense", "head"}; embedding
    lookups, norms, rotary, activations, gates' sigmoid, softmax and the
    top-k not counted.  The cores count their causal pairs exactly."""
    d, hd = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    held_per_token = (config["num_experts_per_tok"] * config["num_experts"]
                      / config["num_experts_published"])
    macs = dict.fromkeys(("projections", "full_cores", "window_cores",
                          "experts", "dense", "head"), 0.0)
    for kind, mlp, heads in _layers(config):
        # q, k, v, the head gate and the output projection
        macs["projections"] += d * (2 * hd * (heads + kv) + heads)
        reach = config["sliding_window"] if kind == "sliding_attention" \
            else seq_len
        core = "window_cores" if kind == "sliding_attention" \
            else "full_cores"
        # scores and weighted values
        macs[core] += 2 * heads * hd * _causal_pairs(seq_len, reach) \
            / seq_len
        if mlp == "dense":
            macs["dense"] += 3 * d * config["intermediate_size"]
        else:
            macs["experts"] += (
                d * config["num_experts_published"]         # router
                + 3 * d * config["shared_expert_intermediate_size"]
                + held_per_token * 3 * d * config["moe_intermediate_size"])
    macs["head"] = d * config["vocab_size"]
    return macs


def flops_per_sample(config, traffic):
    """Trained FLOPs per sequence of seq_len tokens: 2 per multiply-add,
    backward = 2 x forward, no recomputation, no optimizer; the routed
    experts at their expected share of a token's 8 assignments."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    return 3 * 2 * sum(macs.values()) * traffic["seq_len"]


def window_attention_flops_per_sample(config, traffic):
    """The share of flops_per_sample that is the windowed cores' own two
    products over the pairs inside the band: what
    `window_attention_roofline_pct` holds the `sliding_window_attention`
    scope's time against."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    return 3 * 2 * macs["window_cores"] * traffic["seq_len"]
