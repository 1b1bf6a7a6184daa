"""phi4_mini_flash as the benchmark runs it: one chip's share of the
zoo's Phi-4-mini-flash stack (the depth and the vocabulary slice
config.json says are held: M W M W M F G X, every kind of layer, the
head tied to the embedding) under next-token training, the resident
batch, and the FLOPs and bytes the model needs.
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
              "num_key_value_heads", "sliding_window", "mb_per_layer",
              "layer_norm_eps", "tie_word_embeddings", "mlp_bias",
              "lm_head_bias", "d_state", "d_conv", "expand", "dt_rank")


def _step_block(config):
    """The training step as one block, as the other decoders': the loss
    is computed inside, so SPMDTrainer takes it with n_labels=0.  Returns
    (loss, logits, the memory m, and the inputs x, delta, B, C of the
    scan that made m); the trainer differentiates the first and drops
    the rest, `forward` reads them."""
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.phi4flash import Phi4FlashModel

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = Phi4FlashModel(
                    scan_probe=True,
                    **{k: config[k] for k in _ARGUMENTS})

        def hybrid_forward(self, F, tokens):
            import jax
            import jax.numpy as jnp

            logits, memory, *scan_inputs = self.model(tokens)
            lsm = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(
                lsm, tokens[:, 1:, None].astype(jnp.int32), -1)
            return (nll.mean(), logits, memory, *scan_inputs)

    return Step()


def _seeded_normal(sigma, seed, pool):
    """laguna_xs2's threaded float32 draws (its model.py: 16 streams a
    matrix, the same weights on any number of cores), as its siblings'
    model.py take them."""
    return lookup._module(lookup.BENCH_DIR, "configs", "laguna_xs2",
                          "model.py")._seeded_normal(sigma, seed, pool)


def build(seed, config, traffic, chips):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    # the taps, their bias, dt_bias and the lambda vectors draw from
    # numpy's global stream (the blocks' own initializers)
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


def counters():
    """What the program counted at trace time: the route every
    computation with several took, the form of the causal cores'
    backward, what the recomputed segments keep."""
    from mxnet_tpu.ops import pallas_attention, residuals, selective_scan

    return {"selective_scan": selective_scan.route_counts(),
            "attention": {k: v for k, v in
                          pallas_attention.route_counts().items() if v},
            "attention_backward": pallas_attention.backward_counts(),
            "kept_residuals": {k: v for k, v in
                               residuals.kept_residuals().items()
                               if v["values"]}}


# What `system_logits` leaves for `reference_logits`: the inputs the
# memory's scan had in the system (x, delta, B, C) and what it made of
# them, so that the `scan` entry compares the op alone.
_scan_seen = {}


def system_logits(trainer, sample, config):
    """Three entries: `lm`, the logits; `memory`, layer n / 2's scan
    output m, both many bfloat16 layers from the reference's; and
    `scan`, the same m held against the reference's scan of the very
    inputs the system's scan had, where only the op's own arithmetic
    differs (`reference_logits`)."""
    _loss, logits, memory, *scan_inputs = trainer.forward(*sample)
    print("[info] " + json.dumps({"routes": counters()}), flush=True)
    memory = np.asarray(memory.data, np.float32)
    _scan_seen.update(inputs=[a.data for a in scan_inputs], output=memory)
    return {"lm": np.asarray(logits.data, np.float32), "memory": memory,
            "scan": memory}


@functools.lru_cache(maxsize=1)
def _reference_program(reference, frozen_config):
    """One program for both uses of the reference (the sample's logits
    and memory, the batch's loss): compiled once a run, for one
    sequence."""
    import jax

    config = json.loads(frozen_config)

    def run(params, tokens):
        scores, memory = reference.forward(params, tokens, config)
        return scores, memory, reference.loss_of(scores, tokens)
    return jax.jit(run)


def _reference(reference, params, tokens, config):
    return _reference_program(reference, json.dumps(config, sort_keys=True))(
        params, np.asarray(tokens))


def reference_scan(reference, params, config, inputs):
    """reference.py's step-by-step scan of `inputs` (x, delta before its
    projection's bias, B, C as the system's scan had them, (1, S, ...))
    under layer n / 2's own A_log, D and dt_bias -> (1, S, D) float32."""
    import jax
    import jax.numpy as jnp

    pre = f"layer{config['num_hidden_layers'] // 2}_"
    state = jnp.dtype(config.get("_scan_state_dtype", "float32"))

    def run(a_log, d_skip, dt_bias, x, delta, b, c):
        x, delta, b, c = (v[0].astype(jnp.float32)
                          for v in (x, delta, b, c))
        return reference.selective_scan(
            x, jax.nn.softplus(delta + dt_bias), -jnp.exp(a_log), b, c,
            d_skip, state)[None]

    return np.asarray(jax.jit(run)(
        params[pre + "A_log"], params[pre + "D"], params[pre + "dt_bias"],
        *inputs), np.float32)


def reference_logits(reference, params, sample, config):
    """`scan` has a tolerance of its own (config.json's
    `scan_rel_l2_tol`: a scan whose state is not float32 has to fail it)
    and run.py holds every entry to `logits_rel_l2_tol`: so the
    reference's scan output is handed out with its distance from the
    system's stretched by the ratio of the two, and run.py's one
    comparison refuses exactly what the entry's own tolerance
    refuses."""
    scores, memory, _loss = _reference(reference, params, sample[0], config)
    tol = config["reference_check"]
    seen = _scan_seen["output"]
    want = reference_scan(reference, params, config, _scan_seen["inputs"])
    print("[info] " + json.dumps({"scan_rel_l2": float(
        np.linalg.norm(seen - want) / np.linalg.norm(want))}), flush=True)
    stretch = tol["logits_rel_l2_tol"] / tol["scan_rel_l2_tol"]
    return {"lm": np.asarray(scores, np.float32),
            "memory": np.asarray(memory, np.float32),
            "scan": seen + stretch * (want - seen)}


def reference_first_loss(reference, params, batch, config):
    """No dropout anywhere, so step 1's loss has a deterministic
    reference: the reference's loss on the resident batch, a sequence at
    a time through the sample's program."""
    losses = [float(_reference(reference, params, row[None], config)[2])
              for row in np.asarray(batch[0])]
    print("[info] " + json.dumps(
        {"reference_loss_by_sequence": losses}), flush=True)
    return float(np.mean(losses))


@functools.lru_cache(maxsize=1)
def _reference_module():
    return lookup._module(lookup.BENCH_DIR, "configs", "phi4_mini_flash",
                          "reference.py")


def layer_counts(config):
    """{kind: layers of it held}, by the published rule."""
    kinds = _reference_module().layer_kinds(config)
    return {kind: kinds.count(kind)
            for kind in ("mamba", "window", "full", "gmu", "cross")}


def _visible_pairs(seq_len, window=0):
    """(query, key) pairs with key <= query, under a window also query -
    key < window."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def forward_macs_per_token(config, seq_len):
    """Multiply-adds a token of the forward pass: {"mamba_projections",
    "attention_projections", "gmu_projections", "full_cores",
    "window_cores", "mlp", "head"}; embedding lookups, norms, the taps,
    activations, softmax, lambda and the sub-norm not counted; the scan
    is `scan_flops_per_token`.  The cores count their visible pairs
    exactly, at the published 64 for the scores and 128 for the values
    of every one of the 40 heads, whatever the kernel multiplies."""
    d, inner = config["hidden_size"], config["expand"] * config["hidden_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    head = d // heads
    rank = config["dt_rank"] or -(-d // 16)
    n = layer_counts(config)
    pair = heads * (head + 2 * head)
    return {
        # W_in (d -> 2 inner), W_x, W_dt, W_out
        "mamba_projections": n["mamba"] * (
            3 * d * inner + inner * (rank + 2 * config["d_state"])
            + rank * inner),
        # q, k, v, o; a cross layer has q and o alone
        "attention_projections": (n["window"] + n["full"]) * (
            2 * d * d + 2 * d * kv * head) + n["cross"] * 2 * d * d,
        "gmu_projections": n["gmu"] * 2 * d * inner,
        "full_cores": (n["full"] + n["cross"]) * pair
        * _visible_pairs(seq_len) / seq_len,
        "window_cores": n["window"] * pair
        * _visible_pairs(seq_len, config["sliding_window"]) / seq_len,
        "mlp": config["num_hidden_layers"] * 3 * d
        * config["intermediate_size"],
        "head": d * config["vocab_size"]}


def scan_flops_per_token(config):
    """Forward FLOPs a token of the selective scans: 6 a (channel,
    state) pair (dt A, the decay's product, the drive's two, the sum, C's
    product; the exponential not counted)."""
    return (layer_counts(config)["mamba"] * 6 * config["expand"]
            * config["hidden_size"] * config["d_state"])


def flops_per_sample(config, traffic):
    """Trained FLOPs per sequence of seq_len tokens: 2 per multiply-add,
    backward = 2 x forward, no recomputation, no optimizer.  run.py asks
    once, after the measured window: where a step has been traced by
    then, its counters go out on an `[info]` line of their own (the form
    of the cores' backward and what the segments keep exist only once
    the step is traced, after `system_logits`' line)."""
    import sys

    attention = sys.modules.get("mxnet_tpu.ops.pallas_attention")
    if attention is not None and any(attention.backward_counts().values()):
        print("[info] " + json.dumps({"step_counters": counters()}),
              flush=True)
    s = traffic["seq_len"]
    macs = forward_macs_per_token(config, s)
    return 3 * (2 * sum(macs.values()) + scan_flops_per_token(config)) * s


def diff_attention_flops_per_sample(config, traffic):
    """The share of flops_per_sample that is the full and the cross
    layer's two products over the causal pairs, at 64 + 128 a head: what
    `diff_attention_roofline_pct` holds the `differential_attention/full`
    scope's time against."""
    s = traffic["seq_len"]
    return 3 * 2 * forward_macs_per_token(config, s)["full_cores"] * s


def selective_scan_bytes_per_sample(config, traffic):
    """HBM bytes the `selective_scan` ops of one sequence cannot do
    without, in the configuration's 2-byte dtype (A_log, D and dt_bias
    float32), no recomputation: a forward reads x, delta, B, C and the
    three parameters and writes y; its backward reads them again and y's
    cotangent and writes the cotangents of x, delta, B, C and the
    parameters.  What `selective_scan_roofline_pct` holds the op's time
    against, whatever route runs."""
    s, n = traffic["seq_len"], config["d_state"]
    inner = config["expand"] * config["hidden_size"]
    streams = (2 + 1) + (2 + 1 + 2)         # x, delta, y; again, dy, dx, ddelta
    small = 2 * 3 * s * n                   # B and C: read, read, written
    weights = 3 * inner * (n + 2) * 4       # read, read, written: float32
    return layer_counts(config)["mamba"] * (
        (streams * s * inner + small) * 2 + weights)
