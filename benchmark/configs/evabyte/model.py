"""evabyte as the benchmark runs it: one pipeline stage's layers of the
zoo's EvaByte stack (every width as published, with the embedding and
the 8-way multibyte head) under next-8-bytes training, the resident
batch, and the FLOPs the model needs.
"""
from __future__ import annotations

import functools
import json
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import lookup

SAMPLES_UNIT = "sequences"
_ARGUMENTS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads", "window_size",
              "chunk_size", "num_pred_heads", "rope_theta", "rms_norm_eps")


def _step_block(config):
    """The training step as one block, as the other decoders': the loss
    is computed inside, so SPMDTrainer takes it with n_labels=0.  Returns
    (loss, logits); the trainer differentiates the first and `forward`
    reads the second."""
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.evabyte import (EvaByteModel,
                                                   multibyte_loss)

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.model = EvaByteModel(**{k: config[k]
                                             for k in _ARGUMENTS})

        def hybrid_forward(self, F, tokens):
            logits = self.model(tokens)
            return multibyte_loss(logits, tokens), logits

    return Step()


def _seeded_normal(sigma, seed, pool):
    """laguna_xs2's threaded float32 draws (its model.py: 16 streams a
    matrix, the same weights on any number of cores), so that a billion
    normals take seconds of set-up and not a minute; one copy of that
    code until the harness owns it (PERF.md section 7)."""
    return lookup._module(lookup.BENCH_DIR, "configs", "laguna_xs2",
                          "model.py")._seeded_normal(sigma, seed, pool)


def build(seed, config, traffic, chips):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    np.random.seed(seed)        # phi and mu: the zoo's clipped normals
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
    """`n` sequences of bytes uniform over the vocabulary: no padding,
    no document boundary."""
    return (rng.randint(0, config["vocab_size"],
                        (n, traffic["seq_len"])).astype(np.int32),)


def batch(seed, config, traffic, put):
    """The resident batch, in the order Step.hybrid_forward takes it; the
    targets are the next 8 bytes, taken inside the step."""
    rng = np.random.RandomState(seed)
    return tuple(put(a) for a in _draw(rng, traffic["batch"], config,
                                       traffic))


def sample(seed, config, traffic):
    rng = np.random.RandomState(seed + 1)
    return _draw(rng, config["reference_check"]["sample"], config, traffic)


def visible_pairs(config, seq_len):
    """(windows, local, remote): the (query, key) pairs a head's
    `eva_attention` sums over, exactly: the causal triangle of every
    window, and for the queries of window w the w x window / chunk
    summaries of the windows before it."""
    window = min(config["window_size"], seq_len)
    windows = seq_len // window
    return (windows, windows * window * (window + 1) // 2,
            window * (window // config["chunk_size"])
            * windows * (windows - 1) // 2)


def system_logits(trainer, sample, config):
    from mxnet_tpu.ops import pallas_attention

    _loss, logits = trainer.forward(*sample)
    s = sample[0].shape[1]
    windows, local, remote = visible_pairs(config, s)
    print("[info] " + json.dumps({
        "routes": {"attention": pallas_attention.route_counts()},
        "eva": {"windows": windows, "chunks": s // config["chunk_size"],
                "visible_pairs_a_head": {"local": local, "remote": remote}}
    }), flush=True)
    return {"lm": np.asarray(logits.data, np.float32)}


@functools.lru_cache(maxsize=1)
def _reference_program(reference, frozen_config):
    """One program for both uses of the reference (the sample's logits,
    the batch's loss): it is compiled once a run, for one sequence."""
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
    a time through the sample's program (every sequence has the same
    number of targets, so the batch's mean is the mean of theirs)."""
    return float(np.mean([
        float(_reference(reference, params, row[None], config)[1])
        for row in np.asarray(batch[0])]))


def forward_macs_per_token(config, seq_len):
    """Multiply-adds a token of the forward pass: {"projections", "mlp",
    "eva_cores", "head"}; embedding lookups, norms, rotary, the chunk
    summaries (3 multiply-adds a key element), activations and softmax
    not counted.  The cores count exactly the pairs of
    `visible_pairs`."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    _windows, local, remote = visible_pairs(config, seq_len)
    return {
        "projections": layers * 4 * d * d,
        "mlp": layers * 3 * d * config["intermediate_size"],
        # scores and weighted values, every head, hidden / heads wide
        "eva_cores": layers * 2 * d * (local + remote) / seq_len,
        "head": d * config["num_pred_heads"] * config["vocab_size"]}


def flops_per_sample(config, traffic):
    """Trained FLOPs per sequence of seq_len bytes: 2 per multiply-add,
    backward = 2 x forward, no recomputation, no optimizer."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    return 3 * 2 * sum(macs.values()) * traffic["seq_len"]


def eva_attention_flops_per_sample(config, traffic):
    """The share of flops_per_sample that is `eva_attention`'s own two
    products over the visible pairs: what `eva_attention_roofline_pct`
    holds the op scope's time against."""
    macs = forward_macs_per_token(config, traffic["seq_len"])
    return 3 * 2 * macs["eva_cores"] * traffic["seq_len"]
