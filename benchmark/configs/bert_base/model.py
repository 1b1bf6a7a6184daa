"""bert_base as the benchmark runs it: the zoo's BERT-base under the
published pretraining step (masked-LM over the gathered masked positions
plus next-sentence), the batch, and the FLOPs the model needs.
"""
from __future__ import annotations

import numpy as np

SAMPLES_UNIT = "sequences"
_WIDTHS = ("num_layers", "units", "hidden_size", "num_heads", "vocab_size",
           "token_type_vocab_size", "max_length", "dropout")


def _step_block(config):
    """The training step as one block (the shape of bench_all.py's Step):
    the loss is computed inside, so SPMDTrainer takes it with n_labels=0.
    Unlike bench_all.py it gathers the masked positions BEFORE the
    vocabulary projection, as run_pretraining.py does: P x vocab logits a
    sequence, not S x vocab.  Returns (loss, mlm scores, nsp scores); the
    trainer differentiates the first and `forward` reads the rest."""
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.bert import get_bert_model

    class Step(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.bert = get_bert_model(
                    "bert_12_768_12", **{k: config[k] for k in _WIDTHS})

        def hybrid_forward(self, F, tokens, segments, valid_length,
                           positions, mlm_labels, mlm_weight, nsp_labels):
            import jax
            import jax.numpy as jnp

            seq_out, pooled = self.bert(tokens, segments, valid_length)
            picked = jnp.take_along_axis(
                seq_out, positions[..., None].astype(jnp.int32), axis=1)
            mlm_scores = self.bert.decode_mlm(picked)
            nsp_scores = self.bert.classify_nsp(pooled)
            lsm = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(
                lsm, mlm_labels[..., None].astype(jnp.int32), -1)[..., 0]
            mlm_loss = ((nll * mlm_weight).sum()
                        / jnp.maximum(mlm_weight.sum(), 1.0))
            nsp_lsm = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), -1)
            nsp_loss = -jnp.take_along_axis(
                nsp_lsm, nsp_labels[:, None].astype(jnp.int32), -1)[:, 0]
            return mlm_loss + nsp_loss.mean(), mlm_scores, nsp_scores

    return Step()


def build(seed, config, traffic, chips):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    np.random.seed(seed)
    mx.random.seed(seed)
    step = _step_block(config)
    step.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
    with mx.autograd.pause():       # resolve deferred shapes, on cpu()
        tok = mx.nd.array(np.zeros((1, 8), np.int32), ctx=mx.cpu())
        seq_out, pooled = step.bert(
            tok, tok, mx.nd.array(np.full((1,), 8, np.float32), ctx=mx.cpu()))
        step.bert.decode_mlm(seq_out)
        step.bert.classify_nsp(pooled)
    step.cast(config["dtype"])
    opt = dict(config["optimizer"])
    return parallel.SPMDTrainer(
        step, lambda loss: loss, opt.pop("name"), opt,
        mesh=parallel.make_mesh(dp=chips), n_labels=0)


def _draw(rng, n, config, traffic):
    """`n` pretraining examples as create_pretraining_data.py shapes them:
    with probability short_seq_prob a sequence is short, 15% of its valid
    positions (at most max_predictions) are masked, the rest is padding."""
    s, p, vocab = (traffic["seq_len"], traffic["max_predictions"],
                   config["vocab_size"])
    short = rng.rand(n) < traffic["short_seq_prob"]
    valid = np.where(short, rng.randint(2, s + 1, n), s)
    cols = np.arange(s)[None, :]
    tokens = np.where(cols < valid[:, None],
                      rng.randint(5, vocab, (n, s)), 0).astype(np.int32)
    split = (valid * rng.uniform(0.3, 0.7, n)).astype(np.int64)
    segments = ((cols >= split[:, None])
                & (cols < valid[:, None])).astype(np.int32)
    n_pred = np.clip(np.round(valid * traffic["masked_lm_prob"]), 1, p)
    positions = np.zeros((n, p), np.int32)
    weight = np.zeros((n, p), np.float32)
    for i in range(n):
        k = int(n_pred[i])
        positions[i, :k] = np.sort(rng.choice(valid[i], k, replace=False))
        weight[i, :k] = 1.0
    mlm_labels = rng.randint(5, vocab, (n, p)).astype(np.int32)
    nsp_labels = rng.randint(0, 2, n).astype(np.int32)
    return (tokens, segments, valid.astype(np.float32), positions,
            mlm_labels, weight, nsp_labels)


def batch(seed, config, traffic, put):
    """The resident batch, in the order Step.hybrid_forward takes it."""
    rng = np.random.RandomState(seed)
    return tuple(put(a) for a in _draw(rng, traffic["batch"], config,
                                       traffic))


def sample(seed, config, traffic):
    rng = np.random.RandomState(seed + 1)
    return _draw(rng, config["reference_check"]["sample"], config, traffic)


def system_logits(trainer, sample, config):
    _loss, mlm, nsp = trainer.forward(*sample)
    return {"mlm": np.asarray(mlm.data, np.float32),
            "nsp": np.asarray(nsp.data, np.float32)}


def reference_logits(reference, params, sample, config):
    import jax

    mlm, nsp = jax.jit(lambda p, *inputs: reference.logits(
        p, *inputs, config))(params, *sample[:4])
    return {"mlm": np.asarray(mlm, np.float32),
            "nsp": np.asarray(nsp, np.float32)}


def reference_first_loss(reference, params, batch, config):
    """Dropout 0.1 is kept, so step 1's loss has no deterministic
    reference; the logits check above carries the arithmetic."""
    return None


def flops_per_sample(config, traffic):
    """Trained FLOPs per sequence of seq_len positions (padding counted:
    the model computes it): 2 per multiply-add, backward = 2 x forward,
    no recomputation, no optimizer; embedding lookups, LayerNorm, GELU,
    softmax and dropout not counted (under 1%)."""
    return 3 * 2 * forward_macs(config, traffic["seq_len"],
                                traffic["max_predictions"])


def forward_macs(config, seq_len, predictions):
    h, ffn, vocab = config["units"], config["hidden_size"], \
        config["vocab_size"]
    layer = (4 * seq_len * h * h            # query, key, value, output
             + 2 * seq_len * seq_len * h    # scores and weighted values
             + 2 * seq_len * h * ffn)       # feed-forward
    head = (predictions * h * h             # masked-LM transform
            + predictions * h * vocab       # tied decoder
            + h * h + 2 * h)                # pooler, next-sentence
    return config["num_layers"] * layer + head
