"""BERT-base (Devlin et al., arXiv:1810.04805) as plain float32
jax.numpy, written from the paper: token + segment + learned position
embeddings, LayerNorm; 12 post-LayerNorm encoder layers (multi-head
self-attention over the valid keys, GELU feed-forward); a tanh pooler on
[CLS]; the masked-LM head (dense + GELU + LayerNorm, decoder tied to the
word embeddings, plus a bias) at the masked positions only; the
next-sentence classifier.  Inference mode: no dropout.  The yardstick's
own: nothing from mxnet_tpu, parameters by name (the zoo's names with the
block's prefix removed; dense weights are (out, in)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def logits(params, tokens, segments, valid_length, positions, config):
    """-> (mlm (B, P, vocab), nsp (B, 2)) float32."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eps = config["layer_norm_epsilon"]
    heads = config["num_heads"]

    def dense(x, name):
        return x @ p[name + "_weight"].T + p[name + "_bias"]

    def ln(x, name):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return ((x - mean) / jnp.sqrt(var + eps) * p[name + "_gamma"]
                + p[name + "_beta"])

    def gelu(x):
        return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))

    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        x = (p["word_embed_weight"][tokens]
             + p["token_type_embed_weight"][segments]
             + p["position_embed_weight"][:s][None])
        x = ln(x, "embed_ln")
        valid = jnp.arange(s)[None, :] < jnp.asarray(
            valid_length)[:, None]                         # (B, S) keys
        d = x.shape[-1] // heads

        def split(t):                                      # (B, H, S, D)
            return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

        for i in range(config["num_layers"]):
            pre = f"encoder_layer{i}_"
            q, k, v = (split(dense(x, f"{pre}attn_{n}"))
                       for n in ("query", "key", "value"))
            score = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(1.0 * d)
            score = jnp.where(valid[:, None, None, :], score, -jnp.inf)
            ctx = jnp.einsum("bhqk,bhkd->bhqd",
                             jax.nn.softmax(score, -1), v)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
            x = ln(x + dense(ctx, f"{pre}attn_proj"), f"{pre}ln1")
            h = dense(gelu(dense(x, f"{pre}ffn_ffn1")), f"{pre}ffn_ffn2")
            x = ln(x + h, f"{pre}ln2")

        pooled = jnp.tanh(dense(x[:, 0], "pooler"))
        nsp = dense(pooled, "nsp_classifier")
        picked = jnp.take_along_axis(
            x, jnp.asarray(positions, jnp.int32)[..., None], axis=1)
        h = ln(gelu(dense(picked, "mlm_transform")), "mlm_ln")
        mlm = h @ p["word_embed_weight"].T + p["mlm_bias"]
        return mlm, nsp
