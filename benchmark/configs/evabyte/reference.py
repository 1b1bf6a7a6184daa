"""EvaByte (`evabyte`, EvaByte/EvaByte) as plain float32 jax.numpy,
written from the layer equations of EVA (Zheng et al., "Efficient
Attention via Control Variates", arXiv:2302.04542) in the form the
release's config.json names (`attention_class: eva`, `window_size`,
`chunk_size`, `num_pred_heads`), under
`default_matmul_precision("highest")`.  The yardstick's own: nothing from
mxnet_tpu, parameters by name (the zoo's names less the block's prefix;
projection weights are (out, in); `phi` and `mu` are (heads, head size)).

Every layer l of those held, n(x; g) = x / sqrt(mean(x^2) + eps) * (1 + g):

    a = n(h; norm_weight);  q, k, v = a Wq^T, a Wk^T, a Wv^T  (H heads of D)
    q, k <- rotary(q), rotary(k)       rotate-half, every dimension
    chunk j = positions C j .. C j + C - 1, per head:
        alpha_m = softmax over the chunk of  D^-1/2 (k_m . phi_h)
        K~_j = sum_m alpha_m k_m + mu_h      V~_j = sum_m alpha_m v_m
    query i, window w = i // W, sees keys m with W w <= m <= i and
    summaries j < w W / C, under one softmax over both:
        o_i = softmax(D^-1/2 q_i . [k_m ; K~_j]) [v_m ; V~_j]
    h <- h + o Wo^T
    b = n(h; mlp_norm_weight);  h <- h + (silu(b G^T) * (b U^T)) Dn^T

then logits = n(h; head_norm_weight) W_head^T, (S, P, V): head p of
position t predicts byte t + 1 + p, and the loss is the mean
cross-entropy over every (t, p) with t + 1 + p < S.

Written the slow, obvious way, in blocks so that 32,768 positions fit
beside the system under test: attention a block of queries and one head
at a time, each block against ALL the keys and ALL the summaries under a
dense mask built from the two definitions above; the MLP a block of
rows at a time.  Departures from the published model are config.json's
`assumed`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_BLOCK = 512        # rows a block of queries, or of the MLP


def rms_norm(x, gain, eps):
    """The unit offset: the stored gain counts from zero."""
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gain)


def silu(x):
    return x * jax.nn.sigmoid(x)


def rotate(x, theta):
    """x (S, heads, D): every head turned by its position's angles,
    dimension i < D / 2 paired with i + D / 2.  The frequencies are
    constants of the model: worked out in double precision and rounded
    to float32 once."""
    d = x.shape[-1]
    freq = jnp.asarray(
        float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d),
        jnp.float32)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def chunk_summaries(k, v, phi, mu, chunk):
    """k, v (S, H, D); phi, mu (H, D) -> K~, V~ (S / C, H, D)."""
    s, h, d = k.shape
    kc, vc = k.reshape(s // chunk, chunk, h, d), v.reshape(s // chunk,
                                                           chunk, h, d)
    alpha = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", kc, phi) * d ** -0.5, axis=1)
    return (jnp.einsum("nch,nchd->nhd", alpha, kc) + mu,
            jnp.einsum("nch,nchd->nhd", alpha, vc))


def visible(first, rows, s, window, chunk):
    """(rows, S + S / C) bool for queries first .. first + rows - 1:
    columns 0 .. S - 1 are the keys, column S + j is summary j."""
    i = (first + jnp.arange(rows))[:, None]
    m = jnp.arange(s)[None]
    j = jnp.arange(s // chunk)[None]
    local = (m >= (i // window) * window) & (m <= i)
    remote = j < (i // window) * (window // chunk)
    return jnp.concatenate([local, remote], axis=1)


def eva_attention(q, k, v, phi, mu, window, chunk, keep_remote=True):
    """q, k, v (S, H, D) -> (S, H, D).  `keep_remote` False masks every
    summary out: the control that shows the limits see the remote term."""
    s, h, d = q.shape
    if s % window or window % chunk:
        raise ValueError(f"{s} positions, windows of {window}, chunks of "
                         f"{chunk}")
    k_sum, v_sum = chunk_summaries(k, v, phi, mu, chunk)
    keys = jnp.concatenate([k, k_sum], axis=0)          # (S + S/C, H, D)
    values = jnp.concatenate([v, v_sum], axis=0)
    block = min(_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of {block}")

    def rows(first, qb, kh, vh):            # one head, one block of queries
        score = (qb @ kh.T) * d ** -0.5
        seen = visible(first, block, s, window, chunk)
        if not keep_remote:
            seen = seen & (jnp.arange(seen.shape[1]) < s)[None]
        return jax.nn.softmax(jnp.where(seen, score, -jnp.inf), -1) @ vh

    def block_rows(first):
        qb = lax.dynamic_slice_in_dim(q, first, block)
        out = lax.map(lambda x: rows(first, *x),
                      (qb.transpose(1, 0, 2), keys.transpose(1, 0, 2),
                       values.transpose(1, 0, 2)))      # (H, block, D)
        return out.transpose(1, 0, 2)

    return lax.map(block_rows, jnp.arange(0, s, block)).reshape(s, h, d)


def gated_mlp(x, gate, up, down):
    """Weights (out, in); a block of rows at a time."""
    block = min(_BLOCK, x.shape[0])
    out = lax.map(lambda b: (silu(b @ gate.T) * (b @ up.T)) @ down.T,
                  x.reshape(-1, block, x.shape[1]))
    return out.reshape(x.shape)


def hidden(params, tokens, config, keep_remote=True):
    """tokens (S,) -> the last layer's output (S, hidden) after the final
    norm, and the head's weight."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eps, heads = config["rms_norm_eps"], config["num_attention_heads"]
    s = tokens.shape[0]
    h = p["embed_weight"][tokens]
    for i in range(config["num_hidden_layers"]):
        pre = f"layer{i}_"
        a = rms_norm(h, p[pre + "norm_weight"], eps)
        q, k, v = ((a @ p[pre + f"{n}_proj_weight"].T).reshape(s, heads, -1)
                   for n in "qkv")
        q, k = (rotate(x, config["rope_theta"]) for x in (q, k))
        out = eva_attention(q, k, v, p[pre + "phi"], p[pre + "mu"],
                            config["window_size"], config["chunk_size"],
                            keep_remote)
        h = h + out.reshape(s, -1) @ p[pre + "o_proj_weight"].T
        h = h + gated_mlp(rms_norm(h, p[pre + "mlp_norm_weight"], eps),
                          p[pre + "mlp_gate_weight"],
                          p[pre + "mlp_up_weight"],
                          p[pre + "mlp_down_weight"])
    return rms_norm(h, p["head_norm_weight"], eps), p["head_weight"]


def logits(params, tokens, config, keep_remote=True):
    """tokens (B, S) int -> (B, S, num_pred_heads, vocab_size) float32."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            h, head = hidden(params, row, config, keep_remote)
            return (h @ head.T).reshape(
                row.shape[0], config["num_pred_heads"], config["vocab_size"])
        return lax.map(one, tokens)


def loss_of(scores, tokens):
    """Mean cross-entropy over every (position t, head p) whose target,
    byte t + 1 + p, exists."""
    s, heads = scores.shape[1], scores.shape[2]
    logp = jax.nn.log_softmax(scores, -1)
    total, count = 0.0, 0
    for p in range(heads):
        n = s - 1 - p
        if n <= 0:
            continue
        picked = jnp.take_along_axis(
            logp[:, :n, p], tokens[:, 1 + p:, None], -1)
        total, count = total - picked.sum(), count + tokens.shape[0] * n
    return total / count


def loss(params, tokens, config):
    return loss_of(logits(params, tokens, config), tokens)
