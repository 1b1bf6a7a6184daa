"""One chip's share of LiquidAI/LFM2-8B-A1B (`lfm2_moe`) as plain
float32 jax.numpy, written from the layer equations of the family's
config keys and Liquid AI's LFM2 technical report, under
`default_matmul_precision("highest")`.  The yardstick's own: nothing of
mxnet_tpu is imported, parameters go by name (the zoo's names less the
block's prefix; projection weights are (out, in), the taps (channel,
tap), the held experts stacked: experts_w1 (held, in, 2 x width) = [gate
| up], experts_w2 (held, width, in)).

Every layer l of those held:

    h <- h + operator_l(RMSNorm(h; norm_weight))
    h <- h + mlp_l(RMSNorm(h; mlp_norm_weight))

then a final RMSNorm and logits = h W_embed^T (the head is the
embedding's array; no bias anywhere).  Written the slow, obvious way, in
blocks so that 8192 positions fit beside the system under test:

  conv       [B ; C ; x~] = u W_in; z = B * x~; c_t = sum_j w[:, j] *
             z_{t-(L-1)+j}, z zero before position 0 (tap L - 1 on the
             current position); y = (C * c) W_out.  The L shifted copies
             of z one after another;
  attention  q, k, v = u W_q, u W_k, u W_v; H query heads over Hkv
             key/value heads (query head h reads key/value head h //
             (H / Hkv)); every head of q and of k through an RMSNorm over
             its d dimensions (one gain for q, one for k), then turned by
             its position's angles, dimension i with i + d / 2,
             frequency theta^(-2i / d); score = q . k / sqrt(d), causal
             softmax, times v; the heads' outputs through W_o.  Blocks of
             queries, a head at a time, each block against ALL the keys
             under a dense mask;
  dense      silu(b G) * (b U), then Dn;
  sparse     the router over all experts published (sigmoid scores, the
             top-k of score + bias, the chosen scores over their sum),
             then the held experts one by one, each over every token
             with its weight (0 where not chosen); no shared expert; the
             absent experts' part is absent here as in the system.

Loss = mean cross-entropy of the logits over the S - 1 next tokens.
`operator_outputs` hands out one layer's operator(RMSNorm(h)) for the
comparison that the logits are too far from an operator to make.
Departures from the published model are config.json's `assumed`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_QUERY_BLOCK = 512


def rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


def short_conv(bcx, taps):
    """bcx (S, 3 D) = [B ; C ; x~], taps (D, L) -> C * conv(B * x~)."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    s, length = bcx.shape[0], taps.shape[1]
    z = b * x
    z = jnp.concatenate([jnp.zeros((length - 1, z.shape[1]), z.dtype), z], 0)
    return c * sum(taps[:, j] * z[j:j + s] for j in range(length))


def rotate(x, theta):
    """x (S, heads, d): dimension i of every head turns with dimension
    i + d / 2 by the angle p theta^(-2i / d) at position p.  The
    frequencies are constants of the model: worked out in double
    precision and rounded to float32 once."""
    s, _, d = x.shape
    freq = jnp.asarray(
        float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d),
        jnp.float32)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def head_norm(x, weight, eps):
    """x (S, heads, d): RMSNorm over every head's d dimensions."""
    return rms_norm(x, weight, eps)


def convolution(p, pre, u, cfg):
    return short_conv(u @ p[pre + "conv_in_proj_weight"].T,
                      p[pre + "conv_weight"]) \
        @ p[pre + "conv_out_proj_weight"].T


def attention(p, pre, u, cfg):
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta, s = cfg["norm_eps"], cfg["rope_theta"], u.shape[0]
    q = rotate(head_norm((u @ p[pre + "q_proj_weight"].T).reshape(
        s, heads, -1), p[pre + "q_norm_weight"], eps), theta)
    k = rotate(head_norm((u @ p[pre + "k_proj_weight"].T).reshape(
        s, kv_heads, -1), p[pre + "k_norm_weight"], eps), theta)
    v = (u @ p[pre + "v_proj_weight"].T).reshape(s, kv_heads, -1)
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of {block}")

    def rows(first, qh, kh, vh):            # one head's block of queries
        score = qh @ kh.T * q.shape[-1] ** -0.5
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        return jax.nn.softmax(jnp.where(seen, score, -jnp.inf), -1) @ vh

    def block_rows(first):                  # queries first .. first+block
        qb = lax.dynamic_slice_in_dim(q, first, block)
        out = lax.map(
            lambda h: rows(first, qb[:, h], k[:, h // (heads // kv_heads)],
                           v[:, h // (heads // kv_heads)]),
            jnp.arange(heads))              # (heads, block, d)
        return out.transpose(1, 0, 2).reshape(block, -1)

    out = lax.map(block_rows, jnp.arange(0, s, block)).reshape(s, -1)
    return out @ p[pre + "o_proj_weight"].T


def gated_mlp(x, gate, up, down):
    """Weights (out, in)."""
    return (silu(x @ gate.T) * (x @ up.T)) @ down.T


def router(p, pre, u, cfg):
    """-> (T, E) combine weights over ALL experts published: 0 where an
    expert is not among a token's chosen ones."""
    score = jax.nn.sigmoid(u @ p[pre + "router_weight"].T)
    _, chosen = lax.top_k(score + p[pre + "router_bias"],
                          cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(score, chosen, 1)
    weight = (cfg["routed_scaling_factor"] * picked
              / (picked.sum(-1, keepdims=True) + 1e-20))
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(score).at[rows, chosen].set(weight)


def routed(p, pre, u, cfg, first_expert=0):
    """The held experts' part: experts first_expert .. first_expert +
    held - 1, one after another, each over every token."""
    weights = router(p, pre, u, cfg)
    w1, w2 = p[pre + "experts_w1"], p[pre + "experts_w2"]
    held, width = w2.shape[0], w2.shape[1]
    mine = lax.dynamic_slice_in_dim(weights, first_expert, held, axis=1)

    def one(total, expert):
        w1_e, w2_e, weight = expert
        hidden = silu(u @ w1_e[:, :width]) * (u @ w1_e[:, width:])
        return total + weight[:, None] * (hidden @ w2_e), None

    return lax.scan(one, jnp.zeros_like(u), (w1, w2, mine.T))[0]


def layer(p, pre, h, kind, sparse, cfg, first_expert=0):
    eps = cfg["norm_eps"]
    operator = convolution if kind == "conv" else attention
    h = h + operator(p, pre, rms_norm(h, p[pre + "norm_weight"], eps), cfg)
    b = rms_norm(h, p[pre + "mlp_norm_weight"], eps)
    if not sparse:
        return h + gated_mlp(b, p[pre + "mlp_gate_weight"],
                             p[pre + "mlp_up_weight"],
                             p[pre + "mlp_down_weight"])
    return h + routed(p, pre, b, cfg, first_expert)


def hidden(p, tokens, config, first_expert=0):
    """tokens (S,) -> the last layer's output (S, D) after the final
    norm."""
    h = p["embed_weight"][tokens]
    for i, kind in enumerate(config["layer_types"]):
        h = layer(p, f"layer{i}_", h, kind, i >= config["num_dense_layers"],
                  config, first_expert)
    return rms_norm(h, p["head_norm_weight"], config["norm_eps"])


def operator_output(p, tokens, config, index, first_expert=0):
    """tokens (S,) -> what layer `index`'s operator adds to the stream,
    operator(RMSNorm(h)) (S, D): a fault inside an operator is whole
    here and a small part of the logits many layers on."""
    h = p["embed_weight"][tokens]
    for i, kind in enumerate(config["layer_types"][:index]):
        h = layer(p, f"layer{i}_", h, kind, i >= config["num_dense_layers"],
                  config, first_expert)
    pre = f"layer{index}_"
    operator = convolution if config["layer_types"][index] == "conv" \
        else attention
    return operator(p, pre, rms_norm(h, p[pre + "norm_weight"],
                                     config["norm_eps"]), config)


def operator_outputs(params, tokens, config, index, first_expert=0):
    """tokens (B, S) int -> (B, S, D) float32."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        return lax.map(lambda row: operator_output(p, row, config, index,
                                                   first_expert), tokens)


def logits(params, tokens, config, first_expert=0):
    """tokens (B, S) int -> (B, S, vocabulary held) float32: the head is
    the embedding's array."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        return lax.map(lambda row: hidden(p, row, config, first_expert)
                       @ p["embed_weight"].T, tokens)


def loss_of(scores, tokens):
    """Mean cross-entropy of scores (B, S, V) over the S - 1 next
    tokens."""
    logp = jax.nn.log_softmax(scores[:, :-1], -1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()


def loss(params, tokens, config, first_expert=0):
    return loss_of(logits(params, tokens, config, first_expert), tokens)
