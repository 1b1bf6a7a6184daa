"""One pipeline stage's layers of ByteDance/Ouro-2.6B (`ouro`), looped
on their own output, as plain float32 jax.numpy, written from the
equations of "Scaling Latent Reasoning via Looped Language Models"
(arXiv:2510.25741) and of the modeling file beside the published
config.json, under `default_matmul_precision("highest")`.  The
yardstick's own: nothing of mxnet_tpu is imported, parameters go by name
(the zoo's names less the block's prefix; projection weights are (out,
in)).

A layer l of the N held, on h (no bias anywhere, every sub-layer normed
on both sides):

    h <- h + RMSNorm(attention_l(RMSNorm(h; norm_weight)); post_norm_weight)
    h <- h + RMSNorm(mlp_l(RMSNorm(h; mlp_norm_weight)); mlp_post_norm_weight)

One pass is layers 0 .. N - 1 in order.  For t = 1 .. T
(`total_ut_steps`), the SAME layers every pass:

    z_t     = RMSNorm(pass(z_{t-1}); final_norm_weight),  z_0 = E[tokens]
    logits_t = z_t W_head^T                    (exit_head_weight, untied)
    lambda_t = sigmoid(z_t . w_gate + b_gate)  (exit_gate_weight, _bias)

Exit distribution a token: S_0 = 1, S_t = prod_{j<=t} (1 - lambda_j),
p_t = lambda_t S_{t-1} for t < T, p_T = S_{T-1}.  Objective, mean over
the S - 1 predicted positions of every sequence:

    sum_t p_t CE(logits_t, next token) - beta H(p),  H = -sum_t p_t log p_t

Written the slow, obvious way, the loop a Python `for` over t, and in
blocks so that 8192 positions fit beside the system under test:

  attention  q, k, v = u W_q, u W_k, u W_v; H heads of d dimensions
             (query head h reads key/value head h // (H / Hkv)); q and k
             turned by their position's angles, dimension i with
             i + d / 2, frequency theta^(-2i / d); score = q . k /
             sqrt(d), causal softmax, times v; the heads' outputs
             through W_o.  Blocks of queries, a head at a time, each
             block against ALL the keys under a dense mask;
  mlp        silu(m G) * (m U), then Dn;
  an exit's cross-entropy   blocks of positions, each against the whole
             vocabulary.

Departures from the published model are config.json's `assumed`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_QUERY_BLOCK = 512
_LOSS_BLOCK = 1024


def rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


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


def attention(p, pre, u, cfg):
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    theta, s = cfg["rope_theta"], u.shape[0]
    q = rotate((u @ p[pre + "q_proj_weight"].T).reshape(s, heads, -1), theta)
    k = rotate((u @ p[pre + "k_proj_weight"].T).reshape(s, kv_heads, -1),
               theta)
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


def layer(p, pre, h, cfg):
    eps = cfg["rms_norm_eps"]
    a = attention(p, pre, rms_norm(h, p[pre + "norm_weight"], eps), cfg)
    h = h + rms_norm(a, p[pre + "post_norm_weight"], eps)
    m = gated_mlp(rms_norm(h, p[pre + "mlp_norm_weight"], eps),
                  p[pre + "mlp_gate_weight"], p[pre + "mlp_up_weight"],
                  p[pre + "mlp_down_weight"])
    return h + rms_norm(m, p[pre + "mlp_post_norm_weight"], eps)


def states(p, tokens, config):
    """tokens (S,) -> (z_1 .. z_T (T, S, D), gate logits (T, S)): the
    normed state after every pass and its exit gate's logit."""
    z, every = p["embed_weight"][tokens], []
    for _t in range(config["total_ut_steps"]):
        for i in range(config["num_hidden_layers"]):
            z = layer(p, f"layer{i}_", z, config)
        z = rms_norm(z, p["final_norm_weight"], config["rms_norm_eps"])
        every.append(z)
    every = jnp.stack(every)
    return every, every @ p["exit_gate_weight"] + p["exit_gate_bias"]


def exit_pdf(gate_logits):
    """gate logits (T, ...) -> p (..., T): p_t = lambda_t S_{t-1} for
    t < T, p_T = S_{T-1}."""
    leave = jax.nn.sigmoid(gate_logits)
    stay = jnp.cumprod(1.0 - leave, axis=0)                 # S_1 .. S_T
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], 0)
    p = jnp.concatenate([leave[:-1] * before[:-1], before[-1:]], 0)
    return jnp.moveaxis(p, 0, -1)


def cross_entropy(p, z, tokens):
    """z (S, D) of one exit -> the cross-entropy (S - 1,) of positions
    0 .. S - 2 against the next token, over the whole vocabulary, a block
    of positions at a time."""
    n = z.shape[0] - 1
    block = min(_LOSS_BLOCK, n)
    padded = -(-n // block) * block
    zs = jnp.pad(z[:-1], ((0, padded - n), (0, 0)))
    targets = jnp.pad(tokens[1:], (0, padded - n))

    def rows(args):
        zb, tb = args
        logp = jax.nn.log_softmax(zb @ p["exit_head_weight"].T, -1)
        return -jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]

    return lax.map(rows, (zs.reshape(-1, block, z.shape[1]),
                          targets.reshape(-1, block))).reshape(-1)[:n]


def objective(nll, pdf, beta):
    """nll (..., T) a position and exit, pdf (..., T) -> the expected
    loss less beta times the distribution's entropy, mean over the
    positions."""
    plogp = jnp.where(pdf > 0, pdf * jnp.log(jnp.where(pdf > 0, pdf, 1.0)),
                      0.0)
    return jnp.mean(jnp.sum(pdf * nll, -1) + beta * jnp.sum(plogp, -1))


def _float32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}


def exits(params, tokens, config):
    """tokens (B, S) int -> (z (B, T, S, D), gate logits (B, T, S)), a
    sequence at a time."""
    p = _float32(params)
    with jax.default_matmul_precision("highest"):
        return lax.map(lambda row: states(p, row, config), tokens)


def exit_logits(params, z):
    """One exit's states z (B, S, D) -> its logits (B, S, vocabulary)
    float32: 1.6 GB a sequence of 8192, so the caller takes an exit at a
    time."""
    with jax.default_matmul_precision("highest"):
        return z @ jnp.asarray(params["exit_head_weight"], jnp.float32).T


def loss_of(params, z, gate_logits, tokens, config):
    """What `exits` gave -> the training objective on `tokens`."""
    p = _float32(params)
    with jax.default_matmul_precision("highest"):
        nll = lax.map(lambda a: jnp.stack(
            [cross_entropy(p, zt, a[1]) for zt in a[0]], -1), (z, tokens))
        pdf = exit_pdf(jnp.moveaxis(gate_logits, 1, 0))[:, :-1]
        return objective(nll, pdf, config["exit_entropy_beta"])


def loss(params, tokens, config):
    z, gate_logits = exits(params, tokens, config)
    return loss_of(params, z, gate_logits, tokens, config)
