"""One chip's share of poolside/Laguna-XS.2 (`laguna`) as plain float32
jax.numpy, written from the layer equations in config.json's `source`
and, for the full layers' rotary scaling, the YaRN paper
(arXiv:2309.00071), under `default_matmul_precision("highest")`.  The
yardstick's own: nothing from mxnet_tpu, parameters by name (the zoo's
names less the block's prefix; projection weights are (out, in), the
held experts stacked: experts_w1 (held, in, 2 x width) = [gate | up],
experts_w2 (held, width, in)).

Every layer l of those held:

    h <- h + attention_l(RMSNorm(h; norm_weight))
    h <- h + mlp_l(RMSNorm(h; mlp_norm_weight))

then a final RMSNorm and logits = h W_head^T (untied, no bias).  Written
the slow, obvious way, in blocks so that 8192 positions fit beside the
system under test:

  attention  rotary on q and k (default on the window layers, YaRN on
             the first half of a head's dimensions on the full ones),
             grouped-query scores over blocks of queries and one
             key/value head's query heads at a time, each block against
             ALL the keys under a dense mask (causal, or the causal band
             of `sliding_window`), a sigmoid gate of one scalar a head;
  dense      silu(b G) * (b U), then Dn;
  sparse     the router over all experts published, then the held
             experts one by one, each over every token with its weight
             (0 where not chosen), plus the shared expert; the absent
             experts' part is absent here as in the system.

Departures from the published model are config.json's `assumed`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_QUERY_BLOCK = 512


def rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


def inv_freq(rope, head_dim):
    """One entry of `rope_parameters` -> (r / 2 frequencies, the factor
    on cos and sin).  The frequencies are constants of the model: worked
    out in double precision and rounded to float32 once (in float32 the
    power alone is off by 1e-6, 0.007 rad at position 8192)."""
    r = int(head_dim * rope["partial_rotary_factor"])
    base = float(rope["rope_theta"])
    f = base ** (np.arange(0, r, 2, dtype=np.float64) / r)
    if rope["rope_type"] == "default":
        return jnp.asarray(1.0 / f, jnp.float32), 1.0

    def dim(turns):
        return (r * math.log(rope["original_max_position_embeddings"]
                             / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return (jnp.asarray(ramp / (rope["factor"] * f) + (1.0 - ramp) / f,
                        jnp.float32), rope["attention_factor"])


def rotate(x, rope):
    """x (S, heads, D): the first r dimensions of every head turned by
    the position's angles, dimension i < r / 2 paired with i + r / 2."""
    freq, factor = inv_freq(rope, x.shape[-1])
    half = freq.shape[0]
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def attention(p, pre, u, heads, kind, cfg):
    kv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    s, group = u.shape[0], heads // cfg["num_key_value_heads"]
    rope = cfg["rope_parameters"][kind]
    q = rotate((u @ p[pre + "q_proj_weight"].T).reshape(s, heads, d), rope)
    k = rotate((u @ p[pre + "k_proj_weight"].T).reshape(s, kv, d), rope)
    v = (u @ p[pre + "v_proj_weight"].T).reshape(s, kv, d)
    reach = cfg["sliding_window"] if kind == "sliding_attention" else s
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of {block}")

    def rows(first, qb, kh, vh):            # one key/value head's queries
        score = jnp.einsum("qgd,kd->gqk", qb, kh) * d ** -0.5
        ahead = (first + jnp.arange(block))[:, None] - jnp.arange(s)[None]
        seen = (ahead >= 0) & (ahead < reach)
        prob = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), -1)
        return jnp.einsum("gqk,kd->qgd", prob, vh)

    def block_rows(first):                  # queries first .. first+block
        qb = lax.dynamic_slice_in_dim(q, first, block).reshape(
            block, kv, group, d)
        out = lax.map(lambda h: rows(first, qb[:, h], k[:, h], v[:, h]),
                      jnp.arange(kv))       # (kv, block, group, d)
        return out.transpose(1, 0, 2, 3).reshape(block, heads, d)

    out = lax.map(block_rows, jnp.arange(0, s, block)).reshape(s, heads, d)
    gate = jax.nn.sigmoid(u @ p[pre + "gate_proj_weight"].T)     # (S, H)
    return (out * gate[:, :, None]).reshape(s, heads * d) \
        @ p[pre + "o_proj_weight"].T


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
    weight = (cfg["moe_routed_scaling_factor"] * picked
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


def sparse_mlp(p, pre, u, cfg, first_expert=0):
    return routed(p, pre, u, cfg, first_expert) + gated_mlp(
        u, p[pre + "shared_gate_weight"], p[pre + "shared_up_weight"],
        p[pre + "shared_down_weight"])


def layers_held(config):
    """[(kind, mlp kind, query heads)] of the layers held: the first
    `num_hidden_layers` entries of the published per-layer lists."""
    n = config["num_hidden_layers"]
    return list(zip(config["layer_types"][:n],
                    config["mlp_layer_types"][:n],
                    config["num_attention_heads_per_layer"][:n]))


def hidden(params, tokens, config, first_expert=0):
    """tokens (S,) -> the last layer's output (S, D) after the final
    norm, and the head's weight."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eps = config["rms_norm_eps"]
    h = p["embed_weight"][tokens]
    for i, (kind, mlp, heads) in enumerate(layers_held(config)):
        pre = f"layer{i}_"
        h = h + attention(p, pre, rms_norm(h, p[pre + "norm_weight"], eps),
                          heads, kind, config)
        b = rms_norm(h, p[pre + "mlp_norm_weight"], eps)
        if mlp == "dense":
            h = h + gated_mlp(b, p[pre + "mlp_gate_weight"],
                              p[pre + "mlp_up_weight"],
                              p[pre + "mlp_down_weight"])
        else:
            h = h + sparse_mlp(p, pre, b, config, first_expert)
    return rms_norm(h, p["head_norm_weight"], eps), p["head_weight"]


def logits(params, tokens, config, first_expert=0):
    """tokens (B, S) int -> (B, S, vocabulary held) float32."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            h, head = hidden(params, row, config, first_expert)
            return h @ head.T
        return lax.map(one, tokens)


def loss_of(scores, tokens):
    """Mean next-token cross-entropy over the S - 1 predicted positions
    of every sequence, from `logits`' scores."""
    logp = jax.nn.log_softmax(scores[:, :-1], -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
    return -picked.mean()


def loss(params, tokens, config, first_expert=0):
    return loss_of(logits(params, tokens, config, first_expert), tokens)
