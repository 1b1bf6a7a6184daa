"""One chip's share of NVIDIA-Nemotron-3-Super-120B-A12B (`nemotron_h`)
as plain float32 jax.numpy, written from the layer equations in
config.json's `source` and the Mamba-2 paper (arXiv:2405.21060), under
`default_matmul_precision("highest")`.  The yardstick's own: nothing from
mxnet_tpu, parameters by name (the zoo's names less the block's prefix;
projection weights are (out, in), the held experts stacked (held, in,
out)).

Every layer l of the pattern held: h <- h + mixer_l(RMSNorm(h)), eps from
the config; then a final RMSNorm and logits = h W_head^T (untied, no
bias).  Written the slow, obvious way, in blocks so that 8192 positions
fit beside the system under test:

  M  the recurrence itself, one position at a time under `lax.scan`
     (not the chunked form the system uses);
  *  causal grouped-query attention over blocks of queries, each against
     all the keys, scores in full;
  E  the router over all experts published, then the held experts one by
     one, each over every token with its weight (0 where not chosen); the
     absent experts' part is absent here as in the system.

Departures from the published model are config.json's `assumed`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 512


def rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


def mamba(p, pre, u, cfg):
    """u (S, D) -> (S, D)."""
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, s = heads * hd, u.shape[0]
    proj = u @ p[pre + "in_proj_weight"].T
    z, xbc, dt = jnp.split(proj, [inner, proj.shape[1] - heads], axis=1)
    w = p[pre + "conv_weight"]                          # (channels, K)
    k = w.shape[1]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = silu(sum(padded[j:j + s] * w[:, j] for j in range(k))
               + p[pre + "conv_bias"])
    x, b, c = jnp.split(xbc, [inner, inner + groups * n], axis=1)
    x = x.reshape(s, heads, hd)
    b = jnp.repeat(b.reshape(s, groups, n), heads // groups, axis=1)
    c = jnp.repeat(c.reshape(s, groups, n), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + p[pre + "dt_bias"])       # (S, H)
    a = -jnp.exp(p[pre + "A_log"])                      # (H,)

    def step(state, t):                                 # state (H, P, N)
        x_t, dt_t, b_t, c_t = t
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = lax.scan(step, jnp.zeros((heads, hd, n), jnp.float32),
                    (x, dt, b, c))
    y = (y + p[pre + "D"][:, None] * x).reshape(s, inner) * silu(z)
    width = inner // groups                             # group RMSNorm
    y = rms_norm(y.reshape(s, groups, width),
                 p[pre + "gate_norm_weight"].reshape(groups, width),
                 cfg["layer_norm_epsilon"]).reshape(s, inner)
    return y @ p[pre + "out_proj_weight"].T


def attention(p, pre, u, cfg):
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    s = u.shape[0]
    q = (u @ p[pre + "q_proj_weight"].T).reshape(s, heads, d)
    k = (u @ p[pre + "k_proj_weight"].T).reshape(s, kv, d)
    v = (u @ p[pre + "v_proj_weight"].T).reshape(s, kv, d)
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of {block}")

    def rows(first):                        # queries first .. first+block
        qb = lax.dynamic_slice_in_dim(q, first, block)
        score = jnp.einsum("qhd,khd->hqk", qb, k) * d ** -0.5
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        prob = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", prob, v)

    out = lax.map(rows, jnp.arange(0, s, block)).reshape(s, heads * d)
    return out @ p[pre + "o_proj_weight"].T


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


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


def moe(p, pre, u, cfg, first_expert=0):
    weights = router(p, pre, u, cfg)
    latent = u @ p[pre + "latent_down_weight"].T
    w1, w2 = p[pre + "experts_w1"], p[pre + "experts_w2"]
    routed = jnp.zeros_like(latent)
    for e in range(w1.shape[0]):                        # the experts held
        routed += (weights[:, first_expert + e, None]
                   * (relu2(latent @ w1[e]) @ w2[e]))
    shared = (relu2(u @ p[pre + "shared_up_weight"].T)
              @ p[pre + "shared_down_weight"].T)
    return routed @ p[pre + "latent_up_weight"].T + shared


_MIXERS = {"M": ("mamba", mamba), "*": ("attn", attention),
           "E": ("moe", moe)}


def hidden(params, tokens, config):
    """tokens (S,) -> the last layer's output (S, D), before the head."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eps = config["layer_norm_epsilon"]
    h = p["embed_weight"][tokens]
    for i, kind in enumerate(config["pattern_held"]):
        name, mixer = _MIXERS[kind]
        pre = f"layer{i}_{name}_"
        h = h + mixer(p, pre, rms_norm(h, p[pre + "norm_weight"], eps),
                      config)
    return rms_norm(h, p["head_norm_weight"], eps), p["head_weight"]


def logits(params, tokens, config):
    """tokens (B, S) int -> (B, S, vocabulary held) float32."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            h, head = hidden(params, row, config)
            return h @ head.T
        return jnp.stack([one(row) for row in tokens])


def loss_of(scores, tokens):
    """Mean next-token cross-entropy over the S - 1 predicted positions
    of every sequence, from `logits`' scores."""
    logp = jax.nn.log_softmax(scores[:, :-1], -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
    return -picked.mean()


def loss(params, tokens, config):
    return loss_of(logits(params, tokens, config), tokens)
