"""One chip's share of jdopensource/JoyAI-LLM-Flash (`joyai_llm_flash`)
as plain float32 jax.numpy, written from the layer equations of the
family the config's keys are those of: DeepSeek-V2 (arXiv:2405.04434,
section 2.1: multi-head latent attention) and DeepSeek-V3
(arXiv:2412.19437, sections 2.1.2 and 2.2: the sigmoid router with a
selection bias, multi-token prediction), under
`default_matmul_precision("highest")`.  The yardstick's own: nothing of
mxnet_tpu is imported, parameters go by name (the zoo's names less the
block's prefix; projection weights are (out, in), the held experts stacked:
experts_w1 (held, in, 2 x width) = [gate | up], experts_w2 (held, width,
in)).

Every layer l of those held, and the prediction module's own layer:

    h <- h + attention_l(RMSNorm(h; norm_weight))
    h <- h + mlp_l(RMSNorm(h; mlp_norm_weight))

then a final RMSNorm and logits = h W_head^T (untied, no bias).  Written
the slow, obvious way, in blocks so that 8192 positions fit beside the
system under test:

  attention  c_q = RMSNorm(u W_qa); q = c_q W_qb, H heads of [q_nope ;
             q_rope]; [c_kv ; k_r] = u W_kva; [k_nope ; v] =
             RMSNorm(c_kv) W_kvb, H heads of nope + v.  q_rope (every
             head) and k_r (ONE vector a position, shared by the heads)
             turn by the position's angles in pairs (2i, 2i + 1), as
             complex numbers.  score = (q_nope . k_nope + q_rope . k_r)
             / sqrt(nope + rope), causal softmax, times v; the heads'
             outputs through W_o.  Blocks of queries, a head at a time,
             each block against ALL the keys under a dense mask;
  dense      silu(b G) * (b U), then Dn;
  sparse     the router over all experts published, then the held
             experts one by one, each over every token with its weight
             (0 where not chosen), plus the shared expert; the absent
             experts' part is absent here as in the system;
  module     position i joins the main stack's normed output with the
             embedding of token i + 1, h'_i = [RMSNorm_e(Emb(t_{i+1})) ;
             RMSNorm_h(h_i)] W_eh, runs one sparse layer of its own,
             norms with a gain of its own and goes through the main
             head: logits for token i + 2.  Position S - 1 has no next
             token: it is joined with the sequence's first (a roll); no
             other position sees it, and the loss leaves it out.

Loss = mean CE of the main logits over the S - 1 next tokens +
`mtp_loss_weight` x mean CE of the module's over the S - 2 tokens after
next.  Departures from the published model are config.json's `assumed`.
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


def rotate(x, theta):
    """x (S, heads, r): dimensions (2i, 2i + 1) of every head are one
    complex number, multiplied by e^{j p theta^(-2i / r)} at position p.
    The frequencies are constants of the model: worked out in double
    precision and rounded to float32 once."""
    s, _, r = x.shape
    freq = jnp.asarray(
        float(theta) ** (-np.arange(0, r, 2, dtype=np.float64) / r),
        jnp.float32)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    re, im = x[..., 0::2], x[..., 1::2]
    return jnp.stack([re * cos - im * sin, im * cos + re * sin],
                     axis=-1).reshape(x.shape)


def attention(p, pre, u, cfg):
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, s = cfg["kv_lora_rank"], u.shape[0]
    c_q = rms_norm(u @ p[pre + "q_a_proj_weight"].T,
                   p[pre + "q_a_norm_weight"], eps)
    q = (c_q @ p[pre + "q_b_proj_weight"].T).reshape(s, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], cfg["rope_theta"])
    latent = u @ p[pre + "kv_a_proj_weight"].T
    k_rope = rotate(latent[:, None, rank:], cfg["rope_theta"])[:, 0]
    kv = (rms_norm(latent[:, :rank], p[pre + "kv_a_norm_weight"], eps)
          @ p[pre + "kv_b_proj_weight"].T).reshape(s, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of {block}")

    def rows(first, qn, qr, kn, vh):        # one head's block of queries
        score = (qn @ kn.T + qr @ k_rope.T) * (nope + rope) ** -0.5
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        return jax.nn.softmax(jnp.where(seen, score, -jnp.inf), -1) @ vh

    def block_rows(first):                  # queries first .. first+block
        qn, qr = (lax.dynamic_slice_in_dim(x, first, block)
                  for x in (q_nope, q_rope))
        out = lax.map(lambda h: rows(first, qn[:, h], qr[:, h],
                                     k_nope[:, h], v[:, h]),
                      jnp.arange(heads))    # (heads, block, v)
        return out.transpose(1, 0, 2).reshape(block, -1)

    out = lax.map(block_rows, jnp.arange(0, s, block)).reshape(s, -1)
    return out @ p[pre + "o_proj_weight"].T


def gated_mlp(x, gate, up, down):
    """Weights (out, in)."""
    return (silu(x @ gate.T) * (x @ up.T)) @ down.T


def router(p, pre, u, cfg):
    """-> (T, E) combine weights over ALL experts published: 0 where an
    expert is not among a token's chosen ones.  `n_group` = `topk_group`
    = 1: no group limits the choice."""
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


def shared_expert(p, pre, u):
    return gated_mlp(u, p[pre + "shared_gate_weight"],
                     p[pre + "shared_up_weight"],
                     p[pre + "shared_down_weight"])


def layer(p, pre, h, sparse, cfg, first_expert=0):
    eps = cfg["rms_norm_eps"]
    h = h + attention(p, pre, rms_norm(h, p[pre + "norm_weight"], eps), cfg)
    b = rms_norm(h, p[pre + "mlp_norm_weight"], eps)
    if not sparse:
        return h + gated_mlp(b, p[pre + "mlp_gate_weight"],
                             p[pre + "mlp_up_weight"],
                             p[pre + "mlp_down_weight"])
    return h + routed(p, pre, b, cfg, first_expert) \
        + shared_expert(p, pre, b)


def hidden(p, tokens, config, first_expert=0):
    """tokens (S,) -> the last layer's output (S, D) after the final
    norm."""
    h = p["embed_weight"][tokens]
    for i in range(config["num_hidden_layers"]):
        h = layer(p, f"layer{i}_", h, i >= config["first_k_dense_replace"],
                  config, first_expert)
    return rms_norm(h, p["norm_weight"], config["rms_norm_eps"])


def module_hidden(p, h, tokens, config, first_expert=0):
    """The prediction module over the main stack's normed output `h`
    (S, D): its own normed output (S, D), position i standing for token
    i + 2."""
    eps = config["rms_norm_eps"]
    following = p["embed_weight"][jnp.roll(tokens, -1)]
    joined = jnp.concatenate(
        [rms_norm(following, p["mtp_join_embed_norm_weight"], eps),
         rms_norm(h, p["mtp_join_hidden_norm_weight"], eps)], axis=-1)
    h = layer(p, "mtp_layer_", joined @ p["mtp_join_proj_weight"].T, True,
              config, first_expert)
    return rms_norm(h, p["mtp_norm_weight"], eps)


def logits(params, tokens, config, first_expert=0):
    """tokens (B, S) int -> (main logits, the module's logits), each
    (B, S, vocabulary held) float32."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        def one(row):
            h = hidden(p, row, config, first_expert)
            ahead = module_hidden(p, h, row, config, first_expert)
            return h @ p["head_weight"].T, ahead @ p["head_weight"].T
        return lax.map(one, tokens)


def cross_entropy(scores, targets):
    logp = jax.nn.log_softmax(scores, -1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()


def loss_terms(main, ahead, tokens):
    """(mean CE of the main logits over the S - 1 next tokens, mean CE
    of the module's over the S - 2 tokens after next)."""
    return (cross_entropy(main[:, :-1], tokens[:, 1:]),
            cross_entropy(ahead[:, :-2], tokens[:, 2:]))


def loss_of(main, ahead, tokens, config):
    first, second = loss_terms(main, ahead, tokens)
    return first + config["mtp_loss_weight"] * second


def loss(params, tokens, config, first_expert=0):
    return loss_of(*logits(params, tokens, config, first_expert), tokens,
                   config)
