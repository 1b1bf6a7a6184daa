"""Multi-head latent attention (MLA: DeepSeek-V2, arXiv:2405.04434,
section 2.1) in its training form, as two registered ops, so that a
traced program books the low-rank projection chain and the attention
core each under a scope of its own.

`latent_projection`: the normed input x (B, S, hidden) goes through two
low-rank chains, each with an RMSNorm of its own inside,

    c_q          = RMSNorm(x W_qa)           (q_rank)
    q            = c_q W_qb                  H heads of [nope ; rope]
    [c_kv ; k_r] = x W_kva                   (kv_rank + rope)
    [k_nope ; v] = RMSNorm(c_kv) W_kvb       H heads of nope + v

and returns q (B, S, H * (nope + rope)), k_nope (B, S, H * nope), the
one rotary key k_r (B, S, rope) all heads share (not rotated yet:
`rotary_embedding` turns it and the rope part of q) and v (B, S, H *
v_dim).  Its products are plain `jnp` inside the op: the innermost
registered name takes an instruction's time, and this chain is to read
apart from the `FullyConnected` of the MLPs and the head.

`latent_attention`: the causal core.  Every head's key is [its own
k_nope ; the shared rotated k_r], so queries and keys are nope + rope
wide and values v_dim wide.  Which call takes which route (counted by
`pallas_attention.route_counts()`; how a route is chosen is
`ops/kernel_route.py`'s business):

  * values in whole 128-lane blocks, queries and keys in whole or half
    ones, S a multiple of 128: `latent_splash`, the kernels
    `flash_causal` runs (`pallas_attention._attend_causal`: upstream's
    splash forward kernel and, where 1,024 divides S, the one backward
    kernel `mx_causal_attention_bwd`, which forms each score block once
    for dK, dV and dQ; dQ's float32 blocks lie in 256 lanes for the 192
    of a head, as XLA's own layout of them would) with one
    query head a key head, on keys concatenated from k_nope and k_r
    broadcast over the heads (the rotary key is written H times in HBM
    and its gradient summed over the heads by XLA; a kernel that reads
    it once is open: PERF.md section 7).  192 goes in as it is:
    zero-padded to 256 a layer's forward + backward read 63.6 ms against
    62.6 on the v5e (PERF.md, PR 39).  The forward rule names its output
    and logsumexp `latent_splash`, so a recomputed segment keeps them.
  * everything else: `latent_xla`, dense causal scores in float32.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import kernel_route
from . import pallas_attention as pa
from .nn import _rms_norm
from .registry import register_op


@register_op("latent_projection", num_outputs=4)
def _latent_projection(data, q_a_weight, q_norm_weight, q_b_weight,
                       kv_a_weight, kv_norm_weight, kv_b_weight,
                       num_heads=1, nope_dim=128, rope_dim=64, eps=1e-6):
    """data (B, S, hidden); weights (out, in): q_a (q_rank, hidden), q_b
    (H * (nope + rope), q_rank), kv_a (kv_rank + rope, hidden), kv_b (H *
    (nope + v_dim), kv_rank), the two norms' gains (q_rank,) and
    (kv_rank,).  -> (q, k_nope, k_r, v) as the module's docstring has
    them."""
    h, kv_rank = num_heads, kv_norm_weight.shape[0]
    if q_b_weight.shape[0] != h * (nope_dim + rope_dim) \
            or kv_a_weight.shape[0] != kv_rank + rope_dim \
            or kv_b_weight.shape[0] % h \
            or kv_b_weight.shape[0] // h <= nope_dim:
        raise ValueError(
            f"latent_projection: q_b {q_b_weight.shape}, kv_a "
            f"{kv_a_weight.shape}, kv_b {kv_b_weight.shape} for {h} heads "
            f"of {nope_dim} + {rope_dim} over a latent of {kv_rank}")
    q = _rms_norm(data @ q_a_weight.T, q_norm_weight,
                  eps=eps) @ q_b_weight.T
    # the matrices are cut, not what they give: no slice along the lanes
    # of an activation
    c_kv = _rms_norm(data @ kv_a_weight[:kv_rank].T, kv_norm_weight, eps=eps)
    kv_b = kv_b_weight.reshape(h, -1, kv_rank)
    return (q, c_kv @ kv_b[:, :nope_dim].reshape(-1, kv_rank).T,
            data @ kv_a_weight[kv_rank:].T,
            c_kv @ kv_b[:, nope_dim:].reshape(-1, kv_rank).T)


def _keys(k_nope, k_rope, heads):
    """(B, S, H * nope), (B, S, rope) -> (B, H, S, nope + rope): every
    head's own part beside the part all heads share."""
    k_nope = pa._split_to_heads(k_nope, heads)
    shared = jnp.broadcast_to(k_rope[:, None], k_nope.shape[:3]
                              + k_rope.shape[-1:])
    return jnp.concatenate([k_nope, shared], axis=-1)


_LATENT_SPLASH = kernel_route.Kernel("attention", "latent_splash",
                                     "latent_xla")


@register_op("latent_attention")
def _latent_attention(query, key_nope, key_rope, value, num_heads=1,
                      scale=None):
    """Causal self-attention over query (B, S, H * (nope + rope)),
    key_nope (B, S, H * nope), the rotated key_rope (B, S, rope) shared
    by all heads and value (B, S, H * v_dim) -> (B, S, H * v_dim); no
    dropout, no key mask.  `scale` None: (nope + rope) ** -0.5."""
    b, s, u = query.shape
    h = num_heads
    d, rope, d_v = u // h, key_rope.shape[-1], value.shape[-1] // h
    if key_nope.shape[-1] != h * (d - rope) or key_rope.shape != (b, s, rope):
        raise ValueError(
            f"latent_attention: keys {key_nope.shape} + {key_rope.shape} "
            f"for {h} query heads of {d}")
    if scale is None:
        scale = d ** -0.5
    qh, vh = pa._split_to_heads(query, h), pa._split_to_heads(value, h)
    kh = _keys(key_nope, key_rope, h)
    if kernel_route.choose(
            _LATENT_SPLASH, pa._causal_flash_shape(h, h, s, s, d, d_v), b,
            kept=pa._splash_kept(b, h, s, d_v, query.dtype)):
        oh = pa._attend_causal(qh, kh, vh, float(scale), None,
                               kernel_route.interpret(),
                               name="latent_splash")
    else:
        oh = pa._causal_xla(qh, kh, vh, float(scale))
    return oh.transpose(0, 2, 1, 3).reshape(b, s, h * d_v)
