"""Differential attention (arXiv:2410.05258) as one registered op, the
causal cores of `gluon/model_zoo/phi4flash.py`: the window layers, the
one full layer and the cross-decoder's layers, which read that full
layer's keys and values (arXiv:2507.06607).

Heads come in pairs, (2j, 2j + 1) of H query heads and of Hkv key/value
heads of d dimensions; query pair j reads key/value pair g = j // (H /
Hkv).  With (q1, q2) a query pair, (k1, k2) a key pair and V = [v1 ; v2]
the pair's values side by side (2 d wide):

    a1  = softmax(q1 k1^T * scale + mask) V
    a2  = softmax(q2 k2^T * scale + mask) V
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    out = (1 - lambda_init) * RMSNorm(a1 - lam a2; gain)      (2 d wide)

mask: causal, under a sliding `window` also i - j < window; lq1, lk1,
lq2, lk2 are four learned d-vectors and `gain` a 2 d-vector a layer; the
H / 2 outputs are concatenated, H d wide.  a1 and a2 are the four
products the paper writes (q1 against [v1 ; v2], q2 against the same).

As ONE grouped causal call (`pallas_attention._attend_causal`, the
kernels `flash_causal` / `splash_window` run, admitted by
`_causal_flash_shape(H, Hkv, S, S, d, 2 d)`): the Hkv key heads as they
are (k1, k2 of pair 0, of pair 1, ...), Hkv value heads of 2 d (V_g
under both keys of its pair, so V is written twice in HBM: PERF.md
section 7), and the H query heads ordered so that the queries of a key
head are adjacent: under k1 of pair g the q1 of its query pairs, under
k2 their q2; a1 and a2 are picked back out of the H outputs.  lam, the
difference, the norm and the factor are float32.  The backward of either
call is one kernel of the repo's own (`pallas_attention._fused_backward`:
`mx_causal_attention_bwd`, under a window `mx_window_attention_bwd`).

Routes (`pallas_attention.route_counts()`): `diff_splash` (no window),
`diff_window_splash`, and for every other shape, a mesh and the CPU
`diff_xla` (`_causal_xla` / `_window_xla`).  The core call is traced
under `full` or `window`, a component of its name stack, so that a
profile reads the two kinds of layer apart.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import kernel_route
from . import pallas_attention as pa
from .nn import _rms_norm
from .registry import register_op

_DIFF_SPLASH = kernel_route.Kernel("attention", "diff_splash", "diff_xla")
_DIFF_WINDOW = kernel_route.Kernel("attention", "diff_window_splash",
                                   "diff_xla")


def _grouped(query, key, value, heads, kv_heads):
    """(B, S, H d), (B, S, Hkv d) x 2 -> q (B, H, S, d) in the grouped
    order, k (B, Hkv, S, d), v (B, Hkv, S, 2 d)."""
    b, s, _ = query.shape
    d = query.shape[-1] // heads
    per_key = heads // kv_heads         # query pairs a key pair
    # published head = 2 (per_key g + p) + which
    q = query.reshape(b, s, kv_heads // 2, per_key, 2, d)
    q = q.transpose(0, 2, 4, 3, 1, 5).reshape(b, heads, s, d)
    k = pa._split_to_heads(key, kv_heads)
    v = pa._split_to_heads(value, kv_heads // 2)
    return q, k, jnp.repeat(v, 2, axis=1)


def _pairs(out, kv_heads):
    """(B, H, S, 2 d) in the grouped order -> a1, a2 (B, S, H / 2, 2 d),
    float32."""
    b, h, s, w = out.shape
    out = out.astype(jnp.float32).reshape(b, kv_heads // 2, 2, h // kv_heads,
                                          s, w)
    return (out[:, :, i].reshape(b, h // 2, s, w).transpose(0, 2, 1, 3)
            for i in (0, 1))


@register_op("differential_attention")
def _differential_attention(query, key, value, lambda_q1, lambda_k1,
                            lambda_q2, lambda_k2, subln_weight, num_heads=2,
                            num_kv_heads=2, window=0, lambda_init=0.8,
                            eps=1e-5, scale=None):
    """query (B, S, H d), key and value (B, S, Hkv d) (a cross layer's
    come from another layer), the four lambda vectors (d,), subln_weight
    (2 d,) -> (B, S, H d).  `window` 0: the whole triangle."""
    b, s, u = query.shape
    h, kv = num_heads, num_kv_heads
    d = u // h
    if h % 2 or kv % 2 or h % kv or key.shape != (b, s, kv * d) \
            or value.shape != key.shape:
        raise ValueError(
            f"differential_attention: query {query.shape}, key {key.shape}, "
            f"value {value.shape} for {h} heads over {kv} in pairs")
    scale = float(d ** -0.5 if scale is None else scale)
    window = int(window) if 0 < window < s else None
    q, k, v = _grouped(query, key, value, h, kv)
    kernel = _DIFF_SPLASH if window is None else _DIFF_WINDOW
    with jax.named_scope("full" if window is None else "window"):
        if kernel_route.choose(
                kernel, pa._causal_flash_shape(h, kv, s, s, d, 2 * d), b,
                kept=pa._splash_kept(b, h, s, 2 * d, query.dtype)):
            out = pa._attend_causal(q, k, v, scale, window,
                                    kernel_route.interpret(),
                                    name=kernel.route)
        elif window is None:
            out = pa._causal_xla(q, k, v, scale)
        else:
            out = pa._window_xla(q, k, v, scale, window)
    a1, a2 = _pairs(out, kv)
    f32 = lambda x: x.astype(jnp.float32)
    lam = (jnp.exp(jnp.sum(f32(lambda_q1) * f32(lambda_k1)))
           - jnp.exp(jnp.sum(f32(lambda_q2) * f32(lambda_k2))) + lambda_init)
    out = _rms_norm(a1 - lam * a2, f32(subln_weight), eps=eps) \
        * (1.0 - lambda_init)
    return out.reshape(b, s, u).astype(query.dtype)
