"""The gated short convolution of the LFM2 family's `conv` layers (ref:
the `lfm2` / `lfm2_moe` families' config.json, `conv_L_cache`; Liquid
AI's LFM2 technical report) as a registered op, so that a traced program
books its work under `short_conv` (`ops.registry.apply_pure`).

The operator's input projection gives three streams side by side, [B ; C
; x~], each as wide as the model.  The op is what lies between the two
projections,

    z_t = B_t * x~_t
    c_t = sum_{j = 0 .. L-1} w[:, j] * z_{t - (L-1) + j}     (z = 0 before 0)
    y_t = C_t * c_t

a causal depthwise convolution of L taps a channel (tap L - 1 multiplies
the current position) with a gate on either side, no activation and no
state beyond the last L - 1 positions.  One form, plain `jax.numpy`
shifts, float32 inside and rounded once.  The forward pass has to stay
ONE XLA fusion that reads the three streams and writes y (`_products`).
The backward rule is its own and keeps the operands alone: z, c and the
shifted copies are made again from them, where autodiff would keep them
(float32 arrays as large as a stream each); it lags one float32 z.  A
recomputed segment (ops/residuals.py) keeps nothing of the op: there is
no kernel here whose output only a kernel could make again.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op


def _rows(x, first, count):
    return lax.slice_in_dim(x, first, first + count, axis=1)


def _products(bcx, taps):
    """The `taps` lagged copies of z = B * x~, float32: entry j is
    z_{t - (taps - 1) + j}, zero before position 0.  From ONE zero-padded
    copy of the streams, cut by rows and then by lanes: XLA keeps pad,
    slices and products inside the fusion that uses them only so."""
    s, d = bcx.shape[1], bcx.shape[2] // 3
    padded = jnp.pad(bcx, ((0, 0), (taps - 1, 0), (0, 0)))
    rows = [_rows(padded, j, s) for j in range(taps)]
    return [r[..., :d].astype(jnp.float32) * r[..., 2 * d:].astype(
        jnp.float32) for r in rows]


def _convolved(bcx, weight):
    w = weight.astype(jnp.float32)
    return sum(w[:, j] * z for j, z in enumerate(
        _products(bcx, weight.shape[1])))


@jax.custom_vjp
def _gated_conv(bcx, weight):
    d = weight.shape[0]
    return (bcx[..., d:2 * d].astype(jnp.float32)
            * _convolved(bcx, weight)).astype(bcx.dtype)


def _gated_conv_fwd(bcx, weight):
    return _gated_conv(bcx, weight), (bcx, weight)


def _lagged(z, lag):
    """z_{t - lag} along axis 1, zero before position 0; `lag` < 0 looks
    ahead and is zero past the last position."""
    if lag == 0:
        return z
    s = z.shape[1]
    if lag > 0:
        return jnp.pad(z, ((0, 0), (lag, 0), (0, 0)))[:, :s]
    return jnp.pad(z, ((0, 0), (0, -lag), (0, 0)))[:, -lag:]


def _gated_conv_bwd(res, g):
    bcx, weight = res
    b, c, x = (part.astype(jnp.float32)
               for part in jnp.split(bcx, 3, axis=-1))
    taps = weight.shape[1]
    z = b * x
    wf = weight.astype(jnp.float32)
    lagged = [_lagged(z, taps - 1 - j) for j in range(taps)]
    w = [wf[:, j] for j in range(taps)]
    g = g.astype(jnp.float32)
    d_conv = g * c
    d_z = sum(wj * _lagged(d_conv, j - (taps - 1)) for j, wj in enumerate(w))
    d_weight = jnp.stack([(d_conv * zj).sum((0, 1)) for zj in lagged], axis=1)
    d_bcx = jnp.concatenate(
        [d_z * x, g * sum(wj * zj for wj, zj in zip(w, lagged)), d_z * b],
        axis=-1)
    return d_bcx.astype(bcx.dtype), d_weight.astype(weight.dtype)


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


@register_op("short_conv")
def _short_conv(data, weight):
    """data (B, S, 3 * D): the streams [B ; C ; x~] of the operator's
    input projection; weight (D, L): one L-tap filter a channel, tap L - 1
    on the current position.  -> C * conv(B * x~), (B, S, D) in data's
    dtype, causal over S with zeros before position 0."""
    if data.ndim != 3 or weight.ndim != 2 \
            or data.shape[-1] != 3 * weight.shape[0]:
        raise ValueError(f"short_conv: streams {data.shape} for taps "
                         f"{weight.shape}: (B, S, 3 D) and (D, L)")
    return _gated_conv(data, weight)
