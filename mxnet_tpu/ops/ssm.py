"""State-space mixer ops (Mamba-2, arXiv:2405.21060): the causal depthwise
convolution in front of the scan, and the scan itself in its chunked
state-space-dual form.

Per head, with state S in R^{P x N}, a scalar decay a < 0 and a step
dt_t > 0:

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

`ssd_scan` computes it chunk by chunk as four matrix products (scores
C B^T within a chunk, decayed scores times values, the state each chunk
leaves behind, the entering state times C) plus one small recurrence over
the chunks.  dt, a, the cumulative decays and their exponentials stay in
float32 whatever the inputs' dtype, and so does the state carried from
chunk to chunk; the products take their operands in the inputs' dtype and
accumulate in float32, as the published kernels do.
`ssd_scan_sequential` is the recurrence above, step by step: the oracle
both routes are tested against.

Two routes, one algorithm (`route_counts()`; how a route is chosen is
`ops/kernel_route.py`'s business):

- `fused_kernel` (PR 28): `ops.pallas_ssd`'s forward and backward kernels
  under a custom_vjp, every per-chunk intermediate in VMEM; taken when
  the widths fill whole lanes (`pallas_ssd.supports`).
- `chunked_xla`: plain XLA einsums, backward by autodiff; every other
  shape, and the reference the kernels are tested against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from . import kernel_route, pallas_ssd
from .registry import register_op

__all__ = ["ssd_scan_sequential", "route_counts"]

ROUTES = ("chunked_xla", "fused_kernel")
kernel_route.declare("ssd_scan", ROUTES)
_FUSED_KERNEL = kernel_route.Kernel("ssd_scan", "fused_kernel", "chunked_xla")


def route_counts():
    """{route: `ssd_scan` calls traced through it} since import."""
    return kernel_route.counts("ssd_scan")


@register_op("causal_conv1d")
def _causal_conv1d(data, weight, bias=None):
    """Causal depthwise convolution along the sequence: data (B, S, C),
    weight (C, K), bias (C,); out[t] = sum_j weight[:, j] * data[t-K+1+j]
    (+ bias), positions before the start read as zero.  Accumulated in
    float32, returned in data's dtype."""
    k, s = weight.shape[-1], data.shape[1]
    padded = jnp.pad(data.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    out = sum(padded[:, j:j + s] * w[:, j] for j in range(k))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(data.dtype)


def _discretize(dt, dt_bias, a_log):
    """-> (dt = softplus(dt + dt_bias), a = -exp(a_log)), float32."""
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))
    return dt, -jnp.exp(a_log.astype(jnp.float32))


def ssd_scan_sequential(x, dt, a_log, b, c, d, dt_bias):
    """The recurrence step by step under `lax.scan`, all in float32: x
    (B, S, H, P), dt (B, S, H) before its softplus, a_log / d / dt_bias
    (H,), b and c (B, S, G, N) with H // G heads sharing a group."""
    dt, a = _discretize(dt, dt_bias, a_log)
    x32 = x.astype(jnp.float32)
    heads_per_group = x.shape[2] // b.shape[2]
    b32, c32 = (jnp.repeat(m.astype(jnp.float32), heads_per_group, axis=2)
                for m in (b, c))

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs            # (B,H,P) (B,H) (B,H,N) x 2
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    bsz, _s, h, p = x.shape
    state0 = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    _, y = lax.scan(step, state0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x32, dt, b32, c32)))
    y = jnp.moveaxis(y, 0, 1) + d.astype(jnp.float32)[:, None] * x32
    return y.astype(x.dtype)


def _ssd_chunked(x, dt, a, b, c, chunk):
    """y without the D x term, float32: x (B, S, H, P) in the products'
    dtype, dt (B, S, H) and a (H,) float32, b and c (B, S, G, N)."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    r, nc, f32 = h // g, s // chunk, jnp.float32
    xd = (x.astype(f32) * dt[..., None]).astype(x.dtype).reshape(
        bsz, nc, chunk, g, r, p)
    bm = b.reshape(bsz, nc, chunk, g, n)
    cm = c.reshape(bsz, nc, chunk, g, n)
    # cumulative log-decay inside each chunk, (B, c, G, R, l), float32
    cs = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, g, r), axis=2)
    cs = jnp.moveaxis(cs, 2, -1)

    # 1. inside a chunk: (C B^T) * decay, lower triangle, times values
    scores = jnp.einsum("bclgn,bcsgn->bcgls", cm, bm,
                        preferred_element_type=f32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp",
                   (scores[:, :, :, None] * decay).astype(x.dtype), xd,
                   preferred_element_type=f32)

    # 2. the state each chunk leaves behind, (B, c, G, R, P, N)
    to_end = jnp.moveaxis(jnp.exp(cs[..., -1:] - cs), -1, 2)
    states = jnp.einsum(
        "bclgn,bclgrp->bcgrpn", bm,
        (xd.astype(f32) * to_end[..., None]).astype(x.dtype),
        preferred_element_type=f32)

    # 3. the state each chunk enters with: a float32 recurrence over the
    # chunks, written as one lower-triangular product
    total = jnp.cumsum(cs[..., -1], axis=1)             # (B, c, G, R)
    before = jnp.concatenate(
        [jnp.zeros_like(total[:, :1]), total[:, :-1]], axis=1)
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)    # [z, c]: c < z
    carry = jnp.exp(jnp.where(
        earlier[:, :, None, None],
        before[:, :, None] - total[:, None, :], -jnp.inf))  # (B,z,c,G,R)
    entering = jnp.einsum("bzcgr,bcgrpn->bzgrpn", carry, states,
                          precision=lax.Precision.HIGHEST)

    # 4. the entering state read through C
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", cm,
                       entering.astype(x.dtype),
                       preferred_element_type=f32) \
        * jnp.moveaxis(jnp.exp(cs), -1, 2)[..., None]
    return y.reshape(bsz, s, h, p)


@register_op("ssd_scan")
def _ssd_scan(x, dt, a_log, b, c, d, dt_bias, chunk=128):
    """Mamba-2's selective scan, chunked: x (B, S, H, P), dt (B, S, H)
    before bias and softplus, a_log / d / dt_bias (H,), b and c (B, S, G,
    N); S a multiple of `chunk` (or shorter than one).  Returns y
    (B, S, H, P) in x's dtype."""
    s, h, g = x.shape[1], x.shape[2], b.shape[2]
    chunk = min(chunk, s)
    if s % chunk or h % g:
        raise MXNetError(
            f"ssd_scan: sequence {s} must be a multiple of the chunk "
            f"{chunk}, heads {h} of the groups {g}")
    operands = (x, dt, a_log, b, c, d, dt_bias)
    xla = functools.partial(_scan_xla, chunk=chunk)
    if not kernel_route.choose(
            _FUSED_KERNEL,
            pallas_ssd.supports(h, x.shape[3], g, b.shape[3], s, chunk),
            x.shape[0]):
        return xla(*operands)
    return kernel_route.dispatch(
        functools.partial(_scan_kernels, chunk=chunk), xla, *operands,
        interpret=kernel_route.interpret())


def _scan_xla(x, dt, a_log, b, c, d, dt_bias, chunk):
    dt, a = _discretize(dt, dt_bias, a_log)
    y = _ssd_chunked(x, dt, a, b, c, chunk)
    y = y + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return y.astype(x.dtype)


def _kernel_operands(x, dt, a_log, b, c, d, dt_bias, chunk):
    """What `ops.pallas_ssd`'s kernels read: x, B and C in the layout
    they arrive in (free reshapes), dt after its softplus and the
    cumulative log-decay inside each chunk, float32 (B, S, H)."""
    bsz, s, h, _p = x.shape
    dt, a = _discretize(dt, dt_bias, a_log)
    cs = jnp.cumsum((dt * a).reshape(bsz, s // chunk, chunk, h),
                    axis=2).reshape(bsz, s, h)
    return (x.reshape(bsz, s, -1), dt, cs, b.reshape(bsz, s, -1),
            c.reshape(bsz, s, -1), d)


def _scan_kernels(x, dt, a_log, b, c, d, dt_bias, chunk):
    """The same function through the kernels: the (B, S, H) arithmetic
    (softplus, the cumulative sums, and their derivatives by autodiff)
    stays in XLA, 4 MB arrays."""
    y = pallas_ssd.ssd_scan_kernels(
        *_kernel_operands(x, dt, a_log, b, c, d, dt_bias, chunk),
        b.shape[2], chunk)
    return y.reshape(x.shape)
