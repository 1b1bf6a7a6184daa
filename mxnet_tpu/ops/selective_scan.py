"""Mamba-1's selective scan (arXiv:2312.00752, section 3) as a registered
op: a state of N numbers a CHANNEL, every (channel, state) pair with a
decay of its own,

    dt_t    = softplus(delta_t + delta_bias)              (D,)
    h_t     = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t^T    (D, N), h_0 = 0
    y_t     = h_t C_t + d_skip * x_t                      (D,)

with A = -exp(a_log) (D, N), B_t and C_t (N,) shared by the channels.
`ops.ssm.ssd_scan` (Mamba-2) needs one scalar decay a head and writes
its chunk as four matrix products; here the decay differs in every pair,
no matrix form exists, and the op is vector work along a sequential
dependence: its floor is bytes and VPU issue, not MXU FLOPs.  dt, A, the
exponentials, the state and the sums over it are float32 whatever the
inputs' dtype; y leaves in x's.  No z gate: a Gated Memory Unit reads y
ungated (`gluon/model_zoo/phi4flash.py`).

`selective_scan_sequential` is the recurrence step by step, the oracle.
Two routes, one function (`route_counts()`; how a route is chosen is
`ops/kernel_route.py`'s business):

- `chunked_xla`: the sequence in chunks; inside a chunk an associative
  scan over (decay, input) pairs, the (D, N) state carried from chunk to
  chunk by `lax.scan`, the chunk's body recomputed in the backward
  (`jax.checkpoint`), so no (B, S, D, N) array is live in either
  direction: what is kept is the state each chunk enters with.  The
  CPU's route, a mesh's, and the one the kernels are tested against.
- `fused_kernel`: two Pallas kernels under a custom VJP,
  `mx_selective_scan_fwd` and `mx_selective_scan_bwd`.  1,024 channels
  are one (8, 128) float32 register, the N states N such registers held
  across the time loop of a chunk, so a step is plain elementwise work
  on N independent chains and the sum over the states is N - 1 register
  adds; B_t and C_t arrive as scalars in SMEM.  HBM sees x, dt and y as
  (B, S, D / 128, 128) float32 (one relayout each way, in XLA, fused
  with the softplus and the skip term) and the state each chunk enters
  with, (B, S / chunk, N, D): 1 / chunk of the (B, S, D, N) array, which
  never exists.  The backward walks the chunks in reverse: a chunk's
  states are made again from its entering state into VMEM, then time
  runs backwards carrying the state's cotangent.  dB_t and dC_t are sums
  over ALL channels: their products are accumulated elementwise over the
  channel blocks in VMEM and reduced once a chunk (over sublanes in the
  kernel, over lanes in XLA).  Nothing is named for a recomputed segment
  (ops/residuals.py): the entering states are the forward kernel's own
  second output, a recomputed layer runs the forward kernel again (a
  third of the backward's work) and keeps nothing of the op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from . import kernel_route
from .registry import register_op

__all__ = ["selective_scan_sequential", "route_counts", "supports"]

ROUTES = ("chunked_xla", "fused_kernel")
kernel_route.declare("selective_scan", ROUTES)
_FUSED_KERNEL = kernel_route.Kernel("selective_scan", "fused_kernel",
                                    "chunked_xla")

#: channels a register: 8 sublanes of 128 lanes
_ROWS, _LANES = 8, 128
_BLOCK = _ROWS * _LANES


def route_counts():
    """{route: `selective_scan` calls traced through it} since import."""
    return kernel_route.counts("selective_scan")


def supports(channels, state, seq, chunk):
    """The shapes the kernels take: channels in whole (8, 128) registers,
    the published state of 16 (16 registers of state and 16 of A beside
    the step's own fit the 64 there are), whole chunks."""
    return channels % _BLOCK == 0 and state == 16 and seq % chunk == 0


def _discretize(delta, delta_bias, a_log):
    """-> (dt = softplus(delta + bias), A = -exp(a_log)), float32."""
    dt = jax.nn.softplus(delta.astype(jnp.float32)
                         + delta_bias.astype(jnp.float32))
    return dt, -jnp.exp(a_log.astype(jnp.float32))


def selective_scan_sequential(x, delta, a_log, b, c, d_skip, delta_bias):
    """The recurrence step by step under `lax.scan`, all in float32: x
    and delta (B, S, D), a_log (D, N), b and c (B, S, N), d_skip and
    delta_bias (D,)."""
    dt, a = _discretize(delta, delta_bias, a_log)
    x32 = x.astype(jnp.float32)

    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs            # (B, D) x 2, (B, N) x 2
        h = (jnp.exp(dt_t[..., None] * a) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    h0 = jnp.zeros(x.shape[:1] + a.shape, jnp.float32)
    _, y = lax.scan(step, h0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (
            x32, dt, b.astype(jnp.float32), c.astype(jnp.float32))))
    y = jnp.moveaxis(y, 0, 1) + d_skip.astype(jnp.float32) * x32
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked_xla
# ---------------------------------------------------------------------------

def _combine(earlier, later):
    """(decay, input) pairs compose: h -> a h + u."""
    a1, u1 = earlier
    a2, u2 = later
    return a1 * a2, a2 * u1 + u2


def _chunk_xla(a, h, inputs):
    """One chunk: the state it enters with (B, D, N) -> (the state it
    leaves, y (B, l, D)), float32; the (B, l, D, N) arrays live here."""
    x, dt, b, c = inputs                        # (B, l, D) x 2, (B, l, N) x 2
    decay = jnp.exp(dt[..., None] * a)
    drive = (dt * x)[..., None] * b[:, :, None, :]
    decay, drive = lax.associative_scan(_combine, (decay, drive), axis=1)
    states = decay * h[:, None] + drive
    return states[:, -1], jnp.einsum("bldn,bln->bld", states, c)


def _scan_xla(x, delta, a_log, b, c, d_skip, delta_bias, chunk):
    bsz, s, d = x.shape
    dt, a = _discretize(delta, delta_bias, a_log)
    x32 = x.astype(jnp.float32)

    def chunks(v):      # (B, S, ...) -> (S / chunk, B, chunk, ...)
        return jnp.moveaxis(
            v.astype(jnp.float32).reshape(bsz, s // chunk, chunk, -1), 1, 0)

    h0 = jnp.zeros((bsz,) + a.shape, jnp.float32)
    _, y = lax.scan(jax.checkpoint(functools.partial(_chunk_xla, a)), h0,
                    (chunks(x32), chunks(dt), chunks(b), chunks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, s, d)
    return (y + d_skip.astype(jnp.float32) * x32).astype(x.dtype)


# ---------------------------------------------------------------------------
# fused_kernel
# ---------------------------------------------------------------------------
# In both kernels: grid (batch, chunk, channel block), the channel block
# innermost so that a chunk's B and C are brought to SMEM once for all its
# blocks; the chunk axis is sequential (the forward carries the state, the
# backward its cotangent, a (N, 8, 128) float32 scratch a channel block).

_VMEM_LIMIT = 64 * 1024 * 1024


def _specs(pl, pltpu, n, chunk, reverse, chunks):
    """The BlockSpecs both kernels share, over the grid (b, j, cb)."""
    at = (lambda j: chunks - 1 - j) if reverse else (lambda j: j)
    return {
        # B and C of a chunk, flat (chunk * N,) float32 scalars
        "bc": pl.BlockSpec((None, chunk * n), lambda b, j, cb: (b, at(j)),
                           memory_space=pltpu.SMEM),
        # x, dt, y and their cotangents: (chunk, 8, 128) of (B, S, D/128, 128)
        "seq": pl.BlockSpec((None, chunk, _ROWS, _LANES),
                            lambda b, j, cb: (b, at(j), cb, 0)),
        # A, (N, 8, 128) of (N, D/128, 128)
        "a": pl.BlockSpec((n, _ROWS, _LANES), lambda b, j, cb: (0, cb, 0)),
        # the state a chunk enters with, (N, 8, 128) of
        # (B, S/chunk, N, D/128, 128); dA's part a chunk likewise
        "state": pl.BlockSpec((None, None, n, _ROWS, _LANES),
                              lambda b, j, cb: (b, at(j), 0, cb, 0)),
        # dB and dC summed over sublanes: (chunk, N, 128) of (B, S, N, 128)
        "dbc": pl.BlockSpec((None, chunk, n, _LANES),
                            lambda b, j, cb: (b, at(j), 0, 0)),
    }


def _advance(h, t, n, a_n, b_ref, x_ref, dt_ref):
    """One step of the recurrence inside a kernel: the N state registers
    after position t of the chunk."""
    dt_t = dt_ref[t]
    u = dt_t * x_ref[t]
    return tuple(jnp.exp(dt_t * a_n[i]) * h[i] + u * b_ref[t * n + i]
                 for i in range(n))


@kernel_route.shared_kernel("chunk")
def _fwd_pallas(x, dt, a, b, c, chunk, interpret=False):
    """`mx_selective_scan_fwd`: x, dt (B, S, D/128, 128), a (N, D/128,
    128), b, c (B, S * N), all float32 -> (y without the skip term, like
    x; the state each chunk enters with (B, S/chunk, N, D/128, 128))."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, blocks, _ = x.shape
    n, chunks, cbs = a.shape[0], s // chunk, blocks // _ROWS
    spec = _specs(pl, pltpu, n, chunk, False, chunks)

    def kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, y_ref, hs_ref, h_scr):
        j, cb = pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _():
            h_scr[cb] = jnp.zeros(h_scr.shape[1:], jnp.float32)

        hs_ref[...] = h_scr[cb]
        a_n = [a_ref[i] for i in range(n)]

        def step(t, h):
            h = _advance(h, t, n, a_n, b_ref, x_ref, dt_ref)
            y_ref[t] = sum(h[i] * c_ref[t * n + i] for i in range(n))
            return h

        h = lax.fori_loop(0, chunk, step,
                          tuple(h_scr[cb, i] for i in range(n)))
        for i in range(n):
            h_scr[cb, i] = h[i]

    return pl.pallas_call(
        kernel,
        grid=(bsz, chunks, cbs),
        in_specs=[spec["bc"], spec["bc"], spec["seq"], spec["seq"],
                  spec["a"]],
        out_specs=[spec["seq"], spec["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct((bsz, chunks, n, blocks, _LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((cbs, n, _ROWS, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mx_selective_scan_fwd",
    )(b, c, x, dt, a)


@kernel_route.shared_kernel("chunk")
def _bwd_pallas(x, dt, a, b, c, hs, dy, chunk, interpret=False):
    """`mx_selective_scan_bwd`: the forward's operands, the states the
    chunks enter with and y's cotangent -> (dx, d dt like x; dA a chunk
    (B, S/chunk, N, D/128, 128); dB, dC summed over sublanes only (B, S,
    N, 128)), float32.

    Per step, with g_t the cotangent of h_t, u_t = dt_t x_t, a_t =
    exp(dt_t A):

        g_t  = dy_t C_t + a_{t+1} g_{t+1}
        dC_t = sum_c dy_t h_t            dB_t = sum_c g_t u_t
        du_t = sum_n g_t B_t             q_t  = g_t a_t h_{t-1}
        dx_t = du_t dt_t                 d dt_t = du_t x_t + sum_n q_t A
        dA  += q_t dt_t
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, blocks, _ = x.shape
    n, chunks, cbs = a.shape[0], s // chunk, blocks // _ROWS
    spec = _specs(pl, pltpu, n, chunk, True, chunks)
    tile = (n, _ROWS, _LANES)

    def kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, hs_ref, dy_ref,
               dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
               g_scr, h_buf, db_acc, dc_acc):
        j, cb = pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _():
            g_scr[cb] = jnp.zeros(tile, jnp.float32)

        @pl.when(cb == 0)
        def _():
            db_acc[...] = jnp.zeros(db_acc.shape, jnp.float32)
            dc_acc[...] = jnp.zeros(dc_acc.shape, jnp.float32)

        a_n = [a_ref[i] for i in range(n)]

        # the chunk's states again: h_buf[t + 1] = h_t, h_buf[0] entering
        h_buf[0] = hs_ref[...]

        def forward(t, h):
            h = _advance(h, t, n, a_n, b_ref, x_ref, dt_ref)
            for i in range(n):
                h_buf[t + 1, i] = h[i]
            return h

        lax.fori_loop(0, chunk, forward,
                      tuple(hs_ref[i] for i in range(n)))
        da_ref[...] = jnp.zeros(tile, jnp.float32)

        def backward(k, carried):
            t = chunk - 1 - k
            dt_t, x_t, dy_t = dt_ref[t], x_ref[t], dy_ref[t]
            u = dt_t * x_t
            du = ddt = None
            out = []
            for i in range(n):
                a_t = jnp.exp(dt_t * a_n[i])
                g = dy_t * c_ref[t * n + i] + carried[i]
                dc_acc[t, i] += dy_t * h_buf[t + 1, i]
                db_acc[t, i] += g * u
                part = g * b_ref[t * n + i]
                du = part if du is None else du + part
                ga = g * a_t
                q = ga * h_buf[t, i]
                part = q * a_n[i]
                ddt = part if ddt is None else ddt + part
                da_ref[i] += q * dt_t
                out.append(ga)
            dx_ref[t] = du * dt_t
            ddt_ref[t] = du * x_t + ddt
            return tuple(out)

        g = lax.fori_loop(0, chunk, backward,
                          tuple(g_scr[cb, i] for i in range(n)))
        for i in range(n):
            g_scr[cb, i] = g[i]

        @pl.when(cb == cbs - 1)
        def _():
            db_ref[...] = db_acc[...].sum(axis=2)
            dc_ref[...] = dc_acc[...].sum(axis=2)

    like_x = jax.ShapeDtypeStruct(x.shape, jnp.float32)
    like_bc = jax.ShapeDtypeStruct((bsz, s, n, _LANES), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(bsz, chunks, cbs),
        in_specs=[spec["bc"], spec["bc"], spec["seq"], spec["seq"],
                  spec["a"], spec["state"], spec["seq"]],
        out_specs=[spec["seq"], spec["seq"], spec["state"], spec["dbc"],
                   spec["dbc"]],
        out_shape=[like_x, like_x,
                   jax.ShapeDtypeStruct(hs.shape, jnp.float32),
                   like_bc, like_bc],
        scratch_shapes=[
            pltpu.VMEM((cbs,) + tile, jnp.float32),
            pltpu.VMEM((chunk + 1,) + tile, jnp.float32),
            pltpu.VMEM((chunk,) + tile, jnp.float32),
            pltpu.VMEM((chunk,) + tile, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mx_selective_scan_bwd",
    )(b, c, x, dt, a, hs, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _core(x, dt, a, b, c, chunk):
    """The recurrence and the sum over the states through the kernels,
    in the kernels' layout, float32."""
    return _fwd_pallas(x, dt, a, b, c, chunk=chunk)[0]


def _core_fwd(x, dt, a, b, c, chunk):
    y, hs = _fwd_pallas(x, dt, a, b, c, chunk=chunk)
    return y, (x, dt, a, b, c, hs)


def _core_bwd(chunk, res, dy):
    x, dt, a, b, c, hs = res
    dx, ddt, da, db, dc = _bwd_pallas(x, dt, a, b, c, hs, dy, chunk=chunk)
    return (dx, ddt, da.sum((0, 1)), db.sum(-1).reshape(b.shape),
            dc.sum(-1).reshape(c.shape))


_core.defvjp(_core_fwd, _core_bwd)


def _scan_kernels(x, delta, a_log, b, c, d_skip, delta_bias, chunk):
    """The same function through the kernels: softplus, -exp, the skip
    term, the relayouts and their derivatives stay in XLA."""
    bsz, s, d = x.shape
    n = a_log.shape[1]
    dt, a = _discretize(delta, delta_bias, a_log)
    x32 = x.astype(jnp.float32)
    tiled = lambda v: v.reshape(bsz, s, d // _LANES, _LANES)
    flat = lambda v: v.astype(jnp.float32).reshape(bsz, s * n)
    y = _core(tiled(x32), tiled(dt), a.T.reshape(n, d // _LANES, _LANES),
              flat(b), flat(c), chunk)
    return (y.reshape(bsz, s, d)
            + d_skip.astype(jnp.float32) * x32).astype(x.dtype)


@register_op("selective_scan")
def _selective_scan(x, delta, a_log, b, c, d_skip, delta_bias, chunk=64):
    """Mamba-1's selective scan: x and delta (B, S, D), delta before its
    bias and softplus; a_log (D, N); b and c (B, S, N); d_skip and
    delta_bias (D,); S a multiple of `chunk` (or shorter than one).
    Returns y (B, S, D) in x's dtype, ungated."""
    s, d = x.shape[1:]
    chunk = min(chunk, s)
    if delta.shape != x.shape or a_log.shape[0] != d or s % chunk \
            or b.shape != c.shape or b.shape[-1] != a_log.shape[1]:
        raise MXNetError(
            f"selective_scan: x {x.shape}, delta {delta.shape}, a_log "
            f"{a_log.shape}, b {b.shape}, c {c.shape}, chunk {chunk}")
    operands = (x, delta, a_log, b, c, d_skip, delta_bias)
    xla = functools.partial(_scan_xla, chunk=chunk)
    if not kernel_route.choose(
            _FUSED_KERNEL, supports(d, a_log.shape[1], s, chunk),
            x.shape[0]):
        return xla(*operands)
    return kernel_route.dispatch(
        functools.partial(_scan_kernels, chunk=chunk), xla, *operands,
        interpret=kernel_route.interpret())
