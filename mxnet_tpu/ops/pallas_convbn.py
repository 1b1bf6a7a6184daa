"""Cross-layer fused Conv+BN+ReLU unit: Pallas TPU kernel + XLA fallback.

The ResNet-50 train step is HBM-bound (PERF.md roofline: 85-95% of
achievable bandwidth at op granularity), so the remaining headroom is
activation *traffic*, not FLOPs.  The reference gets its version of this
from cuDNN fused conv epilogues + MKLDNN subgraph fusion (ref:
src/operator/subgraph/mkldnn/mkldnn_conv.cc fuses conv+BN+ReLU); the
TPU-native equivalent is this kernel.

The unit computes, for one conv layer k inside a conv->BN->ReLU chain:

    u  = act(x * in_scale + in_bias)        # layer k-1's BatchNorm+ReLU,
                                            # applied WHILE READING x (the
                                            # raw conv_{k-1} output) so the
                                            # normalized activation is never
                                            # materialized in HBM
    y  = conv(u, w)                         # this layer's conv (raw out)
    s1 = sum_c(y); s2 = sum_c((y-shift)^2)  # BN statistics of y, folded
                                            # into the conv epilogue so the
                                            # separate stats pass disappears

A chain of these units touches HBM twice per layer (read x, write y) vs
~5 passes/layer for the op-granular path (conv write, stats read,
normalize read+write, next-conv read).  `shift` is the running mean: the
variance uses the same shifted single-pass formula as ops/nn.py
`_batch_norm` (E[(y-c)^2] - (mean-c)^2, warm-stat exact, floor-bounded)
so fused and unfused training see identical statistics semantics.

Backward is hand-written XLA (not Pallas): dgrad/wgrad via
jax.linear_transpose of the forward conv (exactly the transpose convs
XLA autodiff would emit, with no forward recompute), the BN-stat
cotangents folded into dy (dy_tot = dy + g_s1 + 2(y-shift)g_s2), and the
input-affine/ReLU backward recomputed elementwise from x.  Residuals are
(inputs, y): y is the layer activation that the op-granular path would
have stored anyway, so fusion adds no activation memory.

The Pallas path needs layout NHWC (channels on the 128-lane axis) and a
TPU backend; everything else (CPU tests, NCHW, MXNET_USE_PALLAS=0, a
shape the compiler refuses — logged once per shape at WARNING) takes
the XLA fallback with identical semantics, and `unit_counts()` says how
many units ran on each path.
"""
from __future__ import annotations

import functools
import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..analysis import sanitizer as _mxsan
from ..util import env
from .registry import register_op

__all__ = ["fused_conv_unit"]

#: guards the per-shape probe cache and the unit counts below — serving
#: threads and the training loop race the first conv dispatch (mxlint
#: MX004)
_PROBE_LOCK = threading.Lock()

# VMEM working-set budget for choosing the per-program batch tile
# (padded activation + fp32 accumulator + double-buffered x/y grid
# blocks), leaving headroom for the weight taps and Mosaic's own
# scratch inside the 16MB core VMEM.
_COLS_BUDGET_BYTES = 8 * 1024 * 1024


def _pallas_wanted() -> bool:
    """Pallas usable?  MXNET_USE_PALLAS and a backend that can run it:
    Mosaic on an accelerator, the interpreter (MXNET_PALLAS_INTERPRET,
    tests) on CPU.  Whether Mosaic accepts a given shape is decided per
    shape by `_probe_ok`, which says so when it does not."""
    if not env.get_bool("MXNET_USE_PALLAS"):
        return False
    return (jax.default_backend() != "cpu"
            or env.get_bool("MXNET_PALLAS_INTERPRET"))


def _batch_tile(n, h, w, ci, ho, wo, co, itemsize=2, pad=(1, 1)):
    """Largest power-of-two batch tile dividing n whose whole VMEM
    working set (bytes) fits the budget.  Tap-accumulation working set:
    padded activation block u, fp32 accumulator, one tap slice, plus
    double-buffered x and y grid blocks.  >=1 even when one image
    overflows (the 56x56 stage must still run).  `itemsize` is the
    activation dtype width (2 for bf16, 4 for fp32)."""
    hp, wp = h + 2 * pad[0], w + 2 * pad[1]
    per_image = (hp * wp * ci * itemsize             # u (padded)
                 + ho * wo * co * 4                  # fp32 accumulator
                 + ho * wo * ci * itemsize           # tap slice temp
                 + 2 * h * w * ci * itemsize         # x block, dbuf
                 + 2 * ho * wo * co * itemsize)      # y block, dbuf
    nb = 1
    while nb * 2 <= n and n % (nb * 2) == 0 \
            and (nb * 2) * per_image <= _COLS_BUDGET_BYTES:
        nb *= 2
    return nb


def _out_hw(h, w, kernel, stride, pad):
    ho = (h + 2 * pad[0] - kernel[0]) // stride[0] + 1
    wo = (w + 2 * pad[1] - kernel[1]) // stride[1] + 1
    return ho, wo


def _weight_taps(w):
    """(Co, Ci, kh, kw) checkpoint layout -> (kh, kw, Ci, Co) tap array.

    One (Ci, Co) MXU panel per kernel tap — the tap-accumulation kernel
    indexes w_ref[ky, kx] instead of building an im2col panel (Mosaic
    rejects the in-kernel concatenate an im2col needs; round-5 on-chip
    finding)."""
    return jnp.transpose(w, (2, 3, 1, 0))


# ---------------------------------------------------------------------------
# Pallas forward
# ---------------------------------------------------------------------------

def _pallas_unit(x, w, in_scale, in_bias, shift, *, kernel, stride, pad,
                 act_in, want_stats):
    """Tap-accumulation formulation (round-5, validated on-chip): one
    (Ci, Co) MXU matmul per kernel tap accumulated in fp32, with the
    input affine+ReLU applied in VMEM and padding applied AFTER the
    affine (padded positions must be exact zeros, not relu(bias)).
    Strided taps extract their polyphase plane via contiguous slice +
    reshape + unit-index — a strided slice lowers to a gather Mosaic
    does not support."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, wd, ci = x.shape
    co = w.shape[0]
    kh, kw = kernel
    sh_, sw_ = stride
    ho, wo = _out_hw(h, wd, kernel, stride, pad)
    nb = _batch_tile(n, h, wd, ci, ho, wo, co,
                     itemsize=x.dtype.itemsize, pad=pad)
    wtaps = _weight_taps(w)
    out_dtype = x.dtype

    def kern(x_ref, w_ref, sc_ref, bi_ref, sh_ref, y_ref, s1_ref, s2_ref):
        xb = x_ref[...]
        if act_in:
            u = xb.astype(jnp.float32) * sc_ref[...] + bi_ref[...]
            u = jnp.maximum(u, 0.0).astype(xb.dtype)
        else:
            u = xb
        # window pad + (stride-1) extra so every tap's CONTIGUOUS slice
        # of length s*ho / s*wo stays in bounds
        if pad != (0, 0) or sh_ > 1 or sw_ > 1:
            u = jnp.pad(u, ((0, 0), (pad[0], pad[0] + sh_ - 1),
                            (pad[1], pad[1] + sw_ - 1), (0, 0)))
        acc = jnp.zeros((nb * ho * wo, co), jnp.float32)
        for ky in range(kh):
            for kx in range(kw):
                if sh_ == 1 and sw_ == 1:
                    sl = u[:, ky:ky + ho, kx:kx + wo, :]
                else:
                    rows = u[:, ky:ky + sh_ * ho, :, :]
                    rows = rows.reshape(nb, ho, sh_, rows.shape[2],
                                        ci)[:, :, 0]
                    cols = rows[:, :, kx:kx + sw_ * wo, :]
                    sl = cols.reshape(nb, ho, wo, sw_, ci)[:, :, :, 0]
                acc = acc + jnp.dot(sl.reshape(nb * ho * wo, ci),
                                    w_ref[ky, kx],
                                    preferred_element_type=jnp.float32)
        yc = acc.astype(out_dtype)
        y_ref[...] = yc.reshape(nb, ho, wo, co)
        # the stat outputs must be written in EVERY mode — an output
        # block left untouched returns whatever was in VMEM (the XLA
        # fallback returns zeros for want_stats=False; match it)
        @pl.when(pl.program_id(0) == 0)
        def _():
            s1_ref[...] = jnp.zeros_like(s1_ref)
            s2_ref[...] = jnp.zeros_like(s2_ref)

        if want_stats:
            # stats of the STORED (cast) value, accumulated fp32 across
            # the sequential grid — semantics identical to the unfused
            # BatchNorm reading the bf16 activation back from HBM
            yf = yc.astype(jnp.float32)
            d = yf - sh_ref[...]
            s1_ref[...] += jnp.sum(yf, axis=0, keepdims=True)
            s2_ref[...] += jnp.sum(d * d, axis=0, keepdims=True)

    grid = (n // nb,)
    y, s1, s2 = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nb, h, wd, ci), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kh, kw, ci, co), lambda i: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, ci), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, ci), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, co), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((nb, ho, wo, co), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, co), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, co), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, ho, wo, co), out_dtype),
            jax.ShapeDtypeStruct((1, co), jnp.float32),
            jax.ShapeDtypeStruct((1, co), jnp.float32),
        ],
        interpret=env.get_bool("MXNET_PALLAS_INTERPRET"),
    )(x, wtaps, in_scale.reshape(1, ci), in_bias.reshape(1, ci),
      shift.reshape(1, co))
    return y, s1.reshape(co), s2.reshape(co)


# ---------------------------------------------------------------------------
# Pallas backward (opt-in: MXNET_FUSED_CONVBN_BWD=1)
# ---------------------------------------------------------------------------

def _batch_tile_bwd(n, h, w, ci, ho, wo, co, kh, kw, itemsize=2,
                    pad=(1, 1)):
    """Batch tile for the backward kernel: the fp32 du accumulator and
    the padded activation dominate; the fp32 dw tap accumulator is a
    FIXED cost independent of nb and is subtracted from the budget
    up front (512-channel stages overflow VMEM here and take the XLA
    fallback via the compile probe)."""
    fixed = kh * kw * ci * co * 4          # dw accumulator (f32)
    budget = _COLS_BUDGET_BYTES - fixed
    hp, wp = h + 2 * pad[0], w + 2 * pad[1]
    per_image = (hp * wp * ci * (itemsize + 4)            # u_pad + du_pad
                 + 2 * h * w * ci * itemsize              # x block, dbuf
                 + 3 * ho * wo * co * itemsize            # y + gy + dy
                 + h * w * ci * itemsize)                 # gx out
    nb = 1
    while nb * 2 <= n and n % (nb * 2) == 0 \
            and (nb * 2) * per_image <= max(budget, 0):
        nb *= 2
    return nb


def _pallas_unit_bwd(x, w, in_scale, in_bias, shift, y, gy, gs1, gs2, *,
                     kernel, stride, pad, act_in, want_stats):
    """Single-pass fused backward: dy_tot (BN-stat cotangent fold) is
    computed once in VMEM, then each kernel tap contributes one wgrad
    matmul (Ci,Co) and one dgrad matmul (M,Ci) whose result is
    accumulated into the padded input-grad buffer by a static pad —
    dy and the recomputed activation are read from HBM exactly once,
    where the XLA path's separate dgrad/wgrad convs read them twice.
    Stride-1 only (the dgrad of a strided conv needs interior-dilated
    pads, unproven under Mosaic; strided shapes take the XLA path)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, wd, ci = x.shape
    co = w.shape[0]
    kh, kw = kernel
    ho, wo = _out_hw(h, wd, kernel, stride, pad)
    hp, wp = h + 2 * pad[0], wd + 2 * pad[1]
    nb = _batch_tile_bwd(n, h, wd, ci, ho, wo, co, kh, kw,
                         itemsize=x.dtype.itemsize, pad=pad)
    wtaps = _weight_taps(w)
    gy_dtype = gy.dtype

    def kern(x_ref, w_ref, sc_ref, bi_ref, sh_ref, y_ref, gy_ref,
             gs1_ref, gs2_ref, gx_ref, dw_ref, gsc_ref, gbi_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)
            gsc_ref[...] = jnp.zeros_like(gsc_ref)
            gbi_ref[...] = jnp.zeros_like(gbi_ref)

        gyb = gy_ref[...].astype(jnp.float32)
        if want_stats:
            yf = y_ref[...].astype(jnp.float32)
            dy = (gyb + gs1_ref[...].reshape(1, 1, 1, co)
                  + 2.0 * (yf - sh_ref[...].reshape(1, 1, 1, co))
                  * gs2_ref[...].reshape(1, 1, 1, co))
        else:
            dy = gyb
        # match the XLA path's rounding: dy_tot is cast to gy.dtype
        # before entering the transpose convs
        dyf = dy.astype(gy_dtype).reshape(nb * ho * wo, co)

        xb = x_ref[...]
        if act_in:
            uf32 = (xb.astype(jnp.float32) * sc_ref[...] + bi_ref[...])
            u = jnp.maximum(uf32, 0.0).astype(xb.dtype)
        else:
            u = xb
        if pad != (0, 0):
            u = jnp.pad(u, ((0, 0), (pad[0], pad[0]),
                            (pad[1], pad[1]), (0, 0)))

        du_pad = jnp.zeros((nb, hp, wp, ci), jnp.float32)
        for ky in range(kh):
            for kx in range(kw):
                sl = u[:, ky:ky + ho, kx:kx + wo, :] \
                    .reshape(nb * ho * wo, ci)
                # wgrad tap: (Ci, Co), contract the patch dim
                dw_ref[ky, kx] += jax.lax.dot_general(
                    sl, dyf, dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                # dgrad tap: (M, Ci), contract Co
                contrib = jax.lax.dot_general(
                    dyf, w_ref[ky, kx],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                du_pad = du_pad + jnp.pad(
                    contrib.reshape(nb, ho, wo, ci),
                    ((0, 0), (ky, hp - ho - ky), (kx, wp - wo - kx),
                     (0, 0)))
        du = du_pad[:, pad[0]:pad[0] + h, pad[1]:pad[1] + wd, :]
        if act_in:
            gu = jnp.where(uf32 > 0.0, du, 0.0)
            gx_ref[...] = (gu * sc_ref[...]).astype(gx_ref.dtype)
            gsc_ref[...] += jnp.sum(
                gu * xb.astype(jnp.float32), axis=(0, 1, 2)) \
                .reshape(1, ci)
            gbi_ref[...] += jnp.sum(gu, axis=(0, 1, 2)).reshape(1, ci)
        else:
            gx_ref[...] = du.astype(gx_ref.dtype)

    grid = (n // nb,)
    cspec = lambda r, c: pl.BlockSpec((r, c), lambda i: (0, 0),
                                      memory_space=pltpu.VMEM)
    gx, dw_taps, gsc, gbi = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nb, h, wd, ci), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kh, kw, ci, co), lambda i: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            cspec(1, ci), cspec(1, ci), cspec(1, co),
            pl.BlockSpec((nb, ho, wo, co), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((nb, ho, wo, co), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            cspec(1, co), cspec(1, co),
        ],
        out_specs=[
            pl.BlockSpec((nb, h, wd, ci), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kh, kw, ci, co), lambda i: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            cspec(1, ci), cspec(1, ci),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, wd, ci), x.dtype),
            jax.ShapeDtypeStruct((kh, kw, ci, co), jnp.float32),
            jax.ShapeDtypeStruct((1, ci), jnp.float32),
            jax.ShapeDtypeStruct((1, ci), jnp.float32),
        ],
        interpret=env.get_bool("MXNET_PALLAS_INTERPRET"),
    )(x, wtaps, in_scale.reshape(1, ci), in_bias.reshape(1, ci),
      shift.reshape(1, co), y, gy,
      gs1.reshape(1, co), gs2.reshape(1, co))
    dw = jnp.transpose(dw_taps, (3, 2, 0, 1)).astype(w.dtype)
    if act_in:
        return gx, dw, gsc.reshape(ci), gbi.reshape(ci)
    return gx, dw, jnp.zeros_like(in_scale), jnp.zeros_like(in_bias)


def _pallas_unit_bwd_sharded(x, w, in_scale, in_bias, shift, y, gy, gs1,
                             gs2, *, mesh, axes, kernel, stride, pad,
                             act_in, want_stats):
    """Per-shard backward kernel over the batch axes; the batch-summed
    cotangents (dw, gscale, gbias) are psum'd global, mirroring how
    GSPMD reduces them for the XLA backward.  gx stays batch-sharded
    like x."""
    from jax.sharding import PartitionSpec as P

    from ..parallel._compat import shard_map_unchecked

    def per_shard(xs, ws, scs, bis, shs, ys, gys, g1s, g2s):
        gx, dw, gsc, gbi = _pallas_unit_bwd(
            xs, ws, scs, bis, shs, ys, gys, g1s, g2s, kernel=kernel,
            stride=stride, pad=pad, act_in=act_in, want_stats=want_stats)
        if axes:
            dw = lax.psum(dw, axes)
            gsc = lax.psum(gsc, axes)
            gbi = lax.psum(gbi, axes)
        return gx, dw, gsc, gbi

    bspec = P(axes if axes else None)
    rep = P()
    fn = shard_map_unchecked(
        per_shard, mesh=mesh.mesh,
        in_specs=(bspec, rep, rep, rep, rep, bspec, bspec, rep, rep),
        out_specs=(bspec, rep, rep, rep))
    return fn(x, w, in_scale, in_bias, shift, y, gy, gs1, gs2)


def _bwd_wanted() -> bool:
    return env.get_bool("MXNET_FUSED_CONVBN_BWD") \
        and _pallas_wanted()


def _bwd_shape_supported(x, w, kernel, stride, pad, act_in,
                         want_stats) -> bool:
    n, h, wd, ci = x.shape
    co = w.shape[0]
    ho, wo = _out_hw(h, wd, kernel, stride, pad)
    key = ("bwd", x.shape, str(x.dtype), w.shape, kernel, stride, pad,
           act_in, want_stats)
    return _probe_ok(
        key,
        functools.partial(_pallas_unit_bwd, kernel=kernel, stride=stride,
                          pad=pad, act_in=act_in, want_stats=want_stats),
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(w.shape, w.dtype),
         jax.ShapeDtypeStruct((ci,), jnp.float32),
         jax.ShapeDtypeStruct((ci,), jnp.float32),
         jax.ShapeDtypeStruct((co,), jnp.float32),
         jax.ShapeDtypeStruct((n, ho, wo, co), x.dtype),
         jax.ShapeDtypeStruct((n, ho, wo, co), x.dtype),
         jax.ShapeDtypeStruct((co,), jnp.float32),
         jax.ShapeDtypeStruct((co,), jnp.float32)])


# ---------------------------------------------------------------------------
# XLA fallback (identical semantics) + shared backward
# ---------------------------------------------------------------------------

def _apply_in_affine(x, in_scale, in_bias, act_in):
    if not act_in:
        return x
    u = (x.astype(jnp.float32) * in_scale.reshape(1, 1, 1, -1)
         + in_bias.reshape(1, 1, 1, -1))
    return jnp.maximum(u, 0.0).astype(x.dtype)


def _conv_nhwc(u, w_hwio, stride, pad):
    return lax.conv_general_dilated(
        u, w_hwio, window_strides=stride,
        padding=[(pad[0], pad[0]), (pad[1], pad[1])],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _xla_unit(x, w, in_scale, in_bias, shift, *, kernel, stride, pad,
              act_in, want_stats):
    u = _apply_in_affine(x, in_scale, in_bias, act_in)
    y = _conv_nhwc(u, jnp.transpose(w, (2, 3, 1, 0)), stride, pad)
    if want_stats:
        yf = y.astype(jnp.float32)
        s1 = jnp.sum(yf, axis=(0, 1, 2))
        d = yf - shift.reshape(1, 1, 1, -1)
        s2 = jnp.sum(d * d, axis=(0, 1, 2))
    else:
        co = y.shape[-1]
        s1 = jnp.zeros((co,), jnp.float32)
        s2 = jnp.zeros((co,), jnp.float32)
    return y, s1, s2


# Trace-time success does NOT imply the kernel will survive Mosaic
# lowering (that happens later, when the enclosing jitted program
# compiles, far outside any try/except here).  So each distinct
# (shapes, statics) configuration is probe-COMPILED standalone once —
# with fresh ShapeDtypeStructs, never tracers, so it is safe to do in
# the middle of an outer trace — and configurations Mosaic rejects are
# pinned to the XLA fallback.
_SHAPE_OK: dict = _mxsan.track({}, "ops.pallas_convbn._SHAPE_OK",
                               reads="unlocked-ok")
# fused units traced per path since import, forward and backward
# alike; every access holds _PROBE_LOCK.  A "fused" measurement whose
# units all ran XLA measured the restructured graph, not the kernel.
_UNIT_COUNTS = _mxsan.track({"pallas": 0, "xla": 0},
                            "ops.pallas_convbn._UNIT_COUNTS")


def unit_counts() -> dict:
    """{"pallas": n, "xla": m}: fused-unit dispatches traced on each path
    in this process (bench.py prints it beside the fused variant)."""
    with _PROBE_LOCK:
        return dict(_UNIT_COUNTS)


def _count(path: str) -> None:
    with _PROBE_LOCK:
        _UNIT_COUNTS[path] += 1


def _probe_ok(key, fn, arg_structs) -> bool:
    """Probe-compile one configuration once and cache the answer.  A
    refusal is reported once, at WARNING, with the compiler's message;
    the unit then takes the XLA path and `unit_counts` shows it."""
    # the interpret flag is part of the key: interpreter-mode ok=True
    # says nothing about Mosaic, so a later non-interpret call in the
    # same process must re-probe instead of reusing it (ADVICE round 5)
    interpret = env.get_bool("MXNET_PALLAS_INTERPRET")
    key = (key, interpret)
    ok = _SHAPE_OK.get(key)
    if ok is not None:
        return ok
    with _PROBE_LOCK:
        ok = _SHAPE_OK.get(key)
        if ok is None:
            if interpret:
                ok = True  # interpreter mode has no Mosaic stage
            else:
                try:
                    jax.jit(fn).lower(*arg_structs).compile()
                    ok = True
                except Exception as e:  # noqa: BLE001 — Mosaic/XLA refusal
                    logging.warning(
                        "fused Conv+BN: the compiler refused the Pallas "
                        "kernel for %s; this unit runs the XLA path. %s: %s",
                        key[0], type(e).__name__, e)
                    ok = False
            _SHAPE_OK[key] = ok
    return ok


def _shape_supported(x, w, kernel, stride, pad, act_in, want_stats) -> bool:
    key = (x.shape, str(x.dtype), w.shape, kernel, stride, pad, act_in,
           want_stats)
    return _probe_ok(
        key,
        functools.partial(_pallas_unit, kernel=kernel, stride=stride,
                          pad=pad, act_in=act_in, want_stats=want_stats),
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(w.shape, w.dtype),
         jax.ShapeDtypeStruct((x.shape[-1],), jnp.float32),
         jax.ShapeDtypeStruct((x.shape[-1],), jnp.float32),
         jax.ShapeDtypeStruct((w.shape[0],), jnp.float32)])


def _dispatch_plan(x, shape_probe):
    """ONE dispatch rule for fwd and bwd: returns
    ('single', None, None)   — no multi-device mesh active,
    ('sharded', mesh, axes)  — mesh active, batch divides the shards,
                               and the PER-SHARD shape probe-compiles,
    ('xla', None, None)      — mesh active but unsupported.
    Keeping this in one place means forward and backward can never
    silently disagree about when the Pallas path engages."""
    plan = _mesh_shard_plan()
    if plan is None:
        return ("single", None, None)
    mesh, axes = plan
    nshard = 1
    for a in axes:
        nshard *= mesh.axis_sizes[a]
    shard_shape = (x.shape[0] // nshard,) + tuple(x.shape[1:])
    if x.shape[0] % nshard == 0 and shard_shape[0] > 0 \
            and shape_probe(jax.ShapeDtypeStruct(shard_shape, x.dtype)):
        return ("sharded", mesh, axes)
    return ("xla", None, None)


def _mesh_shard_plan():
    """(mesh, batch_axes) for the active multi-device mesh, else None.

    GSPMD cannot partition a `pallas_call` on its own, so under a
    multi-device mesh the kernel is wrapped in an explicit shard_map
    over the batch-splitting axes (dp/fsdp) with the BN statistics
    psum'd across shards — keeping the fused path alive on exactly the
    configuration the north-star scaling metric measures (round-4
    verdict item #2).  Axes that don't split the batch (tp/pp/sp/ep)
    see the unit's operands replicated, which matches how the ResNet
    SPMD path lays them out."""
    try:
        from ..parallel.mesh import current_mesh

        m = current_mesh()
    except Exception:
        return None
    if m is None or m.mesh.size == 1:
        return None
    axes = tuple(a for a in ("dp", "fsdp")
                 if m.axis_sizes.get(a, 1) > 1)
    return m, axes


def _pallas_unit_sharded(x, w, in_scale, in_bias, shift, *, mesh, axes,
                         kernel, stride, pad, act_in, want_stats):
    """Per-shard pallas_call over the batch axes; stats psum'd global.

    Each device runs the single-chip kernel on its batch shard; s1/s2
    are per-shard partial sums, made global (and replicated) with a
    psum over the batch axes — semantically identical to the XLA
    fallback's jnp.sum over the GSPMD-sharded activation."""
    from jax.sharding import PartitionSpec as P

    from ..parallel._compat import shard_map_unchecked

    def per_shard(xs, ws, scs, bis, shs):
        y, s1, s2 = _pallas_unit(xs, ws, scs, bis, shs, kernel=kernel,
                                 stride=stride, pad=pad, act_in=act_in,
                                 want_stats=want_stats)
        if want_stats and axes:
            s1 = lax.psum(s1, axes)
            s2 = lax.psum(s2, axes)
        return y, s1, s2

    xspec = P(axes if axes else None)
    rep = P()
    fn = shard_map_unchecked(
        per_shard, mesh=mesh.mesh,
        in_specs=(xspec, rep, rep, rep, rep),
        out_specs=(xspec, rep, rep))
    return fn(x, w, in_scale, in_bias, shift)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _unit(x, w, in_scale, in_bias, shift, kernel, stride, pad, act_in,
          want_stats):
    if _pallas_wanted():
        probe = lambda xs: _shape_supported(xs, w, kernel, stride, pad,
                                            act_in, want_stats)
        kind, mesh, axes = _dispatch_plan(x, probe)
        if kind == "single" and probe(x):
            _count("pallas")
            return _pallas_unit(x, w, in_scale, in_bias, shift,
                                kernel=kernel, stride=stride,
                                pad=pad, act_in=act_in,
                                want_stats=want_stats)
        if kind == "sharded":
            _count("pallas")
            return _pallas_unit_sharded(
                x, w, in_scale, in_bias, shift, mesh=mesh,
                axes=axes, kernel=kernel, stride=stride, pad=pad,
                act_in=act_in, want_stats=want_stats)
    _count("xla")
    return _xla_unit(x, w, in_scale, in_bias, shift, kernel=kernel,
                     stride=stride, pad=pad, act_in=act_in,
                     want_stats=want_stats)


def _unit_fwd(x, w, in_scale, in_bias, shift, kernel, stride, pad, act_in,
              want_stats):
    out = _unit(x, w, in_scale, in_bias, shift, kernel, stride, pad,
                act_in, want_stats)
    # y rides along as a residual: it is the stored activation either way
    return out, (x, w, in_scale, in_bias, shift, out[0])


def _unit_bwd(kernel, stride, pad, act_in, want_stats, res, cots):
    x, w, in_scale, in_bias, shift, y = res
    gy, gs1, gs2 = cots
    if _bwd_wanted() and stride == (1, 1):
        probe = lambda xs: _bwd_shape_supported(xs, w, kernel, stride,
                                                pad, act_in, want_stats)
        kind, mesh, axes = _dispatch_plan(x, probe)
        if kind == "single" and probe(x):
            _count("pallas")
            gx, dw, gscale, gbias = _pallas_unit_bwd(
                x, w, in_scale, in_bias, shift, y, gy, gs1, gs2,
                kernel=kernel, stride=stride, pad=pad,
                act_in=act_in, want_stats=want_stats)
            return gx, dw, gscale, gbias, jnp.zeros_like(shift)
        if kind == "sharded":
            _count("pallas")
            gx, dw, gscale, gbias = _pallas_unit_bwd_sharded(
                x, w, in_scale, in_bias, shift, y, gy, gs1, gs2,
                mesh=mesh, axes=axes, kernel=kernel,
                stride=stride, pad=pad, act_in=act_in,
                want_stats=want_stats)
            return gx, dw, gscale, gbias, jnp.zeros_like(shift)
        _count("xla")
    if want_stats:
        # fold the BN-stat cotangents into dy: d(s1)/dy = 1,
        # d(s2)/dy = 2(y - shift); all C-sized broadcasts, XLA fuses
        # this into the transpose-conv input reads
        gy_tot = (gy.astype(jnp.float32)
                  + gs1.reshape(1, 1, 1, -1)
                  + 2.0 * (y.astype(jnp.float32)
                           - shift.reshape(1, 1, 1, -1))
                  * gs2.reshape(1, 1, 1, -1)).astype(gy.dtype)
    else:
        gy_tot = gy
    u = _apply_in_affine(x, in_scale, in_bias, act_in)
    w_hwio = jnp.transpose(w, (2, 3, 1, 0))
    # dgrad / wgrad as the EXACT transpose of the forward conv — no
    # forward recompute (linear_transpose only traces abstractly)
    du = jax.linear_transpose(
        lambda l: _conv_nhwc(l, w_hwio, stride, pad), u)(gy_tot)[0]
    dw_hwio = jax.linear_transpose(
        lambda r: _conv_nhwc(u, r, stride, pad), w_hwio)(gy_tot)[0]
    dw = jnp.transpose(dw_hwio, (3, 2, 0, 1)).astype(w.dtype)
    if act_in:
        uf = (x.astype(jnp.float32) * in_scale.reshape(1, 1, 1, -1)
              + in_bias.reshape(1, 1, 1, -1))
        mask = uf > 0.0
        gu = jnp.where(mask, du.astype(jnp.float32), 0.0)
        gx = (gu * in_scale.reshape(1, 1, 1, -1)).astype(x.dtype)
        gscale = jnp.sum(gu * x.astype(jnp.float32), axis=(0, 1, 2))
        gbias = jnp.sum(gu, axis=(0, 1, 2))
    else:
        gx = du.astype(x.dtype)
        gscale = jnp.zeros_like(in_scale)
        gbias = jnp.zeros_like(in_bias)
    # shift is a running statistic (stop-gradient, like _batch_norm's c)
    return gx, dw, gscale, gbias, jnp.zeros_like(shift)


_unit.defvjp(_unit_fwd, _unit_bwd)


@register_op("FusedConvUnit")
def fused_conv_unit(data, weight, in_scale=None, in_bias=None, shift=None,
                    kernel=(1, 1), stride=(1, 1), pad=(0, 0), act_in=False,
                    want_stats=True):
    """Fused (input-affine+ReLU) -> conv -> (BN stats) unit, NHWC.

    data (N,H,W,Ci) raw previous-layer conv output; weight (Co,Ci,kh,kw)
    in the layout-independent checkpoint layout; in_scale/in_bias the
    fp32 per-channel affine that normalizes `data` (None = identity);
    shift the fp32 variance shift for this layer's stats (the running
    mean; None = zeros).  Returns (y_raw, s1, s2) with s1/s2 fp32
    per-channel sum / shifted sum-of-squares of y_raw.
    """
    kernel = tuple(int(k) for k in kernel)
    stride = tuple(int(s) for s in stride)
    pad = tuple(int(p) for p in pad)
    ci = data.shape[-1]
    co = weight.shape[0]
    if in_scale is None:
        in_scale = jnp.ones((ci,), jnp.float32)
    if in_bias is None:
        in_bias = jnp.zeros((ci,), jnp.float32)
    if shift is None:
        shift = jnp.zeros((co,), jnp.float32)
    return _unit(data, weight, in_scale.astype(jnp.float32),
                 in_bias.astype(jnp.float32), shift.astype(jnp.float32),
                 kernel, stride, pad, bool(act_in), bool(want_stats))
