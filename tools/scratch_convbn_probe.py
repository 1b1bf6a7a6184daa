"""On-chip Mosaic probe for fused-conv kernel candidates (dev scratch).

Iterates kernel formulations against the real TPU: the round-5 finding
is that Mosaic rejects the im2col jnp.concatenate inside the kernel
(tpu_compile_helper exit 1), so this probes the tap-accumulation form.
It takes the chip: run it alone (one process per chip).
"""
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def candidate_tap(x, w_taps, in_scale, in_bias, shift, *, kernel, stride,
                  pad, act_in, want_stats, nb):
    """Tap-accumulation fused unit: pad OUTSIDE the kernel; inside,
    y = sum_{ky,kx} u[:, ky::sh, kx::sw, :] @ w[ky,kx] (one MXU matmul
    per tap, no concat, no pad)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, wd, ci = x.shape
    kh, kw = kernel
    sh_, sw_ = stride
    co = w_taps.shape[-1]
    ho = (h + 2 * pad[0] - kh) // sh_ + 1
    wo = (wd + 2 * pad[1] - kw) // sw_ + 1
    hp, wp = h + 2 * pad[0], wd + 2 * pad[1]
    out_dtype = x.dtype

    def kern(x_ref, w_ref, sc_ref, bi_ref, sh_ref, y_ref, s1_ref, s2_ref):
        xb = x_ref[...]
        if act_in:
            u = xb.astype(jnp.float32) * sc_ref[...] + bi_ref[...]
            u = jnp.maximum(u, 0.0).astype(xb.dtype)
        else:
            u = xb
        # pad AFTER the input affine (padded positions must be exact
        # zeros, not relu(bias)); in-kernel pad, Mosaic permitting
        # pad for the window, plus stride-1 extra rows/cols so every
        # tap's contiguous slice of length s*ho / s*wo stays in bounds
        if pad != (0, 0) or sh_ > 1 or sw_ > 1:
            u = jnp.pad(u, ((0, 0), (pad[0], pad[0] + sh_ - 1),
                            (pad[1], pad[1] + sw_ - 1), (0, 0)))
        acc = jnp.zeros((nb * ho * wo, co), jnp.float32)
        for ky in range(kh):
            for kx in range(kw):
                if sh_ == 1 and sw_ == 1:
                    sl = u[:, ky:ky + ho, kx:kx + wo, :]
                else:
                    # strided slicing lowers to an unsupported gather in
                    # Mosaic; contiguous slice + reshape + unit-index
                    # (a slice of a size-s axis) extracts the same
                    # polyphase plane
                    rows = u[:, ky:ky + sh_ * ho, :, :]
                    rows = rows.reshape(nb, ho, sh_, rows.shape[2], ci)[
                        :, :, 0]
                    cols = rows[:, :, kx:kx + sw_ * wo, :]
                    sl = cols.reshape(nb, ho, wo, sw_, ci)[:, :, :, 0]
                acc = acc + jnp.dot(
                    sl.reshape(nb * ho * wo, ci),
                    w_ref[ky, kx],
                    preferred_element_type=jnp.float32)
        yc = acc.astype(out_dtype)
        y_ref[...] = yc.reshape(nb, ho, wo, co)

        @pl.when(pl.program_id(0) == 0)
        def _():
            s1_ref[...] = jnp.zeros_like(s1_ref)
            s2_ref[...] = jnp.zeros_like(s2_ref)

        if want_stats:
            yf = yc.astype(jnp.float32)
            d = yf - sh_ref[...]
            s1_ref[...] += jnp.sum(yf, axis=0, keepdims=True)
            s2_ref[...] += jnp.sum(d * d, axis=0, keepdims=True)

    grid = (n // nb,)
    return pl.pallas_call(
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
    )(x, w_taps, in_scale.reshape(1, ci), in_bias.reshape(1, ci),
      shift.reshape(1, co))


def _time(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # µs


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--time", action="store_true",
                    help="also time fused kernel vs composed XLA on the "
                         "REAL ResNet-50 BS-256 layer shapes")
    args = ap.parse_args()

    print("backend:", jax.default_backend(), jax.devices())
    rng = np.random.RandomState(0)
    cases = [
        # (shape, co, kernel, stride, pad) — the ResNet-50 hot set
        ((4, 16, 16, 128), 128, (3, 3), (1, 1), (1, 1)),
        ((4, 16, 16, 128), 256, (1, 1), (1, 1), (0, 0)),
        ((4, 16, 16, 256), 128, (1, 1), (1, 1), (0, 0)),
        ((4, 16, 16, 128), 128, (3, 3), (2, 2), (1, 1)),
    ]
    if args.time:
        return time_layers(rng)
    for shape, co, kernel, stride, pad in cases:
        n, h, wd, ci = shape
        x = jnp.asarray(rng.randn(*shape).astype("float32") * 0.5,
                        jnp.bfloat16)
        w = jnp.asarray(
            rng.randn(kernel[0], kernel[1], ci, co).astype("float32")
            * 0.05, jnp.bfloat16)
        sc = jnp.asarray(rng.rand(ci).astype("float32") + 0.5)
        bi = jnp.asarray(rng.randn(ci).astype("float32") * 0.1)
        sh = jnp.asarray(rng.randn(co).astype("float32") * 0.1)
        fn = functools.partial(candidate_tap, kernel=kernel, stride=stride,
                               pad=pad, act_in=True, want_stats=True, nb=2)
        t0 = time.time()
        try:
            y, s1, s2 = jax.jit(fn)(x, w, sc, bi, sh)
            jax.block_until_ready(y)
            # oracle
            u = jnp.maximum(x.astype(jnp.float32) * sc + bi, 0.0) \
                .astype(x.dtype)
            yr = jax.lax.conv_general_dilated(
                u, w, stride, [(pad[0], pad[0]), (pad[1], pad[1])],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            err = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                        - yr.astype(jnp.float32))))
            print(f"OK   {shape} co={co} k={kernel} s={stride} "
                  f"compile+run {time.time()-t0:.1f}s maxerr={err:.4f}")
        except Exception as e:
            print(f"FAIL {shape} co={co} k={kernel} s={stride}: "
                  f"{type(e).__name__}: {str(e).splitlines()[0][:160]}")
    return 0


def time_layers(rng):
    """Per-shape fused-Pallas vs composed-XLA forward timing on the
    BS-256 ResNet-50 bottleneck shapes (the bench workload).  Uses the
    PRODUCTION kernel via ops.pallas_convbn so probe results transfer."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_convbn as pcb

    # (n, h, w, ci) -> co, kernel, stride, pad : stage-representative
    layers = [
        ((256, 56, 56, 64), 64, (3, 3), (1, 1), (1, 1)),
        ((256, 56, 56, 64), 256, (1, 1), (1, 1), (0, 0)),
        ((256, 56, 56, 256), 64, (1, 1), (1, 1), (0, 0)),
        ((256, 28, 28, 128), 128, (3, 3), (1, 1), (1, 1)),
        ((256, 28, 28, 128), 512, (1, 1), (1, 1), (0, 0)),
        ((256, 14, 14, 256), 256, (3, 3), (1, 1), (1, 1)),
        ((256, 14, 14, 1024), 256, (1, 1), (1, 1), (0, 0)),
        ((256, 7, 7, 512), 512, (3, 3), (1, 1), (1, 1)),
        ((256, 7, 7, 512), 2048, (1, 1), (1, 1), (0, 0)),
    ]
    for shape, co, kernel, stride, pad in layers:
        n, h, wd, ci = shape
        x = jnp.asarray(rng.randn(*shape).astype("float32") * 0.5,
                        jnp.bfloat16)
        w = jnp.asarray(rng.randn(co, ci, *kernel).astype("float32")
                        * 0.05, jnp.bfloat16)
        sc = jnp.asarray(rng.rand(ci).astype("float32") + 0.5)
        bi = jnp.asarray(rng.randn(ci).astype("float32") * 0.1)
        sh = jnp.asarray(rng.randn(co).astype("float32") * 0.1)
        kw = dict(kernel=kernel, stride=stride, pad=pad, act_in=True,
                  want_stats=True)
        try:
            pal = jax.jit(functools.partial(pcb._pallas_unit, **kw))
            t_pal = _time(pal, x, w, sc, bi, sh)
        except Exception as e:
            t_pal = None
            err = str(e).splitlines()[0][:100]
        xla = jax.jit(functools.partial(pcb._xla_unit, **kw))
        t_xla = _time(xla, x, w, sc, bi, sh)
        if t_pal is None:
            print(f"{shape} co={co} k={kernel}: pallas FAIL ({err}); "
                  f"xla {t_xla:.0f}us")
        else:
            print(f"{shape} co={co} k={kernel} s={stride}: "
                  f"pallas {t_pal:.0f}us  xla {t_xla:.0f}us  "
                  f"ratio {t_pal / t_xla:.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
