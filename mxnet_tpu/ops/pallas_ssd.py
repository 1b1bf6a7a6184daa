"""Mamba-2's chunked scan (`ops.ssm`) as two Pallas TPU kernels, every
per-chunk intermediate in VMEM.

What HBM sees: x and y as (B, S, H*P), B and C as (B, S, G*N) (free
reshapes of the op's arguments: no head is split off, no group repeated),
dt and the chunk-wise cumulative log-decay `cs` as small float32 (B, S, H)
arrays in two layouts, and between the two kernels the float32 state each
chunk enters with.  The (l x l) decay tile, the decayed scores, the
decayed values and the chunk states live and die in VMEM.

Grid (batch, group, chunk, head block of the group).  The chunk axis is
sequential: the forward carries the state from chunk to chunk in a
float32 scratch, the backward walks the chunks in reverse and carries the
state's cotangent the same way.  The head blocks of one group share one
load of the group's B and C, their score tile C B^T (computed once a
chunk) and, in the backward, the accumulators of dB and dC, which are
sums over the group's heads.  Heads of 64 lie two to a 128-lane block
(`_Tiles`), as `ops.pallas_attention`'s do; a step's lane blocks are a
loop whose body is traced once.

Per head and chunk of l positions, with xd = dt x, decay[t, s] =
exp(cs_t - cs_s) for t >= s, S the entering state, kept as (N x P) so
that no product transposes an operand a head (B^T and C^T are made once
a step for the group):

    y     = ((C B^T) * decay) xd + exp(cs) * (C S) + D x
    S_out = exp(cs_l) S + B^T (xd * exp(cs_l - cs))

The backward recomputes the decay tiles and never forms the cotangent of
the (l x l) decay: its row sums are sum_p dy * (y - D x) and its column
sums sum_p xd * dxd, two reductions over tiles the kernel holds anyway.

Precision is `ops.ssm`'s: dt, cs, every exponential, the carried state and
its cotangent in float32; the products' operands in the inputs' dtype,
accumulated in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel_route

_NT = (((1,), (1,)), ((), ()))      # A @ B^T
_TN = (((0,), (0,)), ((), ()))      # A^T @ B


def supports(heads, head_dim, groups, state, seq, chunk):
    """The shapes the kernels take: chunks of 128 (one (8, 128)-tiled
    float32 decay tile of 16 registers a head; Mosaic refuses the loop's
    loads at 256), a state of whole lane blocks, and each group's heads
    filling whole 128-lane blocks of x (heads of 64 an even number to a
    group, or heads of whole lane blocks)."""
    if heads % groups or chunk != 128 or seq % chunk or state % 128:
        return False
    if head_dim == 64:
        return (heads // groups) % 2 == 0
    return head_dim % 128 == 0


def head_block(heads_per_group, per_lane_block):
    """Heads a grid step works on: the whole group up to 8 lane blocks
    (their columns of dt and cs along every lane are 128 KiB a head of
    scratch).  A grid step costs ~0.45 us, and a group's B, C and score
    tile are loaded or built once for all its blocks.  Swept on the v5e
    in PR 28 at the published widths (B 1, S 8192, H 128, P 64, G 8,
    N 128, bfloat16) with the first version of the kernels, forward +
    backward ms a layer: 2 heads a step 3.75 + 6.25, 4: 2.72 + 4.87,
    8: 2.28 + 4.26, 16 (the group): 2.05 + 3.70; these: 1.71 + 3.17."""
    most = 8 * per_lane_block
    return max(hb for hb in range(per_lane_block,
                                  min(most, heads_per_group) + 1,
                                  per_lane_block)
               if heads_per_group % hb == 0)


def _to_blocks(v, hb):
    """(B, S, H) -> columns (B, H/hb, S, hb) and rows (B, H/hb, hb, S):
    a head's values down the sublanes and along the lanes."""
    bsz, s, h = v.shape
    rows = v.reshape(bsz, s, h // hb, hb).transpose(0, 2, 3, 1)
    return rows.transpose(0, 1, 3, 2), rows


def _from_columns(v):
    """(B, H/hb, S, hb) -> (B, S, H)."""
    bsz, nb, s, hb = v.shape
    return v.transpose(0, 2, 1, 3).reshape(bsz, s, nb * hb)


class _Plan:
    """Grid and BlockSpecs both kernels share.  x (B, S, H*P), dt
    (B, S, H), b (B, S, G*N).  Heads of 64 lie `per` = 2 to a lane block
    of `w` = 128 lanes, wider heads one to a block of their own width."""

    def __init__(self, x, dt, b, groups, chunk, hb, reverse):
        from jax.experimental import pallas as pl

        self.pl = pl
        bsz, s, hp = x.shape
        h = dt.shape[2]
        self.p, self.n_state = hp // h, b.shape[2] // groups
        self.per = per = max(1, 128 // self.p)
        self.w = per * self.p
        r = h // groups
        self.hb = hb = hb or head_block(r, per)
        assert r % hb == 0 and hb % per == 0 and s % chunk == 0, (
            h, groups, hb, s, chunk)
        self.chunk, self.nc = chunk, s // chunk
        self.per_group = r // hb              # head blocks a group
        self.blocks = hb // per               # lane blocks a head block
        self.group_blocks = r // per          # lane blocks a group
        self.grid = (bsz, groups, self.nc, self.per_group)
        nc, per_group = self.nc, self.per_group
        at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
        width, n, blocks = hb * self.p, self.n_state, self.blocks
        self.spec = {
            # (l, hb*P) of x / y / dy / dx
            "x": pl.BlockSpec((1, chunk, width), lambda i, g, c, k:
                              (i, at(c), g * per_group + k)),
            # (l, N) of the group's B / C / dB / dC
            "bc": pl.BlockSpec((1, chunk, n), lambda i, g, c, k:
                               (i, at(c), g)),
            # (l, hb) columns and (hb, l) rows of dt / cs / their cotangents
            "col": pl.BlockSpec((1, 1, chunk, hb), lambda i, g, c, k:
                                (i, g * per_group + k, at(c), 0)),
            "row": pl.BlockSpec((1, 1, hb, chunk), lambda i, g, c, k:
                                (i, g * per_group + k, 0, at(c))),
            # (1, hb*P) of D, each head's value on its P lanes
            "d": pl.BlockSpec((1, width), lambda i, g, c, k:
                              (0, g * per_group + k)),
            # a chunk's part of dD, (1, hb*P)
            "dd": pl.BlockSpec((1, 1, 1, width), lambda i, g, c, k:
                               (i, at(c), 0, g * per_group + k)),
            # (blocks, N, w) of the states entering a chunk
            "state": pl.BlockSpec(
                (1, 1, blocks, n, self.w), lambda i, g, c, k:
                (i, at(c), g * per_group + k, 0, 0)),
        }

    def column_scratch(self, pltpu):
        """Each head's (l, 1) column of dt or cs along every lane."""
        return pltpu.VMEM((self.hb, self.chunk, max(self.chunk, self.w)),
                          jnp.float32)

    def compiler_params(self, pltpu, itemsize, tiles):
        """Scoped VMEM from what a step holds: its (l, hb*P) operand and
        result blocks double buffered, the group's carried states and
        the step's states block, the heads' columns, and some `tiles`
        float32 (l, max(l, w, N)) temporaries of one lane block."""
        l, w, n = self.chunk, self.w, self.n_state
        blocks = 2 * 4 * l * self.hb * self.p * itemsize
        states = (self.group_blocks + 2 * self.blocks) * w * n * 4
        columns = 2 * self.hb * l * max(l, w) * 4
        temps = tiles * l * max(l, w, n) * 4
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=min(100 << 20, 2 * (
                blocks + states + columns + temps) + (8 << 20)))


class _Tiles:
    """What a kernel step builds once for all its lane blocks, and the
    tiles of one lane block j (a traced index: the blocks are a loop)
    made from them.  Head i of the block owns lanes [i*P, (i+1)*P)."""

    def __init__(self, plan, dtc_ref, csc_ref, csr_ref, dt_cols, cs_cols):
        self.plan, self.csr_ref = plan, csr_ref
        self.dt_cols, self.cs_cols = dt_cols, cs_cols
        l, w, p = plan.chunk, plan.w, plan.p
        iota = jax.lax.broadcasted_iota
        self.lower = iota(jnp.int32, (l, l), 0) >= iota(jnp.int32, (l, l), 1)
        lane, lane1 = iota(jnp.int32, (l, w), 1), iota(jnp.int32, (1, w), 1)
        own = lambda at: [(at >= i * p) & (at < (i + 1) * p)
                          for i in range(plan.per)]
        self.own, self.own1 = own(lane), own(lane1)
        # one lane broadcast a head and array, shared by the head's decay
        # tile and the packed tiles of its lane block
        for h in range(plan.hb):
            for ref, cols in ((dtc_ref, dt_cols), (csc_ref, cs_cols)):
                cols[h] = jnp.broadcast_to(ref[0, 0, :, h:h + 1],
                                           cols.shape[1:])

    def _spread(self, values, masks):
        out = values[-1]
        for value, mask in zip(values[-2::-1], masks[-2::-1]):
            out = jnp.where(mask, value, out)
        return out

    def packed(self, cols, j):
        """(l, w): each head of block j's column over its P lanes."""
        per, w = self.plan.per, self.plan.w
        return self._spread([cols[j * per + i][:, :w] for i in range(per)],
                            self.own)

    def end(self, j):
        """(1, w): cs at the chunk's end, each head's over its lanes (the
        last row of its column along every lane)."""
        per, l, w = self.plan.per, self.plan.chunk, self.plan.w
        return self._spread(
            [self.cs_cols[j * per + i][l - 1:l, :w] for i in range(per)],
            self.own1)

    def decay(self, j, i):
        """exp(cs_t - cs_s) for t >= s, else 0: (l, l) float32."""
        h, l, pl = j * self.plan.per + i, self.plan.chunk, self.plan.pl
        return jnp.exp(jnp.where(
            self.lower,
            self.cs_cols[h][:, :l] - self.csr_ref[0, 0, pl.ds(h, 1), :],
            -jnp.inf))

    def only(self, i, x):
        """The (l, w) tile `x` with the other heads' lanes zeroed."""
        if self.plan.per == 1:
            return x
        return jnp.where(self.own[i], x, 0.0)

    def sums(self, x):
        """[(l, 1) or (1, 1): sum of x over head i's lanes]."""
        if self.plan.per == 1:
            return [jnp.sum(x, axis=1, keepdims=True)]
        masks = self.own if x.shape[0] > 1 else self.own1
        return [jnp.sum(jnp.where(m, x, 0.0), axis=1, keepdims=True)
                for m in masks]


@kernel_route.shared_kernel("groups", "chunk", "hb", "keep_states")
def ssd_forward(x, dt, cs, b, c, d, groups, chunk, hb=None,
                keep_states=False, interpret=False):
    """x (B, S, H*P); dt, cs (B, S, H) float32, cs the cumulative sum of
    dt * a inside each chunk; b, c (B, S, G*N); d (H,) float32.  Returns
    y (B, S, H*P) in x's dtype and, with `keep_states`, the float32 state
    each chunk enters with, (B, S/chunk, H/per, N, w), a lane block's
    heads side by side along the lanes: the backward's residual.

    A step's lane blocks are a loop, its body traced once: unrolled, the
    8 blocks of a published group ran the backward 5% faster (3.16
    against 3.32 ms a layer, the forward 1.93 either way) and cost 3.5 s
    of tracing and lowering at every start of the process, a third of
    `setup_s`'s bound (PERF.md section 6, PR 28)."""
    from jax.experimental.pallas import tpu as pltpu

    plan = _Plan(x, dt, b, groups, chunk, hb, reverse=False)
    pl, spec, w = plan.pl, plan.spec, plan.w
    bsz, s, hp = x.shape
    dtype, f32 = x.dtype, jnp.float32

    def kernel(x_ref, dtc_ref, csc_ref, csr_ref, b_ref, c_ref, d_ref,
               y_ref, *rest):
        states_ref = rest[0] if keep_states else None
        state, scores, bt, dt_cols, cs_cols = rest[-5:]
        ci, k = pl.program_id(2), pl.program_id(3)
        cm = c_ref[0]

        @pl.when(k == 0)
        def _():
            # B^T once for the group's heads: every product below is then
            # a plain (rows, k) @ (k, lanes), nothing transposed a head
            bt[...] = b_ref[0].T
            scores[...] = jnp.dot(cm, bt[...], preferred_element_type=f32)

        tiles = _Tiles(plan, dtc_ref, csc_ref, csr_ref, dt_cols, cs_cols)

        def block(j, carry):
            at = k * plan.blocks + j             # lane block of the group
            lanes = pl.ds(pl.multiple_of(j * w, w), w)

            @pl.when(ci == 0)
            def _():
                state[at] = jnp.zeros_like(state[at])

            entering = state[at]                           # (N, w) f32
            if keep_states:
                states_ref[0, 0, j] = entering
            x32 = x_ref[0, :, lanes].astype(f32)
            cs2 = tiles.packed(cs_cols, j)
            xd32 = x32 * tiles.packed(dt_cols, j)
            y = jnp.dot(cm, entering.astype(dtype),
                        preferred_element_type=f32) * jnp.exp(cs2)
            for i in range(plan.per):
                m = (scores[...] * tiles.decay(j, i)).astype(dtype)
                y = y + jnp.dot(m, tiles.only(i, xd32).astype(dtype),
                                preferred_element_type=f32)
            y_ref[0, :, lanes] = (y + d_ref[:, lanes] * x32).astype(dtype)
            # the state the chunk leaves behind
            end = tiles.end(j)
            left = jnp.dot(
                bt[...], (xd32.astype(dtype).astype(f32)
                          * jnp.exp(end - cs2)).astype(dtype),
                preferred_element_type=f32)                # (N, w)
            state[at] = jnp.exp(end) * entering + left
            return carry

        jax.lax.fori_loop(0, plan.blocks, block, 0)

    n = plan.n_state
    out_specs = [spec["x"]]
    out_shape = [jax.ShapeDtypeStruct(x.shape, dtype)]
    if keep_states:
        out_specs.append(spec["state"])
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, plan.nc, hp // w, n, w), f32))
    dtc, _ = _to_blocks(dt, plan.hb)
    csc, csr = _to_blocks(cs, plan.hb)
    out = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=[spec["x"], spec["col"], spec["col"], spec["row"],
                  spec["bc"], spec["bc"], spec["d"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((plan.group_blocks, n, w), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((n, chunk), dtype),
                        plan.column_scratch(pltpu),
                        plan.column_scratch(pltpu)],
        compiler_params=plan.compiler_params(pltpu, dtype.itemsize, 8),
        interpret=interpret,
        name="mx_ssd_scan_fwd",
    )(x, dtc, csc, csr, b, c, jnp.repeat(d.astype(f32), plan.p)[None, :])
    return tuple(out) if keep_states else out[0]


@kernel_route.shared_kernel("groups", "chunk", "hb")
def ssd_backward(x, dt, cs, b, c, d, states, dy, groups, chunk, hb=None,
                 interpret=False):
    """The forward's operands, its `states` and y's cotangent dy (B, S,
    H*P).  Returns (dx, ddt, dcs, db, dc, dd): dx, db, dc in their
    operands' dtype, ddt and dcs (B, S, H) and dd (H,) float32."""
    from jax.experimental.pallas import tpu as pltpu

    plan = _Plan(x, dt, b, groups, chunk, hb, reverse=True)
    pl, spec = plan.pl, plan.spec
    bsz, s, hp = x.shape
    dtype, f32 = x.dtype, jnp.float32
    l, w, hb = chunk, plan.w, plan.hb

    def kernel(x_ref, dy_ref, dtc_ref, csc_ref, csr_ref, b_ref, c_ref, d_ref,
               states_ref, dx_ref, ddt_ref, dcs_ref, db_ref, dc_ref, dd_ref,
               dstate, scores, dscores, db_acc, dc_acc, ct, dt_cols,
               cs_cols):
        ci, k = pl.program_id(2), pl.program_id(3)      # ci counts back
        bm, cm = b_ref[0], c_ref[0]

        @pl.when(k == 0)
        def _():
            ct[...] = cm.T
            scores[...] = jax.lax.dot_general(
                cm, bm, _NT, preferred_element_type=f32)
            dscores[...] = jnp.zeros_like(dscores)
            db_acc[...] = jnp.zeros_like(db_acc)
            dc_acc[...] = jnp.zeros_like(dc_acc)

        tiles = _Tiles(plan, dtc_ref, csc_ref, csr_ref, dt_cols, cs_cols)
        head = jax.lax.broadcasted_iota(jnp.int32, (l, hb), 1)
        bottom = jax.lax.broadcasted_iota(jnp.int32, (l, 1), 0) == l - 1

        def block(j, carry):
            at = k * plan.blocks + j
            lanes = pl.ds(pl.multiple_of(j * w, w), w)

            @pl.when(ci == 0)
            def _():
                dstate[at] = jnp.zeros_like(dstate[at])

            leaving = dstate[at]          # cotangent of the state left
            entering = states_ref[0, 0, j]
            x32 = x_ref[0, :, lanes].astype(f32)
            dy32 = dy_ref[0, :, lanes].astype(f32)
            cs2, dt2 = tiles.packed(cs_cols, j), tiles.packed(dt_cols, j)
            grow = jnp.exp(cs2)
            end = tiles.end(j)
            to_end = jnp.exp(end - cs2)
            xd_wide = x32 * dt2
            xd = xd_wide.astype(dtype)
            xd32 = xd.astype(f32)
            sb, dsb = entering.astype(dtype), leaving.astype(dtype)
            # y's part through the entering state, and the decayed
            # values' cotangent through the state left
            y = jnp.dot(cm, sb, preferred_element_type=f32) * grow
            via_state = jnp.dot(bm, dsb, preferred_element_type=f32) * to_end
            dxd = via_state
            dye = (dy32 * grow).astype(dtype)
            dc_acc[...] += jax.lax.dot_general(
                dye, sb, _NT, preferred_element_type=f32)
            db_acc[...] += jax.lax.dot_general(
                (xd32 * to_end).astype(dtype), dsb, _NT,
                preferred_element_type=f32)
            for i in range(plan.per):
                dec = tiles.decay(j, i)
                m = (scores[...] * dec).astype(dtype)
                dy_i = tiles.only(i, dy32).astype(dtype)
                y = y + jnp.dot(m, tiles.only(i, xd_wide).astype(dtype),
                                preferred_element_type=f32)
                dxd = dxd + jax.lax.dot_general(
                    m, dy_i, _TN, preferred_element_type=f32)
                dscores[...] += jax.lax.dot_general(
                    dy_i, xd, _NT, preferred_element_type=f32) * dec
            dx_ref[0, :, lanes] = (dxd * dt2 + d_ref[:, lanes] * dy32
                                   ).astype(dtype)
            dd_ref[0, 0, :, lanes] = jnp.sum(dy32 * x32, axis=0,
                                             keepdims=True)
            # cs: rows of the decay tile and exp(cs) in y, less its
            # columns and exp(cs_l - cs) in the state left; the chunk's
            # last cs takes what the state left and the carry give it
            grown = jnp.exp(end)
            at_end = tiles.sums(
                jnp.sum(xd32 * via_state, axis=0, keepdims=True)
                + grown * jnp.sum(leaving * entering, axis=0, keepdims=True))
            for i, (dcs, ddt) in enumerate(zip(
                    tiles.sums(dy32 * y - xd32 * dxd),
                    tiles.sums(dxd * x32))):
                here = head == j * plan.per + i
                # every column is written once a step: what the block
                # held before is never read
                dcs_ref[0, 0] = jnp.where(
                    here, dcs + jnp.where(bottom, at_end[i], 0.0),
                    dcs_ref[0, 0])
                ddt_ref[0, 0] = jnp.where(here, ddt, ddt_ref[0, 0])
            dstate[at] = grown * leaving + jnp.dot(
                ct[...], dye, preferred_element_type=f32)
            return carry

        jax.lax.fori_loop(0, plan.blocks, block, 0)

        @pl.when(k == plan.per_group - 1)
        def _():
            dg = dscores[...].astype(dtype)
            dc_ref[0] = (dc_acc[...] + jnp.dot(
                dg, bm, preferred_element_type=f32)).astype(dc_ref.dtype)
            db_ref[0] = (db_acc[...] + jax.lax.dot_general(
                dg, cm, _TN, preferred_element_type=f32)
            ).astype(db_ref.dtype)

    n = plan.n_state
    dtc, _ = _to_blocks(dt, hb)
    csc, csr = _to_blocks(cs, hb)
    cols = jax.ShapeDtypeStruct(dtc.shape, f32)
    dx, ddt, dcs, db, dc, dd = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=[spec["x"], spec["x"], spec["col"], spec["col"],
                  spec["row"], spec["bc"], spec["bc"], spec["d"],
                  spec["state"]],
        out_specs=[spec["x"], spec["col"], spec["col"], spec["bc"],
                   spec["bc"], spec["dd"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, dtype), cols, cols,
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct((bsz, plan.nc, 1, hp), f32)],
        scratch_shapes=[pltpu.VMEM((plan.group_blocks, n, w), f32),
                        pltpu.VMEM((l, l), f32), pltpu.VMEM((l, l), f32),
                        pltpu.VMEM((l, n), f32), pltpu.VMEM((l, n), f32),
                        pltpu.VMEM((n, l), dtype),
                        plan.column_scratch(pltpu),
                        plan.column_scratch(pltpu)],
        compiler_params=plan.compiler_params(pltpu, dtype.itemsize, 14),
        interpret=interpret,
        name="mx_ssd_scan_bwd",
    )(x, dy, dtc, csc, csr, b, c,
      jnp.repeat(d.astype(f32), plan.p)[None, :], states)
    dd = dd.sum(axis=(0, 1, 2)).reshape(-1, plan.p).sum(axis=1)
    return dx, _from_columns(ddt), _from_columns(dcs), db, dc, dd


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_scan_kernels(x, dt, cs, b, c, d, groups, chunk):
    """y (B, S, H*P) without a residual; under differentiation the
    forward also writes the states and the backward kernel reads them."""
    return ssd_forward(x, dt, cs, b, c, d, groups=groups, chunk=chunk)


def _scan_fwd(x, dt, cs, b, c, d, groups, chunk):
    y, states = ssd_forward(x, dt, cs, b, c, d, groups=groups, chunk=chunk,
                            keep_states=True)
    return y, (x, dt, cs, b, c, d, states)


def _scan_bwd(groups, chunk, res, dy):
    *grads, dd = ssd_backward(*res, dy, groups=groups, chunk=chunk)
    return (*grads, dd.astype(res[5].dtype))


ssd_scan_kernels.defvjp(_scan_fwd, _scan_bwd)
