"""Mixture of experts: a share of the experts, told which share it is.

An expert layer here HOLDS `n_local` of the `E` experts the router scores
(those with ids `first_expert .. first_expert + n_local - 1`), routes every
token over all `E`, and computes the part of the result its own experts
give.  That is what expert parallelism asks of a layer: over the 'ep' mesh
axis (`moe_apply`) each device holds one share and the parts are summed;
on one chip of a larger deployment the layer runs alone, and the parts of
the absent experts are simply not there.  With `n_local == E` it is the
whole layer.

No token is ever dropped: a token lands on at most `min(top_k, n_local)`
held experts, so `rows = T * min(top_k, n_local)` (rounded up to the row
tile) holds every assignment under any imbalance; rows beyond the
assignments made carry weight 0 and an out-of-range token.  The rows are
laid out expert by expert (`group_sizes`), which is what a grouped matrix
product wants, and inside an expert by ascending token: one sort of the
T * top_k assignments by (held expert, token) makes the plan and a second
one its gradient, and nothing in `route` is a scatter (on the TPU a
scatter runs update by update: the scatter-built plan cost 300 ms of a
1,445 ms step at 16,384 tokens a layer, PERF.md PR 32).

`rows` is a bound and not the work, for every stage.  `experts` goes over
the plan in chunks of `ROW_CHUNK` rows under a trip count it reads from
`group_sizes` on the device (`plan_chunks`), so a layer that holds 8 of
512 experts pays for the ~4% of its rows that are used and a layer under
the worst imbalance pays for all of them.  A model that expects more than
a chunk of assignments says so (`expected_rows`), and the chunk grows to
hold twice that in one trip (`row_chunk`): then the chunk is a bound too,
about half of it empty, and inside it every stage follows the assignments
again.  The grouped products skip the empty tail themselves; the gather,
the activation and the gradients of gather, activation, mask and scale go
over the chunk in blocks of `row_block(chunk)` rows under a second count
read from `group_sizes` (`plan_blocks`) and stop after the last block that
holds an assignment; nothing they make is zero-filled first.

Nothing in `experts` is a scatter either.  The sum of a chunk's rows into
their tokens, which the combine is and in the gradient the transpose of
the gather of `u`, is computed by the TOKEN reading its rows
(`_token_sums`): one sort puts the chunk's rows in token order, a product
of one-hot digits counts each token's rows, which says where its run
begins, the rows are gathered in that order in the same blocks under the
same count, a token's at most min(top_k, n_local)
neighbouring rows are added in float32 by shifted, masked adds, and one
gather of T head rows makes the chunk's (T, K) part.  Its cost follows
the rows used and T; XLA's scatter-add cost ~100 ns a row over the WHOLE
chunk, half of it empty, where a gathered row costs 12-21 ns (PERF.md,
PRs 43-44).

The two loops, forward and backward, are each ONE traced function for a
set of operand shapes and dtypes, `form`, chunk and block: a model's
layers, each a recomputed segment of its own, bind the trace the first of
them made (`route_counts()["expert_stage_traces"]` counts the real
traces), and XLA inlines the calls.

    route(x, w_router, bias, ...)   -> RoutePlan for the held experts
    experts(u, plan, w1, w2, form, expected_rows)
                                    -> sum over held experts e of
                                        weight_e * (act_e(u) W2_e)
    moe_apply(...)                  -> the same over the 'ep' axis

An expert has one of two `FORMS`, which the model states: `relu2`,
act(u) = relu(u W1)^2 with w1 (n, K, N), or `silu_gated`, act(u) =
silu(u G) * (u U) with w1 (n, K, 2N) holding G and U side by side, so
that either form is two grouped products a chunk.

Routing is the sigmoid-score form of DeepSeek-V3 / Nemotron-H: scores
s = sigmoid(x W_r^T) in float32, the top_k of s + bias chosen (the bias
moves selection only), weights scale * s / sum of the chosen s.

The two grouped products run through the TPU's grouped-matmul kernels
(`jax.experimental.pallas.ops.tpu.megablox`, which skip the empty tail)
or through `jax.lax.ragged_dot`, as `ops/kernel_route.py` chooses;
`route_counts()` says at trace time which was asked for.  Through the
kernels a product and its two gradients are three kernel calls (`_gmm`:
lhs @ rhs, grad @ rhs^T, lhs^T @ grad), and each runs on the tile (tm, tk,
tn) that `choose_tile` works out from ITS kind and dimensions, the groups
and the rows a group is expected to hold (`_Stage.rows`): tiles that divide
the product's own k and n, one k tile wherever VMEM holds it (an expert's
weights then cross HBM once a group, not once a row tile), a row tile
that follows the rows a group holds (a tile across a group boundary is
visited once a group), the cheapest by a count of MXU time, HBM bytes and
grid steps.  `tile_choices()` says which tile each product traced took,
`route_counts()["padded_tiles"]` how many did not divide.  `ROW_TILE` is
what the plan's rows, the chunk and the block are padded to, a multiple of
the row tiles the kernels take, and no longer their row tile.
"""
from __future__ import annotations

import functools
import math
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from ..ops import kernel_route
from ._compat import shard_map_unchecked
from .mesh import DeviceMesh, current_mesh

__all__ = ["RoutePlan", "route", "experts", "moe_apply", "plan_rows",
           "plan_chunks", "plan_blocks", "row_chunk", "row_block",
           "route_counts", "choose_tile", "tile_choices"]

# what `plan_rows`, `row_chunk` and `row_block` pad to: a multiple of every
# row tile below 1,024 that the kernels may take (`ROW_TILES`)
ROW_TILE = 512
ROW_CHUNK = 4096    # rows `experts` handles a trip of its loop: a multiple
# rows a row-wise stage handles a trip of ITS loop inside a chunk (see
# `row_block`), a multiple.  One layer forward + backward on the chip
# (PERF.md, PR 43): at a 32,768-row chunk half full, blocks of 2,048 rows
# read 25.95 and 18.77 ms (lfm2's and laguna's shapes), of 1,024 rows
# 26.79 and 19.21; PR 42's sweep read 4,096 no better than 2,048
ROW_BLOCK = 2048

FORMS = ("relu2", "silu_gated")   # an expert's activation: see `experts`
ROUTES = ("grouped_kernel", "ragged_dot")
kernel_route.declare("moe_experts", ROUTES + (
    "sorted_layout", "expert_stage_traces", "token_sums", "exact_tiles",
    "padded_tiles"))
# asks neither the mesh nor the interpreter (ROADMAP.md names the debt)
_GROUPED_KERNEL = kernel_route.Kernel("moe_experts", "grouped_kernel",
                                      "ragged_dot", kernel_route.ANY_MESH)


def route_counts():
    """Since import: {route: grouped products of the `experts` calls
    made through it while a program was traced (two a call, whatever its
    gradient traces)}, under `sorted_layout` the `route` calls traced
    (every one lays its rows out by a sort: there is no other form),
    under `expert_stage_traces` how often one of `experts`' two loops
    (forward, backward) was really traced: once a signature, so products
    / 2 over traces says how many calls bound a trace that was there, and
    under `token_sums` how often a loop's sum over a token's rows was
    traced as the token reading them (`_token_sums`: the forward's
    combine, the backward's transpose of the gather; there is no other
    form, so it follows the loops' traces), and under `exact_tiles` /
    `padded_tiles` the grouped products traced through the kernels
    (forward, gradient to lhs, gradient to rhs: each counts) by whether
    the tile `choose_tile` gave them divides their k and n: a padded one
    runs a masked last k tile or a partly empty last n tile."""
    return kernel_route.counts("moe_experts")


class RoutePlan(NamedTuple):
    """Where the held experts' work is, rows laid out expert by expert."""
    token: jax.Array        # (rows,) int32: the row's token; T where unused
    weight: jax.Array       # (rows,) float32: its combine weight; 0 unused
    group_sizes: jax.Array  # (n_local,) int32: rows of each held expert
    dropped: jax.Array      # () int32: assignments that found no row (0)


def plan_rows(tokens: int, top_k: int, n_local: int) -> int:
    """Rows that hold every assignment onto `n_local` held experts."""
    rows = tokens * min(top_k, n_local)
    return -(-rows // ROW_TILE) * ROW_TILE


def row_chunk(expected_rows: int = 0) -> int:
    """Rows a trip of `experts`' loop handles: `ROW_CHUNK`, or, where the
    model says how many assignments it expects on the held experts
    (tokens x top_k x held / experts under even routing: a static
    number) and twice that is more, twice that in whole row tiles.  An
    uneven layer is then still ONE trip: at random weights a layer's load
    read up to 1.74 times the expected, and it grows as the held experts
    train (PERF.md, PR 31).  In chunks of `ROW_CHUNK` a load of several
    chunks pays each trip's fixed costs (the float32 weight-gradient
    carries are rewritten a trip) that many times, and the step's time
    follows the seed's load trip by trip (laguna_xs2_s8192 expects
    16,384 = 4 x 4096 a layer); a quarter of headroom was too little
    (some layers took a second trip of 20,480 rows, by the seed and the
    step)."""
    return max(ROW_CHUNK, -(-2 * expected_rows // ROW_TILE) * ROW_TILE)


def plan_chunks(group_sizes, expected_rows: int = 0):
    """Trips of `experts`' loop under a plan with these `group_sizes`
    (an array, traced or not): the chunks of `row_chunk(expected_rows)`
    rows that hold an assignment."""
    return -(-group_sizes.sum() // row_chunk(expected_rows))


def row_block(chunk: int) -> int:
    """Rows a trip of a row-wise stage's loop handles inside a chunk of
    `chunk` rows: the largest multiple of `ROW_TILE` up to `ROW_BLOCK` and
    up to a quarter of the chunk that divides the chunk, so that no block
    straddles its end and the count can follow the rows used (a chunk of
    two blocks that is 56% full visits both: the whole chunk again and
    the loops' cost besides)."""
    step = math.gcd(chunk, ROW_TILE, ROW_BLOCK)
    most = max(min(ROW_BLOCK, chunk // 4), step)
    return next(b for b in range(most - most % step, 0, -step)
                if chunk % b == 0)


def plan_blocks(group_sizes, expected_rows: int = 0, rows=None):
    """Blocks that each row-wise stage of `experts` visits under a plan
    with these `group_sizes` (an array, traced or not), over all its
    trips: the blocks of `row_block(chunk)` rows that hold an assignment,
    where the chunk is `row_chunk(expected_rows)` or, for a plan of
    fewer `rows`, the plan."""
    chunk = row_chunk(expected_rows)
    chunk = chunk if rows is None else min(chunk, rows)
    block = row_block(chunk)
    whole, rest = jnp.divmod(group_sizes.sum(), chunk)
    return whole * (chunk // block) + -(-rest // block)


def _fit(a, n: int, fill):
    """`a` (m,) cut to its first `n` entries, or padded to `n` with
    `fill`."""
    m = a.shape[0]
    return a[:n] if m >= n else jnp.pad(a, (0, n - m), constant_values=fill)


def _lay_out(weights, col, top_k, n_local, rows):
    """The plan's rows from the flat (T * top_k,) assignments: `col` the
    local expert of each (`n_local`: none that is held), `weights` its
    combine weight.  One sort by (expert, flat index) puts the held
    assignments first, expert by expert and token-ascending inside an
    expert.  -> ((token, weight, group_sizes), what the gradient reads)."""
    n = col.shape[0]
    t = n // top_k
    expert, order, weight = lax.sort(
        (col, jnp.arange(n, dtype=jnp.int32), weights), num_keys=2)
    held = expert < n_local
    token = _fit(jnp.where(held, order // top_k, t), rows, t)
    weight = _fit(jnp.where(held, weight, 0), rows, 0)
    group_sizes = (col[:, None] == jnp.arange(n_local)).sum(
        0, dtype=jnp.int32)
    return (token, weight, group_sizes), (order, held)


def _lay_out_backward(top_k, n_local, rows, res, g):
    """The weights' gradient is the rows' in the assignments' own order:
    a second sort, by the flat index the first one carried.  (JAX's rule
    for a sorted operand is a scatter-add over the rows.)"""
    order, held = res
    dweight = jnp.where(held, _fit(g[1], order.shape[0], 0), 0)
    return lax.sort((order, dweight), num_keys=1)[1], None


_layout = jax.custom_vjp(lambda *a: _lay_out(*a)[0],
                         nondiff_argnums=(2, 3, 4))
_layout.defvjp(_lay_out, _lay_out_backward)


def route(x, w_router, bias, *, top_k: int, scale: float = 1.0,
          first_expert=0, n_local: Optional[int] = None) -> RoutePlan:
    """Score `x` (T, D) against all E rows of `w_router` (E, D) in
    float32, choose `top_k` experts a token by score + `bias` (E,), and
    lay out the assignments that land on experts `first_expert ..
    first_expert + n_local - 1` (`first_expert` may be traced, as under a
    shard_map).

    The row order is a property callers rely on: the held experts' rows
    one expert after another (`group_sizes`), inside an expert by
    ascending token, then the unused rows.  Neither the plan nor its
    gradient is built with a scatter: a token's scores are picked by
    comparison, the rows come from one sort (`_lay_out`) and the
    weights' gradient from a second."""
    t, e = x.shape[0], w_router.shape[0]
    n_local = e if n_local is None else n_local
    if not 0 < top_k <= e or not 0 < n_local <= e:
        raise MXNetError(f"route: top_k {top_k} and held experts {n_local} "
                         f"must lie in 1..{e}")
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,ed->te", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(scores + lax.stop_gradient(
        bias.astype(jnp.float32)), top_k)                       # (T, k)
    # scores[t, chosen[t, k]], exact: one term of the sum is not 0.  (A
    # gather's gradient would be a scatter-add into (T, E).)
    picked = jnp.where(chosen[:, :, None] == jnp.arange(e),
                       scores[:, None, :], 0).sum(-1)
    weights = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)

    local = chosen - first_expert
    col = jnp.where((local >= 0) & (local < n_local), local, n_local)
    kernel_route.count("moe_experts", "sorted_layout")
    token, weight, group_sizes = _layout(
        weights.reshape(-1), col.reshape(-1), top_k, n_local,
        plan_rows(t, top_k, n_local))
    dropped = group_sizes.sum() - (token < t).sum().astype(jnp.int32)
    return RoutePlan(token, weight, group_sizes, dropped)


def _ragged(lhs, rhs, group_sizes):
    return lax.ragged_dot(lhs, rhs, group_sizes,
                          preferred_element_type=lhs.dtype)


# A grouped product's tile (tm, tk, tn) is chosen a PRODUCT, from that
# product's own kind and shapes (`choose_tile`), and never handed from one
# product to another: the forward's tile cuts the backward's products,
# whose K and N change places, into masked and partly empty tiles.
KINDS = ("gmm", "dlhs", "tgmm")     # lhs @ rhs[g]; grad @ rhs[g]^T; lhs^T @ grad
ROW_TILES = (128, 256, 512, 1024)   # the kernels' row tiles to choose from
# of the 16 MiB of scoped VMEM a kernel gets on the v5e: what `_vmem_bytes`
# counts may take this much, the rest is the compiler's own
VMEM_BUDGET = 12 * 2 ** 20
# the count's constants (`_tile_cost`): the v5e's two peaks, what a grid
# step costs beside its work, the share of the smaller of MXU and HBM
# time that the larger does not hide, and the rows an MXU pass over a row
# tile costs beside the tile's own.  The last is the chip's: over 290
# timings of the four cells' 24 products (PERF.md, PR 47) the count without
# it put 128-row tiles ahead of the 256-row tiles that ran 1-3% faster; of
# 0, 32 .. 256 rows, 64 picks the tiles whose times sum lowest (17.32 ms
# against 17.21 for each product's fastest tile; 17.35 at 0, 17.79 at 128)
_MXU_FLOPS, _HBM_BYTES, _GRID_STEP_S, _UNHIDDEN = 197e12, 819e9, 0.35e-6, 0.3
_ROW_TILE_EXTRA = 64

_tiles = {}     # (kind, m, k, n, groups) -> (tm, tk, tn) since import
_tiles_lock = threading.Lock()


def tile_choices():
    """Since import: {(kind, m, k, n, groups): the (tm, tk, tn) that the
    grouped product of that kind (`KINDS`) and those dimensions was traced
    with}."""
    with _tiles_lock:
        return dict(_tiles)


def _cuts(dim: int, exact: bool = True):
    """The tiles a dimension may be cut in: the multiples of 128 that
    divide it, or where there is none the whole of it; with `exact` off
    every multiple of 128 up to 1,024 (the last tile is then masked or
    partly empty)."""
    if not exact:
        return list(range(128, 1024 + 1, 128))
    return [t for t in range(128, dim + 1, 128) if dim % t == 0] or [dim]


def _vmem_bytes(kind, tm, tk, tn, itemsize):
    """What a kernel of `kind` holds in VMEM at this tile: its two
    operands' blocks and its out block, double-buffered, and the float32
    accumulator the size of the out block."""
    out = tk * tn if kind == "tgmm" else tm * tn
    other = tm * tn if kind == "tgmm" else tk * tn
    return 2 * itemsize * (tm * tk + other + out) + 4 * out


def _tile_cost(kind, tile, m, k, n, groups, itemsize, rows):
    """Seconds that a grouped product of `kind` is counted to take at
    `tile`, `rows` rows a group expected: the MXU's time on the tiles
    visited (a row tile that straddles a group boundary is visited once a
    group; a last k or n tile that does not divide is paid whole), the
    HBM's time (an operand's block is fetched again whenever its index
    changes, so with ONE k tile a group's weights cross once a group and
    not once a visit), the larger of the two plus `_UNHIDDEN` of the
    smaller, and `_GRID_STEP_S` a grid step.  It orders tiles; it is not a
    time anyone measured."""
    tm, tk, tn = tile
    used = min(m, rows * groups)
    visits = min(-(-used // tm) + groups - 1, m // tm + groups - 1)
    nk, nn = -(-k // tk), -(-n // tn)
    mxu = 2 * visits * (tm + _ROW_TILE_EXTRA) * nk * tk * nn * tn / _MXU_FLOPS
    if kind == "tgmm":      # grid (n tiles, k tiles, visits): out (tk, tn)
        moved = visits * tm * (k * nn + n * nk) + groups * k * n
    else:                   # grid (n tiles, visits, k tiles): out (tm, tn)
        moved = (visits * tm * k * nn + used * n
                 + (groups if nk == 1 else visits) * k * n)
    hbm = moved * itemsize / _HBM_BYTES
    return (max(mxu, hbm) + _UNHIDDEN * min(mxu, hbm)
            + _GRID_STEP_S * visits * nk * nn)


def choose_tile(kind: str, m: int, k: int, n: int, groups: int,
                itemsize: int = 2, rows: int = 0):
    """The tile (tm, tk, tn) of one grouped product, from what its call
    sees: `kind` (`KINDS`), the rows m, the contraction k and the width n
    AS THAT PRODUCT RUNS (for `dlhs` the forward's N and K; for `tgmm` the
    out is (groups, k, n) and m is contracted), the groups, the operands'
    itemsize, and the `rows` a group is expected to hold (0: m / groups).

    tk and tn are multiples of 128 that divide k and n, or the whole
    dimension: no k tile is masked and no n tile partly empty, for any
    shape with such a divisor whose tiles fit; tm is one of `ROW_TILES`
    that divides m (m itself where none does); the blocks fit
    `VMEM_BUDGET` (`_vmem_bytes`).  Of these the cheapest by `_tile_cost`,
    and of equals the one with the largest tk, tn, tm: so tk = k (for
    `tgmm` the largest out tile) wherever that fits beside the same tm
    and tn.  Only where no exact tile fits (a dimension with no such
    divisor and too large to hold whole) the cuts are multiples of 128 that
    do not divide, which `route_counts()["padded_tiles"]` counts."""
    if kind not in KINDS:
        raise MXNetError(f"choose_tile: kind {kind!r} is none of {KINDS}")
    rows = rows or -(-m // groups)
    row_tiles = [t for t in ROW_TILES if m % t == 0] or [m]
    for exact in (True, False):
        fit = [(tm, tk, tn) for tm in row_tiles for tk in _cuts(k, exact)
               for tn in _cuts(n, exact)
               if _vmem_bytes(kind, tm, tk, tn, itemsize) <= VMEM_BUDGET]
        if fit:
            return min(fit, key=lambda t: (_tile_cost(
                kind, t, m, k, n, groups, itemsize, rows),
                -t[1], -t[2], -t[0]))
    raise MXNetError(f"choose_tile: no tile of a {kind} product "
                     f"({m}, {k}, {n}) fits {VMEM_BUDGET} bytes of VMEM")


def _tile_of(kind, m, k, n, groups, itemsize, rows):
    """`choose_tile`, noted for `tile_choices()` and counted at trace time
    by whether the tile divides the product's k and n."""
    tile = choose_tile(kind, m, k, n, groups, itemsize, rows)
    with _tiles_lock:
        _tiles[kind, m, k, n, groups] = tile
    kernel_route.count("moe_experts", "padded_tiles" if k % tile[1]
                       or n % tile[2] else "exact_tiles")
    return tile


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, rows, interpret=False):
    """lhs (m, K) @ rhs[g] (K, N) through upstream's grouped-matmul
    kernels, under a rule of the repo's own so that each of the three
    products (this one, its gradient to lhs: the same kernel with rhs
    transposed, and to rhs: `tgmm`) runs on the tile its OWN dimensions
    ask for (`choose_tile`); upstream's rule hands the forward's tile to
    all three.  `rows`: the rows a group is expected to hold."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    (m, k), (groups, _, n) = lhs.shape, rhs.shape
    return gmm(
        lhs, rhs, group_sizes, lhs.dtype,
        _tile_of("gmm", m, k, n, groups, lhs.dtype.itemsize, rows),
        interpret=interpret)


def _gmm_forward(lhs, rhs, group_sizes, rows, interpret):
    return _gmm(lhs, rhs, group_sizes, rows, interpret), (
        lhs, rhs, group_sizes)


def _gmm_backward(rows, interpret, res, grad):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, group_sizes = res
    (m, k), (groups, _, n), size = lhs.shape, rhs.shape, lhs.dtype.itemsize
    dlhs = gmm(
        grad, rhs, group_sizes, lhs.dtype,
        _tile_of("dlhs", m, n, k, groups, size, rows),
        transpose_rhs=True, interpret=interpret)
    drhs = tgmm(
        lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
        _tile_of("tgmm", m, k, n, groups, size, rows),
        num_actual_groups=groups, interpret=interpret)
    return dlhs, drhs, None


_gmm.defvjp(_gmm_forward, _gmm_backward)


def _grouped(lhs, rhs, group_sizes, kernel, rows=0):
    """lhs (m, K) @ rhs[g] (K, N) for the rows of group g, `rows` of them
    expected a group (what the kernels' row tile follows; 0: m / groups).
    Rows past the last group come back undefined from the kernel: the
    caller masks them."""
    with jax.named_scope("products"):
        if not kernel:
            return _ragged(lhs, rhs, group_sizes)
        return kernel_route.dispatch(
            lambda lhs, rhs, sizes: _gmm(lhs, rhs, sizes, rows), _ragged,
            lhs, rhs, group_sizes, interpret=False)


def _rows_of(u, tok):
    """u's rows (T, K) of the tokens `tok`; 0 for token T, no assignment."""
    return jnp.take(u, tok, axis=0, mode="fill", fill_value=0)


def _activate(hidden, form):
    """The first product's rows (rows, N) or (rows, 2N) -> (rows, N)."""
    if form == "relu2":
        return jnp.square(jnp.maximum(hidden, 0))
    gate, up = jnp.split(hidden, 2, axis=1)
    return jax.nn.silu(gate) * up


def _weigh(out, used, weight=None):
    """A product's rows, those that hold no assignment masked (the
    kernel leaves them undefined), in float32, weighed where a weight is
    given."""
    out = jnp.where(used[:, None], out, 0).astype(jnp.float32)
    return out if weight is None else out * weight[:, None]


def _by_block(name, count, block, fn, *rows, halo=0, spare=0):
    """`fn(*the block of each of rows) -> a tuple of blocks` over the
    first `count` (traced) blocks of `block` rows of `rows`, arrays
    (chunk + halo, ...): a tuple of arrays (chunk + spare, ...), made in a
    loop under the scope `name`; with a `halo`, `fn` is also handed the
    `halo` rows that follow its block; the `spare` rows after the chunk's
    are the caller's to fill.  The rows of a block that was not visited
    are UNDEFINED, as the rows past the last group are that a grouped
    product returns (`lax.empty`: memory as it was found on the TPU, where
    zeros would be a pass over the whole chunk at every call; 0
    elsewhere)."""
    made = jax.eval_shape(fn, *(jax.ShapeDtypeStruct(
        (block + halo,) + r.shape[1:], r.dtype) for r in rows))

    def body(b, outs):
        lo = b * block
        blocks = fn(*(lax.dynamic_slice_in_dim(r, lo, block + halo)
                      for r in rows))
        return tuple(lax.dynamic_update_slice_in_dim(o, v, lo, 0)
                     for o, v in zip(outs, blocks))

    with jax.named_scope(name):
        return lax.fori_loop(0, count, body, tuple(
            lax.empty((rows[0].shape[0] - halo + spare,) + m.shape[1:],
                      m.dtype) for m in made))


def _in_token_order(tok, wt, t):
    """A chunk's rows put in the order of their tokens, by one sort and
    no scatter: tok (chunk,) the rows' tokens (`t`: no assignment), wt
    (chunk,) their weights or None.  -> (the tokens ascending, the row
    each came from, its weight or None, rows (t,): how many of the
    chunk's rows are a token's, start (t,): how many belong to a token
    before it, which is where its run begins).

    The count is a product of two one-hot matrices: with a token's
    number split in two digits, `high` and `low`, the rows' one-hot
    digits (chunk, highs) and (chunk, 128) multiply to the (highs, 128)
    table of the tokens' rows, exact in float32 (JAX's histogram is a
    scatter-add)."""
    chunk, highs = tok.shape[0], -(-t // 128)
    token, row, *weight = lax.sort(
        (tok, jnp.arange(chunk, dtype=jnp.int32))
        + (() if wt is None else (wt,)), num_keys=2)
    high = jax.nn.one_hot(jnp.where(tok < t, tok // 128, highs), highs,
                          dtype=jnp.bfloat16)
    low = jax.nn.one_hot(tok % 128, 128, dtype=jnp.bfloat16)
    rows = jnp.einsum("rh,rl->hl", high, low,
                      preferred_element_type=jnp.float32)
    rows = rows.reshape(-1)[:t].astype(jnp.int32)
    return (token, row, weight[0] if weight else None, rows,
            jnp.cumsum(rows) - rows)


def _token_sums(name, into, first, values, tok, wt, count, most, block):
    """`into` (T, K) float32 plus every token's sum over the rows of a
    chunk that are its own, or where `first` (traced) the sums alone,
    `into` not read (it may hold anything): values (chunk, K) a product's
    rows (those past the assignments undefined), tok (chunk,) their
    tokens (T: none), wt (chunk,) float32 weights or None, `count`
    (traced) the blocks of `block` rows that hold an assignment, `most`
    the rows a token can have.  A token with no row here adds 0.

    The TOKEN reads its rows; nothing is scattered.  The rows are taken
    in token order (`_in_token_order`), a block at a time under the
    count; a token's rows are then a run of at most `most` neighbours, so
    `most` shifted windows of the block (a halo of `most` - 1 rows at its
    end), masked and weighed in float32 and added in one pass, leave the
    token's sum at the head of its run, always in the same order of its
    terms; one gather of T head rows makes the chunk's part.  The cost
    follows the rows used and T, not the chunk."""
    kernel_route.count("moe_experts", "token_sums")
    (t, _), chunk, halo, spare = into.shape, tok.shape[0], most - 1, 8
    with jax.named_scope(name):
        token, row, weight, rows, start = _in_token_order(tok, wt, t)

        def sums(token, row, *weight):
            rows = values.at[row].get(mode="promise_in_bounds")
            head, total = token[:block], None
            for j in range(most):
                term = _weigh(rows[j:j + block],
                              (token[j:j + block] == head) & (head < t),
                              *(w[j:j + block] for w in weight))
                total = term if total is None else total + term
            return total,

        heads, = _by_block(
            "sums", count, block, sums,
            jnp.pad(token, (0, halo), constant_values=t),
            jnp.pad(row, (0, halo)),
            *(() if weight is None else (jnp.pad(weight, (0, halo)),)),
            halo=halo, spare=spare)
        # a tile of zeros after the chunk: what a token with no row reads
        heads = lax.dynamic_update_slice_in_dim(
            heads, jnp.zeros((spare,) + heads.shape[1:], heads.dtype),
            chunk, 0)
        index = jnp.where(rows > 0, start, chunk)

        def part():
            return heads.at[index].get(mode="promise_in_bounds")

        # the first trip's part IS the sum: nothing is zero-filled for it
        # and nothing added to it (most layers take one trip)
        return lax.cond(first, lambda into: part(),
                        lambda into: into + part(), into)


def _most_rows_a_token(rows: int, t: int, n_local: int) -> int:
    """The rows one token can have in a plan of `rows` rows for `t` tokens
    on `n_local` held experts: one an expert, and `rows` holds `t` times
    min(top_k, n_local) (`plan_rows`)."""
    return min(n_local, -(-rows // t))


class _Stage(NamedTuple):
    """What a trace of the expert stage is made for, beside its operands'
    shapes and dtypes: `experts` reads these where it is called."""
    form: str
    chunk: int      # rows a trip of the loop over the plan handles
    block: int      # rows a trip of a row-wise stage's loop inside it
    kernel: bool    # whether `kernel_route` admits the grouped kernel
    rows: int = 0   # rows a held expert is expected to hold (0: chunk / held)


def _chunks(token, weight, group_sizes, t, stage):
    """How `experts`' two loops cut a plan: (its rows rounded up to whole
    chunks, the trip count, window(c) -> (chunk c's first row, its
    tokens, its weights, the rows of each group that lie inside it, its
    blocks that hold an assignment))."""
    rows, chunk = token.shape[0], stage.chunk
    most = -(-rows // chunk)
    pad = most * chunk - rows
    token = jnp.pad(token, (0, pad), constant_values=t)
    weight = jnp.pad(weight, (0, pad))
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes

    def window(c):
        lo = c * chunk
        return (lo, lax.dynamic_slice(token, (lo,), (chunk,)),
                lax.dynamic_slice(weight, (lo,), (chunk,)),
                jnp.clip(ends, lo, lo + chunk)
                - jnp.clip(starts, lo, lo + chunk),
                -(-jnp.clip(ends[-1] - lo, 0, chunk) // stage.block))

    return most * chunk, jnp.minimum(-(-ends[-1] // chunk), most), window


def _over_trips(trips, body, sums, *others):
    """`body(c, carry) -> carry` for c in 0 .. `trips` - 1 (traced), the
    carry float32 arrays: first a (T, K) sum of shape `sums`, then arrays
    of the shapes `others` that start as zeros.  The sum's start is
    UNDEFINED (`lax.empty`) where there is a trip: the first one writes it
    (`_token_sums`); a plan with no trip gives zeros.  The start is made
    in a branch for its lifetime's sake: an uninitialised buffer made in
    the program's entry has no operand, so XLA makes every layer's at the
    program's start and keeps them all to their use (laguna's step
    planned 0.78 GiB more, PERF.md PR 44); the branch waits for `trips`."""
    start = lax.cond(trips > 0, lambda: lax.empty(sums, jnp.float32),
                     lambda: jnp.zeros(sums, jnp.float32))
    return lax.fori_loop(0, trips, body, (start,) + tuple(
        jnp.zeros(shape, jnp.float32) for shape in others))


@functools.partial(jax.jit, static_argnums=6)
def _forward(u, token, weight, group_sizes, w1, w2, stage):
    kernel_route.count("moe_experts", "expert_stage_traces")
    t, block = u.shape[0], stage.block
    product = functools.partial(_grouped, kernel=stage.kernel,
                                rows=stage.rows)
    _, trips, window = _chunks(token, weight, group_sizes, t, stage)
    most = _most_rows_a_token(token.shape[0], t, w1.shape[0])

    def body(c, carry):
        _, tok, wt, sizes, count = window(c)
        x, = _by_block("gather", count, block,
                       lambda tok: (_rows_of(u, tok),), tok)
        hidden, = _by_block(
            "activate", count, block, lambda h: (_activate(h, stage.form),),
            product(x, w1, sizes))
        out = product(hidden, w2, sizes)
        return _token_sums("combine", carry[0], c == 0, out, tok, wt, count,
                           most, block),

    return _over_trips(trips, body, u.shape)[0].astype(u.dtype)


def _forward_and_inputs(*inputs):
    # the residuals are the inputs and nothing of size rows x N: the
    # backward's loop makes each chunk's hidden again from its rows
    return _forward(*inputs), inputs[:-1]


@functools.partial(jax.jit, static_argnums=0)
def _backward(stage, res, g):
    """The gradient, written out as the forward is (a loop with a traced
    trip count has no reverse mode of its own): the same chunks, the
    same blocks, each product and each row-wise stage under `jax.vjp` by
    itself."""
    kernel_route.count("moe_experts", "expert_stage_traces")
    u, token, weight, group_sizes, w1, w2 = res
    t, block = u.shape[0], stage.block
    product = functools.partial(_grouped, kernel=stage.kernel,
                                rows=stage.rows)
    padded, trips, window = _chunks(token, weight, group_sizes, t, stage)
    most = _most_rows_a_token(token.shape[0], t, w1.shape[0])

    def activate(hidden):
        return _activate(hidden, stage.form),

    def activate_back(hidden, dact):
        return jax.vjp(activate, hidden)[1]((dact,))

    def weigh_back(tok, wt, out):
        _, pull = jax.vjp(lambda out, wt: _weigh(out, tok < t, wt), out, wt)
        return pull(_rows_of(g, tok).astype(jnp.float32))

    def body(c, carry):
        du, dweight, dw1, dw2 = carry
        lo, tok, wt, sizes, count = window(c)
        x, = _by_block("gather", count, block,
                       lambda tok: (_rows_of(u, tok),), tok)
        hidden, pull1 = jax.vjp(lambda x, w: product(x, w, sizes), x, w1)
        act, = _by_block("activate", count, block, activate, hidden)
        out, pull2 = jax.vjp(lambda a, w: product(a, w, sizes), act, w2)
        dout, dwt = _by_block("combine", count, block, weigh_back, tok, wt,
                              out)
        dact, d2 = pull2(dout)
        dx, d1 = pull1(*_by_block("activate", count, block, activate_back,
                                  hidden, dact))
        # a row that no block visited: 0, not what memory held
        dwt = jnp.where(jnp.arange(stage.chunk) < count * block, dwt, 0)
        # the gather of u's rows, transposed: a token sums its rows of dx
        du = _token_sums("gather", du, c == 0, dx, tok, None, count, most,
                         block)
        return (du, lax.dynamic_update_slice(dweight, dwt, (lo,)),
                dw1 + d1.astype(jnp.float32), dw2 + d2.astype(jnp.float32))

    du, dweight, dw1, dw2 = _over_trips(trips, body, u.shape, (padded,),
                                        w1.shape, w2.shape)
    return (du.astype(u.dtype), None,
            dweight[:weight.shape[0]].astype(weight.dtype), None,
            dw1.astype(w1.dtype), dw2.astype(w2.dtype))


# the two loops are jitted by themselves, so each is traced ONCE for a
# signature (the operands' shapes and dtypes, `_Stage`): a model's layers,
# each under its own `jax.checkpoint`, bind that trace, and XLA inlines it
_experts = jax.custom_vjp(_forward, nondiff_argnums=(6,))
_experts.defvjp(_forward_and_inputs, _backward)


def experts(u, plan: RoutePlan, w1, w2, form: str = "relu2",
            expected_rows: int = 0):
    """The held experts' part of the layer: u (T, K) tokens, w2
    (n_local, N, K), and w1 (n_local, K, N) for `form` "relu2" or
    (n_local, K, 2N), gate and up side by side, for "silu_gated";
    returns (T, K) in u's dtype: sum over a token's held experts of
    weight * relu(u W1_e)^2 W2_e, or of weight * (silu(u G_e) * (u U_e))
    W2_e.

    The plan's rows are handled `row_chunk(expected_rows)` at a time
    (`expected_rows`, static: the assignments the model expects on the
    held experts; 0 leaves the chunk at `ROW_CHUNK`), `plan_chunks(
    plan.group_sizes, expected_rows)` times: per chunk gather, grouped
    product, the activation, grouped product, mask, scale in float32,
    add into a (T, K) float32 sum.  The products skip the chunk's empty
    tail themselves.  The gather, the activation and, in the gradient,
    the gather of the cotangent, the weights' gradient and the
    activation's go over the chunk `row_block(chunk)` rows at a time and
    stop after the last block that holds an assignment (`plan_blocks`).
    The two sums over a token's rows (the result into (T, K), the
    gradient into u's) are no scatter-adds: the token reads its rows in
    token order (`_token_sums`; `route_counts()["token_sums"]`), in the
    same blocks, always in the same order of its float32 terms, so the
    result is deterministic; a row past the assignments carries token T
    and weight 0 and is read as 0, and a plan of several trips adds each
    trip's part in float32 (a token's rows may lie in two chunks).  The
    gradient is a second loop of the same trip counts over the same
    chunks and blocks; nothing of a forward pass is kept for it but the
    inputs.  The plan is `route`'s: its rows hold T x min(top_k, n_local)
    assignments, which is how many rows a token can have.

    The two loops are traced once for a set of operand shapes and
    dtypes, `form`, the chunk and the block (`route_counts()`
    ["expert_stage_traces"]); every further call binds that trace."""
    if form not in FORMS or w1.shape[2] != w2.shape[1] * (
            2 if form == "silu_gated" else 1):
        raise MXNetError(f"experts: form {form!r} (of {FORMS}) with w1 "
                         f"{w1.shape} and w2 {w2.shape}")
    kernel = kernel_route.choose(_GROUPED_KERNEL, True, u.shape[0],
                                 times=2)     # two grouped products a call
    chunk = min(row_chunk(int(expected_rows)), plan.token.shape[0])
    # what the model expects, or a full chunk where it states nothing;
    # the twin has no tile, and no signature of its own for it
    rows = -(-min(int(expected_rows) or chunk, chunk)
             // w1.shape[0]) if kernel else 0
    return _experts(u, plan.token, plan.weight, plan.group_sizes, w1, w2,
                    _Stage(form, chunk, row_block(chunk), kernel, rows))


def moe_apply(x, u, w_router, bias, w1, w2, *, top_k: int,
              scale: float = 1.0, form: str = "relu2",
              mesh: Optional[DeviceMesh] = None, axis_name: str = "ep"):
    """The routed part of one expert layer over every expert in `w1` /
    `w2` (E, ...): tokens x (T, D) are scored, their latents u (T, K) go
    through the chosen experts of `form` (see `experts`).  Under a mesh
    with an `axis_name` axis of several devices the stacked experts are split over it, each device
    routes over all E and computes its own share (tokens replicated in
    the group, as the weights outside the experts are), and the shares
    are summed; without one the layer runs in one piece.  Returns ((T, K)
    result, dropped assignments: always 0)."""
    e = w1.shape[0]
    if w_router.shape[0] != e:
        raise MXNetError(f"stacked experts {e} != router width "
                         f"{w_router.shape[0]}")
    mesh = mesh or current_mesh()
    n = mesh.size(axis_name) if mesh is not None and axis_name in mesh else 1
    if e % n:
        raise MXNetError(f"experts ({e}) must divide over '{axis_name}' "
                         f"({n})")

    def share(first, x, u, w_router, bias, w1, w2):
        plan = route(x, w_router, bias, top_k=top_k, scale=scale,
                     first_expert=first, n_local=w1.shape[0])
        return experts(u, plan, w1, w2, form), plan.dropped

    if n == 1:
        return share(0, x, u, w_router, bias, w1, w2)

    def per_device(x, u, w_router, bias, w1, w2):
        first = lax.axis_index(axis_name) * w1.shape[0]
        part, dropped = share(first, x, u, w_router, bias, w1, w2)
        return lax.psum(part, axis_name), lax.psum(dropped, axis_name)

    held = P(axis_name, None, None)
    return shard_map_unchecked(
        per_device, mesh=mesh.mesh,
        in_specs=(P(), P(), P(), P(), held, held),
        out_specs=(P(), P()))(x, u, w_router, bias, w1, w2)
