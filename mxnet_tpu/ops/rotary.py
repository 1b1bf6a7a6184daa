"""Rotary position embedding as a registered op, so that a traced program
books its work under `rotary_embedding` (`ops.registry.apply_pure`).

The op takes its angles ready-made: float32 tables `cos` and `sin` of
shape (S, r), row p holding the rotation of position p, the r / 2
frequencies repeated over both halves (the rotate-half pairing: dimension
i turns with dimension i + r / 2) or each twice in a row (`interleaved`:
dimension 2i turns with 2i + 1, the complex-number form).  Which
frequencies, and any factor on them (YaRN's attention factor), is the
model's business: it builds one pair of tables a rotary kind from its
config (`rotary_tables`, `yarn_inv_freq`) and hands them to every layer
of that kind.  r may be less than the head size: the other dimensions
pass through, those past r or, with `rotate_last`, those before the
last r (a latent-attention query is [unrotated ; rotated]).  Query and
key may differ in head size; a key all heads share is `num_kv_heads=1`.

Which operand takes which form (`route_counts()`; one operand at a time,
how a route is chosen is `ops/kernel_route.py`'s business):

  * `kernel`: `_turn`, one Pallas pass that reads x once and writes it
    once in its own dtype, float32 only in VMEM, with a backward rule of
    its own (the same pass on the cotangent): heads of a whole number of
    64 lanes and a sequence that is a multiple of a row block.
  * `xla`: `_rotate`, the product with a signed permutation in plain
    XLA, whose float32 product and sum XLA writes to HBM and reads back
    (4-6 times the bytes of one pass: PERF.md, PR 40): every other shape.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import kernel_route
from .registry import register_op

__all__ = ["default_inv_freq", "yarn_inv_freq", "rotary_tables",
           "route_counts"]


def default_inv_freq(theta: float, rotary_dim: int) -> np.ndarray:
    """theta^(-2i / r), i = 0 .. r/2 - 1, float64."""
    return theta ** (-np.arange(0, rotary_dim, 2, dtype=np.float64)
                     / rotary_dim)


def yarn_inv_freq(theta: float, rotary_dim: int, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's frequencies (arXiv:2309.00071): dimensions that turn more
    than `beta_fast` times over the original context keep theta's
    frequency, those that turn less than `beta_slow` times are
    interpolated (divided by `factor`), with a linear ramp between."""
    r = rotary_dim
    freq = 1.0 / default_inv_freq(theta, r)

    def dim_of(turns):      # the dimension that makes `turns` rotations
        return (r * math.log(original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0, 1)
    return ramp / (factor * freq) + (1 - ramp) / freq


def rotary_tables(inv_freq, length: int, factor: float = 1.0,
                  interleaved: bool = False):
    """-> (cos, sin) float32 (length, r) for positions 0 .. length - 1
    and r / 2 frequencies, both multiplied by `factor`: frequency i at
    columns i and i + r / 2, or at 2i and 2i + 1 (`interleaved`)."""
    inv_freq = jnp.asarray(np.asarray(inv_freq, np.float32))
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = (jnp.repeat(angle, 2, axis=-1) if interleaved
             else jnp.concatenate([angle, angle], axis=-1))
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def _partner(d: int, r: int, interleaved: bool = False,
             rotate_last: bool = False) -> np.ndarray:
    """(d, d) signed permutation P: x @ P holds every rotated
    dimension's partner, -x[b] at a and x[a] at b for each pair (a, b) =
    (i, i + r / 2), or (2i, 2i + 1) `interleaved`, of the first r
    dimensions (the last r: `rotate_last`), and 0 elsewhere."""
    turn = np.zeros((d, d), np.float32)
    first = d - r if rotate_last else 0
    for i in range(r // 2):
        a, b = (2 * i, 2 * i + 1) if interleaved else (i, i + r // 2)
        turn[first + b, first + a] = -1.0
        turn[first + a, first + b] = 1.0
    return turn


@functools.partial(jax.jit, static_argnames=("heads", "interleaved",
                                             "rotate_last"))
def _rotate(x, cos, sin, heads, interleaved=False, rotate_last=False):
    """The XLA form: what runs on the CPU, under a mesh and for shapes
    the kernel cannot tile.  x (B, S, heads * D): r = cos.shape[-1]
    dimensions of each head rotated, in float32, the rest passed through.
    The partner of every dimension comes from a product with a signed
    permutation (exact: one term a sum), so that nothing is sliced or
    concatenated along the lanes: on the v5e the sliced form took 3.1-3.7
    times as long (PERF.md, PR 31), which is why this form, and the
    kernel's body after it, is a product.  XLA writes the float32
    product to HBM and a second fusion reads it back, and autodiff's
    transpose does the same on a float32 cotangent: `_turn` keeps both
    in VMEM."""
    b, s, u = x.shape
    d, r = u // heads, cos.shape[-1]
    x = x.reshape(b, s, heads, d)
    partner = jnp.einsum(
        "bshd,de->bshe", x,
        jnp.asarray(_partner(d, r, interleaved, rotate_last), x.dtype),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if x.dtype == jnp.float32 else None)
    still = (d - r, 0) if rotate_last else (0, d - r)
    cos = jnp.pad(cos, ((0, 0), still), constant_values=1.0)
    sin = jnp.pad(sin, ((0, 0), still))
    out = x.astype(jnp.float32) * cos[:, None, :] + partner * sin[:, None, :]
    return out.astype(x.dtype).reshape(b, s, u)


# ---- the kernel --------------------------------------------------------
#
# One pass over x seen head-major, (B, heads, S, D): a block of rows of all
# heads is read once, every head is turned in float32 with `_rotate`'s own
# arithmetic (`x * cos + (x @ P) * sin`, the product on the MXU from the
# block in VMEM: it never reaches HBM), rounded once and written once.  The
# tables are read as (rows, r) blocks as they came and, in the kernel, laid
# over a head's D lanes once a block and broadcast over the heads.  Where
# whole 128-lane tiles of a head pass through (the first 128 of a latent
# query's 192), the call reads and writes the other tiles alone, in place.
# Head-major because that is what XLA makes of this op's operands and
# results between a projection and an attention kernel anyway: the
# transposes around the call are views of (B, S, heads * D) that layout
# assignment turns into bitcasts, where a kernel over rows of heads * D
# lanes would force a copy on either side (PERF.md, PR 40).  The backward
# is the same body on the cotangent: `g * cos + (g * sin) P^T` written as
# `g * cos + (g @ P) * t` with t the negated sine of each lane's partner.

_BLOCK_BYTES = 2 << 20      # of x a grid step: ~5 us of HBM beside its ~0.35 fixed
_MAX_ROWS = 1024


def _lane_window(d, r, rotate_last):
    """(first lane, lanes) of what the kernel reads and writes of a head:
    the 128-lane tiles that hold rotated lanes, where they make a block
    of their own (the last 64 of 192: the second tile, half of it past
    the head's end), else the whole head.  The other tiles are never
    touched: the result is written over the operand."""
    first = d - r if rotate_last else 0
    t0, t1 = first // 128, -(-(first + r) // 128)
    if t1 - t0 < -(-d // 128) and t0 % (t1 - t0) == 0:
        return t0 * 128, (t1 - t0) * 128
    return 0, d


def _tiling(s, heads, d, r, rotate_last, itemsize):
    """(rows a block, first lane, lanes): all heads of as many positions
    as `_BLOCK_BYTES` hold, of a head its `_lane_window`.  None where the
    kernel does not apply: a head that fills no whole number of 64 lanes,
    a sequence no multiple of a row block, nothing to turn."""
    if d % 64 or not r:
        return None
    lo, lanes = _lane_window(d, r, rotate_last)
    held = -(-lanes // 128) * 128       # what VMEM holds of a row
    rows = _MAX_ROWS
    while rows >= 16 and (s % rows
                          or heads * rows * held * itemsize > _BLOCK_BYTES):
        rows //= 2
    return (rows, lo, lanes) if rows >= 16 else None


def _turn_kernel(x_ref, cos_ref, sin_ref, p_ref, *rest, valid):
    *spread_ref, o_ref = rest
    p = p_ref[...]
    exact = lax.Precision.HIGHEST if p.dtype == jnp.float32 else None
    cos, sin = cos_ref[...], sin_ref[...]
    if spread_ref:
        # the (rows, r) tables laid over the block's lanes by products
        # with 0 / 1 / -1 matrices, exact in float32: cos with 1 where a
        # lane passes through, sin with 0 there
        place, place_sin = spread_ref[0][0], spread_ref[0][1]
        still = 1.0 - jnp.sum(place, axis=0, keepdims=True)
        cos = jnp.dot(cos, place, precision=lax.Precision.HIGHEST) + still
        sin = jnp.dot(sin, place_sin, precision=lax.Precision.HIGHEST)
    lanes = x_ref.shape[3]
    inside = valid == lanes or \
        lax.broadcasted_iota(jnp.int32, x_ref.shape[2:], 1) < valid

    def turn(h, _):
        x = x_ref[0, h]
        if valid < lanes:       # past the head's end a block holds anything
            x = jnp.where(inside, x, jnp.zeros_like(x))
        partner = jnp.dot(x, p, preferred_element_type=jnp.float32,
                          precision=exact)
        o_ref[0, h] = (x.astype(jnp.float32) * cos + partner * sin
                       ).astype(o_ref.dtype)

    lax.fori_loop(0, x_ref.shape[1], turn, None)


def _turn_pass(x, cos, sin, heads, interleaved, rotate_last, interpret,
               transpose):
    from jax.experimental import pallas as pl

    b, s, u = x.shape
    d, r = u // heads, cos.shape[-1]
    rows, lo, lanes = _tiling(s, heads, d, r, rotate_last, x.dtype.itemsize)
    valid = min(lanes, d - lo)
    p = np.zeros((lanes, lanes), np.float32)
    p[:valid, :valid] = _partner(d, r, interleaved, rotate_last)[
        lo:lo + valid, lo:lo + valid]
    table = pl.BlockSpec((rows, r), lambda i, j: (j, 0))
    block = pl.BlockSpec((1, heads, rows, lanes),
                         lambda i, j: (i, 0, j, lo // lanes))
    operands = [x.reshape(b, s, heads, d).transpose(0, 2, 1, 3), cos, sin,
                jnp.asarray(p, x.dtype)]
    in_specs = [block, table, table,
                pl.BlockSpec(p.shape, lambda i, j: (0, 0))]
    if transpose or r < lanes:
        first = d - r if rotate_last else 0
        place = np.eye(r, lanes, first - lo, dtype=np.float32)
        # P^T = -P: in the backward each lane takes its partner's -sin
        spread = np.stack([place, place @ -np.abs(p) if transpose else place])
        operands.append(jnp.asarray(spread))
        in_specs.append(pl.BlockSpec(spread.shape, lambda i, j: (0, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_turn_kernel, valid=valid),
        grid=(b, s // rows),
        in_specs=in_specs,
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, heads, s, d), x.dtype),
        # the lanes outside the block are the operand's own
        input_output_aliases={0: 0} if lanes < d else {},
        interpret=interpret,
        name="mx_rotary_turn",      # the kernel's name in a device profile
    )(*operands)
    return out.transpose(0, 2, 1, 3).reshape(b, s, u)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _turn(x, cos, sin, heads, interleaved, rotate_last, interpret):
    """`_rotate`'s function as one Pallas pass, x's dtype in and out,
    float32 inside; the backward is the same pass on the cotangent, in
    the dtype it arrives in.  The tables get no gradient."""
    return _turn_pass(x, cos, sin, heads, interleaved, rotate_last,
                      interpret, transpose=False)


def _turn_fwd(x, cos, sin, *statics):
    return _turn(x, cos, sin, *statics), (cos, sin)


def _turn_bwd(heads, interleaved, rotate_last, interpret, tables, g):
    return (_turn_pass(g, *tables, heads, interleaved, rotate_last,
                       interpret, transpose=True), None, None)


_turn.defvjp(_turn_fwd, _turn_bwd)


@functools.partial(jax.jit, static_argnames=("heads", "interleaved",
                                             "rotate_last", "interpret"))
def _rotate_on_tpu(x, cos, sin, heads, interleaved, rotate_last, interpret):
    """`_turn` or `_rotate`.  Jitted, so that a stack of layers traces
    and lowers the kernel once a kind of layer."""
    pairing = dict(heads=heads, interleaved=interleaved,
                   rotate_last=rotate_last)
    return kernel_route.dispatch(
        functools.partial(_turn, **pairing, interpret=interpret),
        functools.partial(_rotate, **pairing), x, cos, sin,
        interpret=interpret)


# one count an operand turned
ROUTES = ("kernel", "xla")
kernel_route.declare("rotary", ROUTES)
_KERNEL = kernel_route.Kernel("rotary", "kernel", "xla")


def route_counts():
    """{route: operands traced through it} since import, in the form of
    `pallas_attention.route_counts()`."""
    return kernel_route.counts("rotary")


def _rotate_routed(x, cos, sin, heads, interleaved, rotate_last):
    """One operand by the kernel where `_tiling` can tile its shape."""
    if not kernel_route.choose(
            _KERNEL, _tiling(x.shape[1], heads, x.shape[-1] // heads,
                             cos.shape[-1], rotate_last, x.dtype.itemsize),
            x.shape[0]):
        return _rotate(x, cos, sin, heads=heads, interleaved=interleaved,
                       rotate_last=rotate_last)
    return _rotate_on_tpu(x, cos, sin, heads, interleaved, rotate_last,
                          kernel_route.interpret())


@register_op("rotary_embedding", num_outputs=2)
def _rotary_embedding(query, key, cos, sin, num_heads=1, num_kv_heads=0,
                      interleaved=False, rotate_last=False):
    """Rotate query (B, S, num_heads * D) and key (B, S, num_kv_heads *
    Dk) by the float32 tables cos, sin (S, r) made for the same pairing,
    r <= D, Dk even: dimension i < r / 2 of a head pairs with i + r / 2
    (`interleaved`: 2i with 2i + 1); dimensions from r on are unchanged
    (`rotate_last`: the last r are rotated, those before unchanged).
    Returns (query, key) in their own dtypes."""
    r = cos.shape[-1]
    kv_heads = num_kv_heads or num_heads
    d = min(query.shape[-1] // num_heads, key.shape[-1] // kv_heads)
    if r % 2 or r > d or cos.shape != sin.shape \
            or cos.shape[0] != query.shape[1]:
        raise ValueError(f"rotary_embedding: tables {cos.shape} / "
                         f"{sin.shape} for {query.shape[1]} positions and "
                         f"heads of {d}")
    pairing = (bool(interleaved), bool(rotate_last))
    return (_rotate_routed(query, cos, sin, num_heads, *pairing),
            _rotate_routed(key, cos, sin, kv_heads, *pairing))
