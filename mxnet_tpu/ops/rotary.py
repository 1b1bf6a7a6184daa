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
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register_op

__all__ = ["default_inv_freq", "yarn_inv_freq", "rotary_tables"]


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
    """x (B, S, heads * D): r = cos.shape[-1] dimensions of each head
    rotated, in float32, the rest passed through.  The partner of every
    dimension comes from a product with a signed permutation (exact: one
    term a sum), so that nothing is sliced or concatenated along the
    lanes: on the v5e the sliced form took 3.1-3.7 times as long
    (PERF.md, PR 31)."""
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
    pairing = dict(interleaved=bool(interleaved),
                   rotate_last=bool(rotate_last))
    return (_rotate(query, cos, sin, heads=num_heads, **pairing),
            _rotate(key, cos, sin, heads=kv_heads, **pairing))
