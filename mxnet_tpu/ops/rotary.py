"""Rotary position embedding as a registered op, so that a traced program
books its work under `rotary_embedding` (`ops.registry.apply_pure`).

The op takes its angles ready-made: float32 tables `cos` and `sin` of
shape (S, r), row p holding the rotation of position p, the r / 2
frequencies repeated over both halves (the rotate-half pairing: dimension
i turns with dimension i + r / 2).  Which frequencies, and any factor on
them (YaRN's attention factor), is the model's business: it builds one
pair of tables a rotary kind from its config (`rotary_tables`,
`yarn_inv_freq`) and hands them to every layer of that kind.  r may be
less than the head size: the dimensions past r pass through.
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


def rotary_tables(inv_freq, length: int, factor: float = 1.0):
    """-> (cos, sin) float32 (length, r) for positions 0 .. length - 1
    and r / 2 frequencies, both multiplied by `factor`."""
    inv_freq = jnp.asarray(np.asarray(inv_freq, np.float32))
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def _half_turn(d: int, r: int) -> np.ndarray:
    """(d, d) signed permutation P with x @ P = [-x[r/2:r], x[:r/2], 0]:
    every dimension's partner in the rotation, 0 past r."""
    turn = np.zeros((d, d), np.float32)
    for i in range(r // 2):
        turn[i + r // 2, i] = -1.0
        turn[i, i + r // 2] = 1.0
    return turn


@functools.partial(jax.jit, static_argnames=("heads",))
def _rotate(x, cos, sin, heads):
    """x (B, S, heads * D): each head's first r = cos.shape[-1]
    dimensions rotated, in float32, the rest passed through.  The
    partner of every dimension comes from a product with a signed
    permutation (exact: one term a sum), so that nothing is sliced or
    concatenated along the lanes: on the v5e the sliced form took 3.1-3.7
    times as long (PERF.md, PR 31)."""
    b, s, u = x.shape
    d, r = u // heads, cos.shape[-1]
    x = x.reshape(b, s, heads, d)
    partner = jnp.einsum(
        "bshd,de->bshe", x, jnp.asarray(_half_turn(d, r), x.dtype),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if x.dtype == jnp.float32 else None)
    cos = jnp.pad(cos, ((0, 0), (0, d - r)), constant_values=1.0)
    sin = jnp.pad(sin, ((0, 0), (0, d - r)))
    out = x.astype(jnp.float32) * cos[:, None, :] + partner * sin[:, None, :]
    return out.astype(x.dtype).reshape(b, s, u)


@register_op("rotary_embedding", num_outputs=2)
def _rotary_embedding(query, key, cos, sin, num_heads=1, num_kv_heads=0):
    """Rotate query (B, S, num_heads * D) and key (B, S, num_kv_heads * D)
    by the float32 tables cos, sin (S, r), r <= D even: dimension i < r / 2
    of a head pairs with i + r / 2; dimensions from r on are unchanged.
    Returns (query, key) in their own dtypes."""
    r = cos.shape[-1]
    d = query.shape[-1] // num_heads
    if r % 2 or r > d or cos.shape != sin.shape \
            or cos.shape[0] != query.shape[1]:
        raise ValueError(f"rotary_embedding: tables {cos.shape} / "
                         f"{sin.shape} for {query.shape[1]} positions and "
                         f"heads of {d}")
    return (_rotate(query, cos, sin, heads=num_heads),
            _rotate(key, cos, sin, heads=num_kv_heads or num_heads))
