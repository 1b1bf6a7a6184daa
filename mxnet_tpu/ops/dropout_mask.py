"""The repo's one dropout-mask generator: a counter-based integer hash.

Every dropout of the repo takes its bits here: the op `Dropout` and the
RNN op's dropout between layers (`inverted_dropout`, a mask of any shape)
and attention's dropout on the probabilities
(`pallas_attention.dropout_keep_mask`, the (b*h, q, k) form its Mosaic
kernels compute a tile at a time).  JAX's threefry `bernoulli` is left to
the samplers (`ops/random_ops.py`, `ops/image_ops.py`).

The bits are lowbias32's two multiplies and three xor-shifts over (the
key's two words, the element's GLOBAL index) in plain uint32 arithmetic,
about ten integer operations an element where a threefry2x32 draw takes
twenty rounds for every two.  Being a pure function of global indices
they are the same bits inside a Mosaic kernel, in the Pallas interpreter,
in XLA (under `jit`, eagerly, and under GSPMD, where a batch shard reads
its slice of the one mask) and in numpy, whatever the block sizes: that
is what lets tier-1 hold an output AND its gradient to a reference under
the identical mask (`pltpu.prng_random_bits` depends on the block layout
and never could).  XLA keeps no mask for the backward; it draws it again
in every fusion that reads it, which is why a draw has to be cheap
(PERF.md, PR 52).

Which stream: each site gets a key of its own from the frontend's split,
so the words differ site to site and step to step.  Same distribution as
the threefry `bernoulli` the ops used before (attention until PR 26,
`Dropout` until PR 52), other draws.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from . import kernel_route

__all__ = ["keep_mask", "inverted_dropout", "site_counts"]

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_GOLDEN = np.uint32(0x9E3779B9)
_S15 = np.uint32(15)
_S16 = np.uint32(16)
_WORD = 1 << 32


def _hash_qk(q_idx, k_idx, seq_k, key0):
    """First round, shared by every head: (q, k) and the key's first word.
    uint32 operands (numpy, jnp or kernel values); q*seq_k + k cannot wrap
    below 65536 keys."""
    x = (q_idx * np.uint32(seq_k) + k_idx) ^ key0
    x = x ^ (x >> _S16)
    x = x * _M1
    return x ^ (x >> _S15)


def _hash_head(bh_idx, key1):
    """Per-head word: b*h and the key's second word through a full
    two-multiply mix.  b*h enters here, not through bh*S*S, so no shape
    can wrap 32 bits; in a kernel this is scalar work."""
    x = bh_idx * _GOLDEN + key1
    x = x ^ (x >> _S16)
    x = x * _M1
    x = x ^ (x >> _S15)
    x = x * _M2
    return x ^ (x >> _S16)


def _hash_bits(h_qk, h_head):
    """Second round, per (b*h, q, k): a word whose HIGH bits are uniform.
    The threshold compare reads the high bits, which the closing
    xor-shift of a full round would not change, so there is none."""
    x = h_qk + h_head
    x = x ^ (x >> _S16)
    return x * _M2


def _keep_threshold(keep):
    """An element is kept when its bits are below this; the rounding of
    keep*2^32 (2^-32) is far below bf16."""
    return np.uint32(min(int(keep * 2.0 ** 32), 2 ** 32 - 1))


def _key_words(rng_key):
    """The first two uint32 words of a PRNG key, typed or raw."""
    if jnp.issubdtype(rng_key.dtype, jax.dtypes.prng_key):
        rng_key = jax.random.key_data(rng_key)
    return rng_key.reshape(-1)[:2].astype(jnp.uint32)


def _runs(shape):
    """`shape`'s dimensions in runs, innermost first, each run's row-major
    index below 2^32.  The first run is the counter of the hash's first
    round; each further one is folded into the second key word, the way
    `_hash_head` takes b*h.  One run while the flat index fits 32 bits."""
    runs, stop, block = [], len(shape), 1
    for d in reversed(range(len(shape))):
        if shape[d] >= _WORD:
            raise MXNetError(f"a dropout mask of shape {tuple(shape)}: "
                             f"axis {d} cannot be indexed by 32 bits")
        if block * shape[d] >= _WORD:
            runs.append(range(d + 1, stop))
            stop, block = d + 1, 1
        block *= shape[d]
    return runs + [range(stop)]


def _mask_bits(key_words, shape, index, xp):
    """The uint32 word of every element of a mask of `shape` (rank >= 1)
    at `index`: one uint32 array a dimension, broadcastable to the
    result, the element's GLOBAL position along it."""
    zero = xp.zeros((1,) * len(shape), dtype=xp.uint32)

    def flat(dims):     # row-major over `dims`; arrays wrap, scalars warn
        at = zero
        for d in dims:
            at = at * np.uint32(shape[d]) + index[d]
        return at

    inner, *outer = _runs(shape)
    h_head = key_words[1]
    for run in outer or [()]:
        h_head = _hash_head(flat(run), h_head)
    return _hash_bits(
        _hash_qk(flat(inner[:-1]), index[inner[-1]], shape[inner[-1]],
                 key_words[0]),
        h_head)


def keep_mask(key_words, shape, keep, xp=jnp):
    """The boolean kept-mask of `shape` for the two uint32 `key_words`:
    element by element independent Bernoulli(`keep`).  `xp` is jnp or
    numpy: the same bits from either."""
    dims = tuple(shape) or (1,)         # a scalar's mask: one element
    index = [xp.arange(n, dtype=xp.uint32).reshape(
        (1,) * d + (n,) + (1,) * (len(dims) - d - 1))
        for d, n in enumerate(dims)]
    kept = _mask_bits(key_words, dims, index, xp) < _keep_threshold(keep)
    return kept.reshape(tuple(shape))


kernel_route.declare("dropout", ("hash",))
kernel_route.declare("dropout_elements", ("hash",))


def site_counts():
    """{generator: {"sites": dropout sites traced, "elements": what their
    masks cover}} since import: counted where a program is traced (or an
    eager call made), never per step, as the routes of `kernel_route`
    are.  Read it before and after to count."""
    elements = kernel_route.counts("dropout_elements")
    return {generator: {"sites": sites, "elements": elements[generator]}
            for generator, sites in kernel_route.counts("dropout").items()}


def inverted_dropout(data, key, p, axes=()):
    """`data` with each element zeroed with probability `p` and the kept
    ones scaled by 1 / (1 - p), in `data`'s dtype: upstream's form
    (dropout-inl.h: the mask holds 1 / pkeep, the output is data * mask),
    with no divide an element.  One mask element is shared along each of
    `axes`: a broadcast mask hashes its own reduced shape."""
    shared = {a % data.ndim for a in axes}
    shape = tuple(1 if d in shared else n for d, n in enumerate(data.shape))
    keep = 1.0 - p
    kernel_route.count("dropout", "hash")
    kernel_route.count("dropout_elements", "hash", math.prod(shape))
    kept = keep_mask(_key_words(key), shape, keep)
    return jnp.where(kept, data * (1.0 / keep), 0)
