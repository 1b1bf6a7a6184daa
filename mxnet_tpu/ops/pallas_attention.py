"""Fused scaled-dot-product attention: Pallas TPU kernel + XLA reference.

The reference's counterpart is the fused attention path in later-1.x
contrib (ref: src/operator/contrib/transformer.cc —
_contrib_interleaved_matmul_selfatt_* used by GluonNLP BERT); this is the
TPU-native equivalent per SURVEY.md §7 ("fused cells (RNN/attention) …
in Pallas").

Which call takes which route (`route_counts()`).  How a route is chosen,
kernel or XLA twin, is `ops/kernel_route.py`'s business; here is what
each route covers:

  * Dropout-free calls (inference, p=0): `_attend`.  One Pallas kernel per
    (batch*head, q-block), the query block in VMEM, keys/values for the
    whole sequence as one block, scores on the MXU in fp32 and never in
    HBM; backward = recompute through jax.vjp of the XLA reference under
    custom_vjp.  Counted `kernel_infer`; its twin `reference`.
  * Causal self-attention with no dropout and no key mask, heads of 128
    (or a multiple) or of 64, sq == sk a multiple of 128, query heads a
    multiple of the key/value heads (grouped-query), training or
    inference: `_attend_causal`, upstream's splash multi-query forward
    kernel over a `CausalMask` (`_causal_splash`) and, where the block of
    1,024 rows divides S, a backward of the repo's own under a custom
    VJP of the repo's own: ONE kernel, `mx_causal_attention_bwd`, walks
    the block pairs the triangle touches, forms each score block once
    and gives dK, dV and dQ from it (6 block products a pair for the 6
    a causal pair needs, where upstream's dK/dV and dQ kernels execute
    9), dQ summed over the key blocks in float32 in ONE array in HBM
    that the kernel adds to in place and rounded once (upstream's fused
    form rounds a part a key block to the operands' type; PERF.md,
    PR 48).  Other S: upstream's two backward kernels.  O(S) memory: the
    only route that fits a decoder at S = 8192, where `_attend`'s
    backward would hold 32 x 8192^2 scores.  A call it does not admit
    takes the dropout-free route.  Counted `flash_causal` (the key is
    older than the kernels: until PR 38 upstream's `flash_attention`
    kernels ran here); `backward_counts()` says which form a traced
    backward took.
  * Causal sliding-window self-attention (`sliding_window_attention`, an
    op of its own so that a profile reads the window and the full cores
    apart): query i sees keys j with 0 <= i - j < window.  Shapes as for
    `flash_causal`, window < S: `_attend_causal` over a `LocalMask`,
    O(S x window) work where `flash_causal` does O(S^2 / 2); the
    forward is upstream's kernel, the backward ONE kernel of the repo's
    own, `mx_window_attention_bwd`: it walks the block pairs the band
    touches, leaves the tiles of a pair that no query sees a key of out
    of its products, masks only the tiles an edge of the band cuts, and
    sums dQ, dK and dV in float32 in VMEM, each written once (PERF.md,
    PR 50; `_fused_backward` says which calls).  Counted
    `splash_window`; every other windowed call is the banded XLA form,
    counted `reference`; window >= S is causal attention.
  * `eva_attention`, `latent_attention` and `differential_attention`
    (modules of their own) count their routes here too: `eva_splash` /
    `eva_xla`, `latent_splash` / `latent_xla`, `diff_splash` and
    `diff_window_splash` / `diff_xla`.
  * Training with dropout on the probabilities, self-attention shaped as
    BERT's (not causal, sq == sk, a multiple of 128 up to 1024, heads of
    64, 128 or 256 filling whole 128-lane blocks): `_attend_train`, two
    fused kernels under one custom_vjp in the packed (B, S, H*D) layout
    (the comment above `_FUSED_MAX_SEQ`).  The one kernel that takes a
    batch shard (`kernel_route.BATCH_SHARDS`): its seed holds the
    shard's first row, so the mask is indexed globally.  Counted
    `fused_train`.
  * Every other training call with dropout (causal, cross, ragged
    lengths: the NMT model, toy shapes; tensor- or sequence-parallel
    meshes): `_attention_with_prob_dropout`, XLA, probabilities saved for
    the backward.  Counted `xla_dropout`.

The dropout mask is `dropout_keep_mask`: the rounds of the repo's one
generator, `ops/dropout_mask.py` (the op `Dropout` draws from the same),
over (key words, b*h, q, k) by GLOBAL index, the same bits in Mosaic, the
interpreter, XLA and numpy.  Both training routes take it from there,
which is why the dropout STREAM differs from the threefry `bernoulli` this
op used before PR 26: same distribution, other draws, so a loss pinned
under attention dropout moved once.

The splash routes NAME what the forward kernel wrote for the backward,
output and logsumexp (ops/residuals.py); their XLA twins name nothing.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import kernel_route
from .dropout_mask import (_hash_bits, _hash_head, _hash_qk, _keep_threshold,
                           _key_words)
from .registry import register_op

__all__ = ["dot_product_attention_ref", "dropout_keep_mask", "route_counts",
           "backward_counts"]


def dot_product_attention_ref(q, k, v, mask, scale, causal=False):
    """Pure-XLA reference: q,k,v (BH, S, D); mask (BH, S) in {0,1} or None."""
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[:, None, :] > 0, s, -1e30)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        qpos = jnp.arange(sq)[:, None] + (sk - sq)  # align last q to last k
        s = jnp.where(qpos >= jnp.arange(sk)[None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _attention_pallas(q, k, v, mask, scale, causal=False):
    """Pallas kernel: grid (BH, S//bq); K/V whole-sequence blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    bq = min(128, s)
    # pad query len to a multiple of bq and key len to a tiling-friendly
    # multiple of 8; padded keys are killed via the validity mask
    s_pad = ((s + bq - 1) // bq) * bq
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0)))
    sk = k.shape[1]
    sk_pad = ((sk + 7) // 8) * 8
    if sk_pad != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad - sk), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, sk_pad - sk)))
    sk_len = sk_pad
    nq = s_pad // bq
    causal_off = sk - s  # align last query to last key

    def kernel(q_ref, k_ref, v_ref, m_ref, o_ref):
        qb = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        kb = k_ref[0].astype(jnp.float32)                  # (Sk, d)
        vb = v_ref[0]                                      # (Sk, d)
        sc = jax.lax.dot_general(
            qb, kb, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bq, Sk)
        # the mask stays 2-D: Mosaic (libtpu 0.0.34) aborts the process
        # in vector-layout inference on a 1-D bf16 vector
        valid = m_ref[0].astype(jnp.float32) > 0           # (1, Sk)
        sc = jnp.where(valid, sc, -1e30)
        if causal:
            qi = pl.program_id(1)
            qpos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, sk_len), 0) + causal_off
            kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, sk_len), 1)
            sc = jnp.where(qpos >= kpos, sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1).astype(vb.dtype)
        o_ref[0] = jnp.dot(p, vb,
                           preferred_element_type=jnp.float32).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk_len, d), lambda b, i: (b, 0, 0)),
            # mask rides as (BH, 1, Sk) so the block's LAST TWO dims
            # equal the array's — Mosaic requires last-two either
            # (8,128)-divisible or full-dimension (a 2-d (1, Sk) block
            # over (BH, Sk) is rejected on current jax)
            pl.BlockSpec((1, 1, sk_len), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        interpret=kernel_route.interpret(),
        name="mx_attention_fwd",    # the kernel's name in a device profile
    )(q, k, v, mask[:, None, :])
    return out[:, :s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attend(q, k, v, mask, scale, causal=False):
    """`_attention_pallas` or the reference, as `kernel_route` chooses."""
    reference = functools.partial(dot_product_attention_ref, scale=scale,
                                  causal=causal)
    if not kernel_route.admit(_KERNEL_INFER, True, q.shape[0]):
        return reference(q, k, v, mask)
    return kernel_route.dispatch(
        functools.partial(_attention_pallas, scale=scale, causal=causal),
        reference, q, k, v, mask, interpret=kernel_route.interpret())


def _attend_fwd(q, k, v, mask, scale, causal):
    return _attend(q, k, v, mask, scale, causal), (q, k, v, mask)


def _attend_bwd(scale, causal, res, ct):
    q, k, v, mask = res
    # recompute-from-inputs backward through the XLA reference math
    _, vjp = jax.vjp(lambda q_, k_, v_:
                     dot_product_attention_ref(q_, k_, v_, mask, scale,
                                               causal),
                     q, k, v)
    dq, dk, dv = vjp(ct)
    return dq, dk, dv, jnp.zeros_like(mask)


_attend.defvjp(_attend_fwd, _attend_bwd)


# ---------------------------------------------------------------------------
# Training with dropout on the attention probabilities
# ---------------------------------------------------------------------------
# One mask generator for every route AND for the op `Dropout`:
# `ops/dropout_mask.py`'s integer hash, here over (key words, b*h, q, k).
# The kernels call its rounds on a tile's GLOBAL indices.


def dropout_keep_mask(key_words, bh, seq_q, seq_k, keep, xp=jnp,
                      first_head=0):
    """The (bh, seq_q, seq_k) boolean kept-mask of attention dropout for
    the two uint32 `key_words`; `xp` is jnp or numpy.  `first_head` is the
    GLOBAL b*h index of row 0, for a caller that holds one batch shard."""
    u32 = lambda n, shape: xp.arange(n, dtype=xp.uint32).reshape(shape)
    h_qk = _hash_qk(u32(seq_q, (1, seq_q, 1)), u32(seq_k, (1, 1, seq_k)),
                    seq_k, key_words[0])
    h_head = _hash_head(
        u32(bh, (bh, 1, 1)) + xp.asarray(first_head, dtype=xp.uint32),
        key_words[1])
    return _hash_bits(h_qk, h_head) < _keep_threshold(keep)


def _attention_with_prob_dropout(q, k, v, mask, scale, p, rng_key,
                                 causal=False, first_head=0):
    """XLA path with dropout on the attention probabilities — the BERT /
    reference training semantics (dropout on softmax(QK^T)), the mask from
    `dropout_keep_mask`.  Training calls the fused kernels do not cover
    (causal, cross, ragged lengths) run this, and so does a fused-route
    call in a program lowered for another platform than the TPU."""
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[:, None, :] > 0, s, -1e30)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        qpos = jnp.arange(sq)[:, None] + (sk - sq)
        s = jnp.where(qpos >= jnp.arange(sk)[None, :], s, -1e30)
    p_attn = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    keep = 1.0 - p
    drop_mask = dropout_keep_mask(_key_words(rng_key), *s.shape, keep,
                                  first_head=first_head)
    p_attn = p_attn * drop_mask.astype(p_attn.dtype) / keep
    return jnp.einsum("bqk,bkd->bqd", p_attn, v)


# The fused route: two kernels under one custom_vjp, adapted from
# jax.experimental.pallas.ops.tpu.flash_attention (saved row statistics,
# bf16 operands with f32 accumulation, several heads per grid step), cut
# to what BERT-shaped self-attention needs and extended by the dropout
# hash and the key-validity mask.  A whole row of keys is one block
# (S*D and a (block_q, S) f32 tile fit VMEM up to _FUSED_MAX_SEQ), so
# the softmax is not tiled, the backward is ONE kernel (dQ, dK and dV
# from a single recomputation of the probabilities and the mask), and no
# S x S tensor is ever written to HBM.
#
#   forward   O_i = (sum_j D_ij P~_ij V_j) / (keep * l_i),
#             P~ = exp(S - m), l_i = sum_j P~_ij over ALL j;
#             saves lse_i = m_i + log l_i as (B, H, 1, S) f32
#   backward  delta_i = rowsum(dO_i * O_i); P = exp(S - lse);
#             dV = (P * D / keep)^T dO; dP = D * (dO V^T) / keep;
#             dS = P * (dP - delta); dQ = scale dS K; dK = scale dS^T Q
#             (in the kernel keep * dS = P * (D * dO V^T - keep * delta),
#             and scale / keep multiplies the (S, D) results)
#
# The kernels read q, k, v and write o and the gradients in the PACKED
# (B, S, H*D) layout the projections produce: a block is 128 lanes wide,
# two heads of 64 side by side, and a head is taken out of it by zeroing
# the other head's lanes in ONE operand of each matmul (a contraction
# over 64 fills half the MXU's depth either way).  No head is ever split
# off or merged back in HBM, and nothing is padded from 64 to 128 lanes.
# The backward works on the TRANSPOSED tile (keys on sublanes, queries on
# lanes): lse and delta then broadcast as rows, and dV and dK need no
# transposed operand.

_FUSED_MAX_SEQ = 1024     # a (block_q, S) f32 tile: at most 2 MiB
_NT = (((1,), (1,)), ((), ()))      # A @ B^T
_TN = (((0,), (0,)), ((), ()))      # A^T @ B


def _train_blocks(batch, seq, width=128):
    """(batch rows per grid step, query rows per block, rows unrolled in
    the kernel's loop), from the shape alone.  The query block is the
    largest multiple of 128 up to 512 that DIVIDES S (512 at S=512 and
    1024, 384 at 768, 128 at 640 and 896): the grid runs S / block_q query
    blocks, so a block that does not divide S would leave rows unwritten.
    A key block of some 4096 rows at 128 lanes (1 MiB of bf16; 8 rows at
    S=512, 24 of 264 at S=128; half as many rows at 256 lanes) buries the
    ~0.35 us a grid step costs.  Small tiles are unrolled, some 2^17 score
    elements a head (8 rows at S=128, none at S=512), so that one row's
    matmuls overlap another's vector work; each unrolled row costs ~0.12 s
    of tracing and lowering at every start of the process (all 24 rows at
    S=128: 1% of the step won, 5.6 s of `setup_s` lost).  Swept on the v5e in PR 26 (PERF.md section 6);
    forward + backward ms a layer: S=512, B=40: (8, 512, 1) 0.59 + 1.02,
    (8, 512, 2) 0.58 + 0.99, (8, 256, 2) 0.59 + 1.30, (8, 128, 1) 0.80 +
    1.89; S=128, B=264: (24, 128, 1) 0.77 + 1.41, (24, 128, 6) 0.55 + 0.80,
    (24, 128, 12) 0.50 + 0.73, (24, 128, 24) 0.47 + 0.71."""
    bq = max(r for r in range(128, min(seq, 512) + 1, 128) if seq % r == 0)
    divisor = lambda n, most: max(d for d in range(1, max(1, min(most, n)) + 1)
                                  if n % d == 0)
    bb = divisor(batch, 4096 * 128 // (width * seq))
    return bb, bq, divisor(bb, (1 << 17) // (bq * seq))


def _train_vmem_bytes(bb, bq, unroll, seq, width, itemsize):
    """The scoped VMEM to ask Mosaic for, from the tiles the backward (the
    larger kernel) holds: its eight operand and result blocks, double
    buffered; the two f32 accumulators when there is more than one query
    block; some eight (block_q, S) f32 tiles (scores, probabilities, hash
    bits, dP, dS and their temporaries) for each unrolled row; twice that
    for what Mosaic allocates itself.  48 MiB at (8, 512, 1) on S=512 and
    32 MiB at (24, 128, 8) on S=128, bf16; a chip with less VMEM than a
    shape asks for refuses it at compile time, with Mosaic's message."""
    blocks = 2 * 4 * bb * (bq + seq) * width * itemsize
    scratch = 2 * bb * seq * width * 4 if seq > bq else 0
    tiles = 8 * unroll * bq * seq * 4
    return 2 * (blocks + scratch + tiles)


def _for_each_entry(n, unroll, body):
    """body(i) for the n batch entries of a block, `unroll` at a time in
    one basic block, so that the scheduler overlaps one entry's matmuls
    with another's vector work (Mosaic's own loops unroll all or nothing)."""
    def group(g, carry):
        for u in range(unroll):
            body(g * unroll + u)
        return carry

    jax.lax.fori_loop(0, n // unroll, group, 0)


def _u32_iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(jnp.uint32)


def _first_head(row0, i, j, heads, per_block):
    """Global b*h index (uint32) of the first head of lane block `j`, for
    entry `i` of a batch block whose first entry is global row `row0`."""
    u32 = lambda x: jnp.asarray(x).astype(jnp.uint32)
    return (row0 + u32(i)) * np.uint32(heads) + u32(j) * np.uint32(per_block)


def _scaled_scores(a, b, scale):
    """scale * a @ b^T in f32.  A power-of-two scale (1/8 at D=64) goes
    into the first operand, exactly; any other is applied to the scores,
    as the reference applies it."""
    if math.frexp(scale)[0] == 0.5:
        return jax.lax.dot_general(a * jnp.asarray(scale, a.dtype), b, _NT,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(
        a, b, _NT, preferred_element_type=jnp.float32) * scale


class _HeadLanes:
    """The heads that share one lane block of width `w`: head `h` of the
    block owns lanes [h*d, (h+1)*d)."""

    def __init__(self, d):
        self.d, self.w = d, max(128, d)
        self.n = self.w // d

    def mine(self, h):
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, self.w), 1)
        return (lane >= h * self.d) & (lane < (h + 1) * self.d)

    def only(self, h, x):
        """`x` with the other heads' lanes zeroed."""
        return x if self.n == 1 else jnp.where(self.mine(h), x,
                                               jnp.zeros_like(x))

    def put(self, h, new, into):
        """`into` with head h's lanes taken from `new`."""
        return new if into is None else jnp.where(self.mine(h), new, into)


def _train_specs(pl, batch, heads, seq, d, blocks):
    """Grid and the BlockSpecs both kernels share."""
    lanes = _HeadLanes(d)
    bb, bq, unroll = blocks or _train_blocks(batch, seq, lanes.w)
    # a block that does not divide its axis leaves rows unwritten
    assert batch % bb == 0 and seq % bq == 0 and bb % unroll == 0, (
        batch, seq, (bb, bq, unroll))
    assert heads % lanes.n == 0 and bq % 128 == 0, (heads, d, bq)
    grid = (batch // bb, heads // lanes.n, seq // bq)
    return lanes, bb, bq, unroll, grid, {
        # (bb, bq, w) of q / o / do / dq; (bb, S, w) of k / v / dk / dv
        "q": pl.BlockSpec((bb, bq, lanes.w), lambda b, j, i, key: (b, i, j)),
        "kv": pl.BlockSpec((bb, seq, lanes.w), lambda b, j, i, key: (b, 0, j)),
        # the (B, 1, S) key-validity mask, one row a batch entry
        "mask": pl.BlockSpec((bb, 1, seq), lambda b, j, i, key: (b, 0, 0)),
        # the (B, H, 1, S) row statistic: lse
        "row": pl.BlockSpec((bb, lanes.n, 1, bq),
                            lambda b, j, i, key: (b, j, 0, i)),
    }


_STATICS = ("heads", "scale", "keep")


@kernel_route.shared_kernel(*_STATICS, "blocks")
def _attention_train_fwd_pallas(q, k, v, mask, seed, heads, scale, keep,
                                blocks=None, interpret=False):
    """Forward kernel: q, k, v (B, S, H*D), mask (B, S), seed uint32[3]
    (`_seed`).  Grid (B/bb, H / heads-per-lane-block, S/bq).  Returns
    (o, lse)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, units = q.shape
    d = units // heads
    lanes, bb, bq, unroll, grid, spec = _train_specs(pl, batch, heads, seq, d,
                                                     blocks)
    threshold = _keep_threshold(keep)

    def kernel(key_ref, q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref):
        j, qi = pl.program_id(1), pl.program_id(2)
        # the GLOBAL batch row of the block's first entry
        b0 = key_ref[2] + (pl.program_id(0) * bb).astype(jnp.uint32)
        q_idx = _u32_iota((bq, seq), 0) + (qi * bq).astype(jnp.uint32)
        h_qk = _hash_qk(q_idx, _u32_iota((bq, seq), 1), seq, key_ref[0])

        def entry(i):
            qb, kb, vb = q_ref[i], k_ref[i], v_ref[i]
            valid = m_ref[i] > 0                               # (1, S)
            head0 = _first_head(b0, i, j, heads, lanes.n)
            o = None
            for h in range(lanes.n):
                s = _scaled_scores(lanes.only(h, qb), kb, scale)   # (bq, S)
                s = jnp.where(valid, s, -1e30)
                m = jnp.max(s, axis=1, keepdims=True)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=1, keepdims=True)          # over ALL keys
                kept = _hash_bits(h_qk, _hash_head(
                    head0 + np.uint32(h), key_ref[1])) < threshold
                pv = jnp.dot(jnp.where(kept, p, 0.0).astype(vb.dtype), vb,
                             preferred_element_type=jnp.float32)   # (bq, w)
                o = lanes.put(h, pv / (l * keep), o)
                # the row statistics leave as a ROW: (bq, 1) -> (1, bq)
                lse = jnp.broadcast_to(m + jnp.log(l), (bq, 128))
                lse_ref[i, h] = lse.T[:1]
            o_ref[i] = o.astype(o_ref.dtype)

        _for_each_entry(bb, unroll, entry)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[spec["q"], spec["kv"], spec["kv"], spec["mask"]],
            out_specs=[spec["q"], spec["row"]]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((batch, heads, 1, seq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_train_vmem_bytes(
                bb, bq, unroll, seq, lanes.w, q.dtype.itemsize)),
        interpret=interpret,
        name="mx_attention_train_fwd",
    )(seed, q, k, v, mask.astype(jnp.float32)[:, None, :])


@kernel_route.shared_kernel(*_STATICS, "blocks")
def _attention_train_bwd_pallas(q, k, v, mask, seed, o, lse, do, heads,
                                scale, keep, blocks=None, interpret=False):
    """Backward kernel, same grid; dK and dV accumulate over the query
    blocks in f32 scratch.  Returns (dq, dk, dv)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, units = q.shape
    d = units // heads
    lanes, bb, bq, unroll, grid, spec = _train_specs(pl, batch, heads, seq, d,
                                                     blocks)
    nq = grid[2]
    threshold = _keep_threshold(keep)

    def kernel(key_ref, q_ref, k_ref, v_ref, m_ref, o_ref, do_ref, lse_ref,
               dq_ref, dk_ref, dv_ref, *acc):
        j, qi = pl.program_id(1), pl.program_id(2)
        # the GLOBAL batch row of the block's first entry
        b0 = key_ref[2] + (pl.program_id(0) * bb).astype(jnp.uint32)
        q_idx = _u32_iota((seq, bq), 1) + (qi * bq).astype(jnp.uint32)
        h_qk = _hash_qk(q_idx, _u32_iota((seq, bq), 0), seq, key_ref[0])

        def entry(i):
            qb, kb, vb, dob = q_ref[i], k_ref[i], v_ref[i], do_ref[i]
            # keys on sublanes: the (1, S) validity row becomes a column
            valid = jnp.broadcast_to(m_ref[i], (128, seq)).T[:, :1] > 0
            # rowsum(dO * O) is exact under dropout: O already holds the mask
            o_do = o_ref[i].astype(jnp.float32) * dob.astype(jnp.float32)
            dq = dk = dv = None
            head0 = _first_head(b0, i, j, heads, lanes.n)
            for h in range(lanes.n):
                delta = keep * jnp.sum(lanes.only(h, o_do), axis=1,
                                       keepdims=True)          # (bq, 1)
                delta = jnp.broadcast_to(delta, (bq, 128)).T[:1]   # (1, bq)
                st = _scaled_scores(lanes.only(h, kb), qb, scale)  # (S, bq)
                st = jnp.where(valid, st, -1e30)
                pt = jnp.exp(st - lse_ref[i, h])
                kept = _hash_bits(h_qk, _hash_head(
                    head0 + np.uint32(h), key_ref[1])) < threshold
                # 1/keep and the scale leave the S x S tile: they multiply
                # the (S, w) and (bq, w) results, delta is taken times keep
                dv = lanes.put(h, jnp.dot(
                    jnp.where(kept, pt, 0.0).astype(dob.dtype), dob,
                    preferred_element_type=jnp.float32), dv)
                dpt = jax.lax.dot_general(
                    lanes.only(h, vb), dob, _NT,
                    preferred_element_type=jnp.float32)
                dst = (pt * (jnp.where(kept, dpt, 0.0) - delta)
                       ).astype(qb.dtype)
                dk = lanes.put(h, jnp.dot(
                    dst, qb, preferred_element_type=jnp.float32), dk)
                dq = lanes.put(h, jax.lax.dot_general(
                    dst, kb, _TN, preferred_element_type=jnp.float32), dq)
            dq_ref[i] = (dq * (scale / keep)).astype(dq_ref.dtype)
            dk, dv = dk * (scale / keep), dv * (1.0 / keep)
            if nq == 1:
                dk_ref[i] = dk.astype(dk_ref.dtype)
                dv_ref[i] = dv.astype(dv_ref.dtype)
            else:
                dk_acc, dv_acc = acc

                @pl.when(qi == 0)
                def _():
                    dk_acc[i] = dk
                    dv_acc[i] = dv

                @pl.when(qi > 0)
                def _():
                    dk_acc[i] += dk
                    dv_acc[i] += dv

                @pl.when(qi == nq - 1)
                def _():
                    dk_ref[i] = dk_acc[i].astype(dk_ref.dtype)
                    dv_ref[i] = dv_acc[i].astype(dv_ref.dtype)

        _for_each_entry(bb, unroll, entry)

    acc = pltpu.VMEM((bb, seq, lanes.w), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[spec["q"], spec["kv"], spec["kv"], spec["mask"],
                      spec["q"], spec["q"], spec["row"]],
            out_specs=[spec["q"], spec["kv"], spec["kv"]],
            scratch_shapes=[acc, acc] if nq > 1 else []),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_train_vmem_bytes(
                bb, bq, unroll, seq, lanes.w, q.dtype.itemsize)),
        interpret=interpret,
        name="mx_attention_train_bwd",
    )(seed, q, k, v, mask.astype(jnp.float32)[:, None, :], o, do, lse)


def _split_heads(x, heads):
    """(B, S, H*D) -> (B*H, S, D)."""
    b, s, u = x.shape
    return x.reshape(b, s, heads, u // heads).transpose(0, 2, 1, 3).reshape(
        b * heads, s, u // heads)


def _merge_heads(x, heads):
    """(B*H, S, D) -> (B, S, H*D)."""
    bh, s, d = x.shape
    return x.reshape(bh // heads, heads, s, d).transpose(0, 2, 1, 3).reshape(
        bh // heads, s, heads * d)


def _seed(key_words, first_row=0):
    """uint32[3], the kernels' scalar operand: the two key words and the
    GLOBAL index of the operands' first batch row (0 unless the caller
    holds one batch shard), so the mask is a function of global indices."""
    return jnp.concatenate(
        [key_words, jnp.asarray(first_row, jnp.uint32).reshape(1)])


def _train_xla(q, k, v, mask, seed, heads, scale, keep):
    """The function the kernels compute, in XLA, packed in and out."""
    return _merge_heads(_attention_with_prob_dropout(
        _split_heads(q, heads), _split_heads(k, heads),
        _split_heads(v, heads), jnp.repeat(mask, heads, axis=0), scale,
        1.0 - keep, seed[:2], first_head=seed[2] * np.uint32(heads)), heads)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _train_fwd_xla(q, k, v, mask, seed, heads, scale, keep):
    """What the forward kernel returns, in XLA: (o, lse)."""
    s = jnp.einsum("bqd,bkd->bqk", _split_heads(q, heads),
                   _split_heads(k, heads),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(jnp.repeat(mask, heads, axis=0)[:, None, :] > 0, s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1).reshape(q.shape[0], heads, 1, -1)
    return _train_xla(q, k, v, mask, seed, heads, scale, keep), lse


@functools.partial(jax.jit, static_argnames=_STATICS)
def _train_bwd_xla(q, k, v, mask, seed, o, lse, do, heads, scale, keep):
    """What the backward kernel returns, in XLA (recomputed from the
    inputs; the saved o and lse are the kernels' residuals)."""
    del o, lse
    _, vjp = jax.vjp(lambda q_, k_, v_: _train_xla(
        q_, k_, v_, mask, seed, heads, scale, keep), q, k, v)
    return vjp(do)


def _on_tpu_else(kernel, reference, shard, *operands):
    """`kernel` or the XLA `reference` of the same function on operands
    (q, k, v, mask, key_words, ...), one call or one a batch shard as
    `kernel_route.admit` said (`shard`): the first global row a call
    holds goes into its seed, so the mask does not depend on the mesh."""
    def run(first_row, q, k, v, mask, key_words, *rest):
        return kernel_route.dispatch(
            kernel, reference, q, k, v, mask, _seed(key_words, first_row),
            *rest, interpret=kernel_route.interpret())

    return kernel_route.per_batch_shard(run, shard, *operands,
                                        replicated=(4,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _attend_train(q, k, v, mask, key_words, heads, scale, keep, shard=True):
    """Self-attention with dropout on the probabilities through the fused
    kernels: q, k, v (B, S, H*D); mask (B, S); key_words uint32[2]; shard
    from `kernel_route.admit`."""
    return _attend_train_fwd(q, k, v, mask, key_words, heads, scale, keep,
                             shard)[0]


def _attend_train_fwd(q, k, v, mask, key_words, heads, scale, keep, shard):
    statics = dict(heads=heads, scale=scale, keep=keep)
    o, lse = _on_tpu_else(
        functools.partial(_attention_train_fwd_pallas, **statics),
        functools.partial(_train_fwd_xla, **statics),
        shard, q, k, v, mask, key_words)
    return o, (q, k, v, mask, key_words, o, lse)


def _attend_train_bwd(heads, scale, keep, shard, res, do):
    q, k, v, mask, key_words, o, lse = res
    statics = dict(heads=heads, scale=scale, keep=keep)
    dq, dk, dv = _on_tpu_else(
        functools.partial(_attention_train_bwd_pallas, **statics),
        functools.partial(_train_bwd_xla, **statics),
        shard, q, k, v, mask, key_words, o, lse, do)
    return (dq, dk, dv, jnp.zeros_like(mask),
            np.zeros(key_words.shape, jax.dtypes.float0))


_attend_train.defvjp(_attend_train_fwd, _attend_train_bwd)


def _fused_train_shape(heads, sq, sk, d, causal):
    """The calls the fused kernels cover: self-attention shaped as BERT's
    is, the heads filling whole 128-lane blocks.  Everything else in
    training (causal, cross, ragged lengths, other head sizes) stays on
    `_attention_with_prob_dropout`."""
    return (not causal and sq == sk and sq % 128 == 0
            and sq <= _FUSED_MAX_SEQ and d in (64, 128, 256)
            and (heads * d) % 128 == 0)


# ---------------------------------------------------------------------------
# Causal self-attention without dropout, over the whole triangle or under
# a sliding window: a decoder's layers
# ---------------------------------------------------------------------------

def _split_to_heads(x, heads):
    """(B, S, heads * D) -> (B, heads, S, D)."""
    b, s, u = x.shape
    return x.reshape(b, s, heads, u // heads).transpose(0, 2, 1, 3)


def _repeat_kv(x, heads):
    """(B, Hkv, S, D) -> (B, heads, S, D): each key/value head serves
    heads // Hkv consecutive query heads."""
    groups = heads // x.shape[1]
    return x if groups == 1 else jnp.repeat(x, groups, axis=1)


def _causal_xla(q, k, v, scale):
    b, h, s, _ = q.shape
    flat = [x.reshape(b * h, s, x.shape[-1])
            for x in (q, _repeat_kv(k, h), _repeat_kv(v, h))]
    return dot_product_attention_ref(*flat, None, scale,
                                     causal=True).reshape(b, h, s, -1)


def _causal_flash_shape(heads, kv_heads, sq, sk, d, d_v=None):
    """Heads of `d` for queries and keys and of `d_v` for values (None:
    `d`): the values fill whole 128-lane blocks, queries and keys whole
    or half ones (latent attention's 192 = 128 + its 64 rotary); or all
    three are 64 wide, half a block each (upstream's kernels cut their
    128-lane statistics to the output's 64)."""
    d_v = d if d_v is None else d_v
    return (sq == sk and sq % 128 == 0 and heads % kv_heads == 0
            and (d_v % 128 == 0 and d % 64 == 0 or d == d_v == 64))


def _window_xla(q, k, v, scale, window):
    """The band 0 <= i - j < window in plain XLA: q (B, H, S, D), k
    (B, Hkv, S, D) and v (B, Hkv, S, Dv); scores and softmax in float32.  Queries in blocks
    of `window`, each against its own block of keys and the one before
    it, so the scores are (B, H, S, 2 window) where a dense mask would
    hold (B, H, S, S): the form a mesh of several devices and the CPU
    take stays O(S x window) in memory and work, as the kernels are."""
    b, h, s, _ = q.shape
    n = -(-s // window)

    def blocks(x):
        """(B, H, S, D) -> (B, H, n, window, D), zeros after S: keys no
        query of the S looks ahead to, queries cut off again below."""
        x = jnp.pad(x, ((0, 0), (0, 0), (0, n * window - s), (0, 0)))
        return x.reshape(b, h, n, window, x.shape[-1])

    def with_previous(x):
        before = jnp.pad(x[:, :, :-1],
                         ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))
        return jnp.concatenate([before, x], axis=3)

    score = jnp.einsum("bhnqd,bhnkd->bhnqk", blocks(q),
                       with_previous(blocks(_repeat_kv(k, h))),
                       preferred_element_type=jnp.float32) * scale
    # key c of a block's 2 window is position c - window of the block
    ahead = jnp.arange(window)[:, None] + window - jnp.arange(2 * window)
    exists = (jnp.arange(n)[:, None, None] > 0) | (
        jnp.arange(2 * window) >= window)           # block 0 has no before
    score = jnp.where((ahead >= 0) & (ahead < window) & exists, score,
                      -1e30)
    prob = jax.nn.softmax(score, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhnqk,bhnkd->bhnqd", prob,
                     with_previous(blocks(_repeat_kv(v, h))))
    return out.reshape(b, h, n * window, -1)[:, :, :s]


def _splash_blocks(s, window):
    """(rows of queries and of keys a block, rows of keys a product) of
    upstream's splash kernels, from the mask's kind and S.  Under a
    window: 512 where it divides S, so that a query block visits the 2
    key blocks its band touches; on the v5e at the window of 512,
    forward + backward of one layer through upstream's three kernels
    (PERF.md, PR 31): 512 23.4 ms, 256 (3 blocks, 768 keys for the 512 a
    query sees, but three times the grid steps) 33.5, 128 69.3, (1024,
    512) 30.1; the forward alone (PERF.md, PR 50) at laguna's shape /
    phi4's (`_band_blocks`): 512 5.81 / 3.69 ms, (512, 256) 7.17 / 4.52,
    (1024, 512) 8.30 / 5.26, (1024, 256) 8.67 / 5.49.  Only the forward
    of a windowed call runs at these blocks: its backward is a kernel
    of the repo's own at blocks of its own (`_band_blocks`).  The
    whole triangle is mostly whole blocks and takes 1024 rows in
    products of 512 keys where 1024 divides S; one full layer of
    `laguna_xs2_s8192` (B 2, 48 heads over 8, S 8192) on the v5e
    (PERF.md, PR 38): 512 70.2 ms, (1024 queries, 512 keys) 64.2, (512,
    1024) 62.2, (2048, 512) 65.3, 1024 in one product 58.9, 1024 in
    products of 512 57.6 (the least in each of the three kernels),
    against 83.2 through upstream's flash kernels at 512 on repeated
    heads.  The backward of such a call is one kernel of the repo's own
    at the same blocks (`_fused_backward`)."""
    if window is None and s % 1024 == 0:
        return 1024, 512
    rows = next(n for n in (512, 256, 128) if s % n == 0)
    return rows, rows


def _fused_backward(window, s, d, d_v, groups):
    """The form of a call's backward, from what `_causal_splash` sees (the
    mask's kind, S, the head sizes of queries / keys and of values, the
    query heads a key/value head): "fused", `mx_causal_attention_bwd` over
    the triangle; "band", `mx_window_attention_bwd` over a window; or
    "split", upstream's two kernels (dK/dV and dQ).  Either kernel of the
    repo's own forms each score block once where upstream's two form it
    twice.  The triangle takes its kernel where the 1,024-row block
    divides S, a window where `_band_blocks` finds a block that divides
    S and whose sums fit the kernel's VMEM (at a window of 512, every
    group up to 176 query heads a key/value head of 128); EVA's mask,
    and an S the blocks do not divide, keep upstream's split backward.
    Every size a cell runs at S 8192 read faster fused on the v5e
    (PERF.md, PR 48), one layer forward + backward, ms, upstream's
    split kernels / upstream's fused form with its bfloat16 partials /
    the one kernel: 48 heads over 8 of 128 (B 2) 54.90 / 45.69 / 40.70;
    32 heads of 192 and 128 (B 2) 58.08 / 50.66 / 44.90; a group of
    one, 16 heads of 128 (B 1) 8.99 / 7.58 / 6.87; 32 over 8 of 64 (B 2)
    37.66 / 31.44 / 27.85; 32 over 2 of 128 (B 2) 36.32 / 30.13 /
    27.11.  Under the window of 512
    (PERF.md, PR 50), one layer forward + backward, split / band: 64
    heads over 8 of 128 (B 2, S 8192) 22.14 / 13.58; 40 over 20, q and
    k of 64 and v of 128 (B 1, S 16384) 14.76 / 9.43: a group of 2
    and 64-lane queries and keys gain as a group of 8 of 128 does, so
    neither the head sizes nor the group decide anything yet."""
    if window is None:
        return "fused" if s % 1024 == 0 else "split"
    return "band" if _band_blocks(s, window, d, d_v, groups) else "split"


# upstream's value for a masked score: exp(it - logsumexp) is 0
_MASKED = -0.7 * float(np.finfo(np.float32).max)


def _triangle_walk(blocks, groups):
    """int32 (4, steps): the (key block, query head of the group, query
    block) of every step of `mx_causal_attention_bwd` over one key/value
    head, and whether the step before it wrote the same dQ block.  Key
    block outermost, so dK and dV of a key block are one sum over the
    group's heads and the query blocks at or after it: 36 x groups
    steps at 8 blocks where the square has 64."""
    steps = [(kb, g, qb) for kb in range(blocks) for g in range(groups)
             for qb in range(kb, blocks)]
    again = [0] + [int(a[1:] == b[1:]) for a, b in zip(steps, steps[1:])]
    return np.concatenate([np.asarray(steps, np.int32).T,
                           np.asarray([again], np.int32)])


def _score_products(k_ref, v_ref, q_ref, do_ref, stat_ref, dk_sum, dv_sum,
                    keys, seen, keep, head=()):
    """The five products of one tile of scores, the `keys` of the step's
    key block (sublanes) against its `seen` queries (lanes), formed once:
    dV's and dK's parts are added to the float32 sums, dQ's part (seen
    rows, d) float32 is returned.  `keep` (None: a whole tile) gives, from
    the tile's shape, where a query sees a key; `head`: the index of the
    query head where a block of q, dO and the statistics holds several."""
    kc, vc = k_ref[keys, :], v_ref[keys, :]
    qs, dos = q_ref[(*head, seen, slice(None))], do_ref[(*head, seen,
                                                         slice(None))]
    st = jax.lax.dot_general(kc, qs, _NT,
                             preferred_element_type=jnp.float32)
    if keep is not None:
        st = jnp.where(keep(st.shape), st, _MASKED)
    pt = jnp.exp(st - stat_ref[(*head, slice(None, 1), seen)])  # logsumexp
    dv_sum[keys, :] += jnp.dot(pt.astype(dos.dtype), dos,
                               preferred_element_type=jnp.float32)
    dpt = jax.lax.dot_general(vc, dos, _NT,
                              preferred_element_type=jnp.float32)
    dst = ((dpt - stat_ref[(*head, slice(1, None), seen)])      # di
           * pt).astype(qs.dtype)
    dk_sum[keys, :] += jnp.dot(dst, qs, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(dst, kc, _TN,
                               preferred_element_type=jnp.float32)


def _at_or_after(shape):
    """Where the query (lane) is at or after the key (sublane)."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            >= jax.lax.broadcasted_iota(jnp.int32, shape, 0))


def _causal_bwd_vmem_bytes(rows, compute, d, d_v, itemsize):
    """The scoped VMEM `mx_causal_attention_bwd` asks Mosaic for, from
    the tiles it holds (a head of 192 lies in 256 lanes): the operand
    and result blocks q, dO, k, v, dK, dV double buffered, the float32
    sums of dK and dV, the step's part of dQ and the two dQ blocks, some
    eight (keys a product, rows) float32 tiles (scores, probabilities,
    dP, dS and their casts), and as much again for what Mosaic allocates
    itself.  43 MiB at (1024, 512) and heads of 128 or 64, 50 at 192 + 128:
    the v5e has 128."""
    lanes = lambda n: -(-n // 128) * 128
    d, d_v = lanes(d), lanes(d_v)
    blocks = 2 * 3 * rows * (d + d_v) * itemsize
    sums = rows * (d + d_v) * 4 + 3 * rows * d * 4
    tiles = 8 * compute * rows * 4
    return 2 * (blocks + sums + tiles)


def _causal_bwd_pallas(q, k, v, do, lse, di, rows, compute, interpret):
    """`mx_causal_attention_bwd`: dQ (float32), dK and dV of causal
    attention from ONE pass over the blocks the triangle touches.  q (B,
    Hkv, G, S, D) scaled already, k (B, Hkv, S, D), v (B, Hkv, S, Dv),
    dO (B, Hkv, G, S, Dv), the forward kernel's logsumexp and di =
    rowsum(dO * o), both (B, Hkv, G, S) float32; blocks of `rows`
    queries and keys, `compute` keys a product.

    Grid (B, Hkv, steps of `_triangle_walk`).  A step forms the scores
    of one (key block, query block) pair of one query head once, keys on
    sublanes and queries on lanes as upstream's dK/dV kernel does (the
    row statistics broadcast as rows, dV and dK need no transposed
    operand), the diagonal blocks masked from an iota and without the
    queries before a chunk's first key (a quarter of such a block's
    products; one layer 41.8 -> 40.7 ms at 48 heads over 8 on the v5e),
    and gives all three gradients their part of it:

      * dK, dV: float32 VMEM sums over the group's heads and the query
        blocks of one key block, written once, in the operands' type;
      * dQ: ONE float32 array the size of q in HBM, never zero-filled:
        the step reads its block (but at key block 0, every query
        block's first visit), adds its part and writes it back, by
        copies of its own that overlap the products (the read starts
        with the step, the write runs into the next step).  Two VMEM
        blocks in turn; a block is free again once the write of two
        steps before has landed, which is waited for one step before.
        A read must see the last write of its block: a block comes back
        a whole sweep of heads and query blocks later, except in a
        group of one, where the last two key blocks visit the last
        query block in consecutive steps; there (`again` in the walk)
        the write is waited for BEFORE the read starts.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, kv, groups, s, d = q.shape
    d_v = v.shape[-1]
    blocks, chunks = s // rows, rows // compute
    # a copy of the kernel's own cuts whole 128-lane tiles alone: dQ's
    # blocks lie in as many lanes as XLA's layout of a head gives them
    d_whole = -(-d // 128) * 128
    walk = _triangle_walk(blocks, groups)
    steps = walk.shape[1]

    def kernel(walk_ref, q_ref, k_ref, v_ref, do_ref, stat_ref, dq_hbm,
               dk_ref, dv_ref, dk_sum, dv_sum, dq_part, dq_even, dq_odd,
               read_sem, write_sem):
        bi, hi, step = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        kb, g, qb = walk_ref[0, step], walk_ref[1, step], walk_ref[2, step]
        again = walk_ref[3, step] == 1
        mine = dq_hbm.at[bi, hi, g, qb]
        turns = (dq_even, dq_odd)

        def read(i):
            return pltpu.make_async_copy(mine, turns[i], read_sem.at[i])

        def write(i):
            return pltpu.make_async_copy(turns[i], mine, write_sem.at[i])

        def in_turn(when, body):
            """`body(i)` where `when` holds, i the step's VMEM block of
            dQ: a static index (Mosaic cuts no 64- or 192-lane array by
            a dynamic one)."""
            for i in range(2):
                pl.when(jnp.logical_and(when, jax.lax.rem(step, 2) == i))(
                    functools.partial(body, i))

        # the write that the step before started, from the other block
        in_turn(again, lambda i: write(1 - i).wait())
        in_turn(kb > 0, lambda i: read(i).start())

        @pl.when(jnp.logical_and(g == 0, qb == kb))
        def _():
            dk_sum[...] = jnp.zeros_like(dk_sum)
            dv_sum[...] = jnp.zeros_like(dv_sum)

        def products(diagonal):
            """The five products of the step's block pair, a chunk of
            keys at a time, keys on sublanes: the diagonal block is cut
            by the triangle, and the queries before a chunk's first key
            see none of it, so they stay out of its products."""
            for c in range(chunks):
                seen = pl.ds(c * compute, rows - c * compute) if diagonal \
                    else pl.ds(0, rows)
                # query seen.start + j sees key keys.start + i
                part = _score_products(
                    k_ref, v_ref, q_ref, do_ref, stat_ref, dk_sum, dv_sum,
                    pl.ds(c * compute, compute), seen,
                    _at_or_after if diagonal else None)
                if c == 0:
                    dq_part[...] = part
                else:
                    dq_part[seen, :] += part

        pl.when(qb > kb)(functools.partial(products, False))
        pl.when(qb == kb)(functools.partial(products, True))

        in_turn(jnp.logical_and(step > 0, jnp.logical_not(again)),
                lambda i: write(1 - i).wait())

        def add(i):
            read(i).wait()
            turns[i][:, :d] += dq_part[...]

        def put(i):
            turns[i][:, :d] = dq_part[...]

        in_turn(kb > 0, add)
        in_turn(kb == 0, put)
        in_turn(True, lambda i: write(i).start())

        @pl.when(jnp.logical_and(g == groups - 1, qb == blocks - 1))
        def _():
            dk_ref[...] = dk_sum[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_sum[...].astype(dv_ref.dtype)

        in_turn(step == steps - 1, lambda i: write(i).wait())

    at_query = lambda bi, hi, step, walk: (bi, hi, walk[1, step],
                                           walk[2, step], 0)
    at_key = lambda bi, hi, step, walk: (bi, hi, walk[0, step], 0)
    heads = lambda width: pl.BlockSpec((None, None, None, rows, width),
                                       at_query)
    keys = lambda width: pl.BlockSpec((None, None, rows, width), at_key)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, kv, steps),
            in_specs=[heads(d), keys(d), keys(d_v), heads(d_v),
                      # logsumexp and di of a query block, as two rows
                      pl.BlockSpec((None, None, None, 2, rows),
                                   lambda bi, hi, step, walk: (
                                       bi, hi, walk[1, step], 0,
                                       walk[2, step]))],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), keys(d),
                       keys(d_v)],
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                            pltpu.VMEM((rows, d_v), jnp.float32),
                            pltpu.VMEM((rows, d), jnp.float32),
                            pltpu.VMEM((rows, d_whole), jnp.float32),
                            pltpu.VMEM((rows, d_whole), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(
                       (b, kv, groups, blocks, rows, d_whole), jnp.float32),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_causal_bwd_vmem_bytes(
                rows, compute, d, d_v, q.dtype.itemsize)),
        interpret=interpret,
        name="mx_causal_attention_bwd",
    )(jnp.asarray(walk), q, k, v, do, jnp.stack([lse, di], axis=-2))
    return dq[..., :d].reshape(q.shape), dk, dv


def _band_reach(rows, window):
    """The key blocks before its own that a query block's band touches."""
    return -(-(window - 1) // rows)


def _band_walk(blocks, reach):
    """int32 (2, steps): the (key block, query block) of every step of
    `mx_window_attention_bwd` over one key/value head: the block pairs
    with a pair inside the band, `reach` key blocks before a query
    block's own.  Key block outermost, so dK and dV of a key block are
    one sum over consecutive steps; its query blocks from the farthest
    back to its own, so the diagonal, the last of a dQ block's `reach +
    1` visits, ends the key block's steps."""
    return np.asarray([(kb, qb) for kb in range(blocks) for qb in range(
        min(kb + reach, blocks - 1), kb - 1, -1)], np.int32).T


def _band_tiles(offset, rows, compute, window):
    """((first key, first query, queries, cut below, cut above), ...): for
    each chunk of `compute` keys of the block pair `offset` blocks apart
    that some query sees, the queries (in whole chunks) that see one of
    its keys inside 0 <= i - j < window, and which of the band's two
    edges cuts that tile: i - j of query row r and key row c of the pair
    is offset * rows + r - c wherever the pair lies, so this is known
    when the kernel is traced."""
    tiles = []
    for c0 in range(0, rows, compute):
        # query row r is ahead + r past the chunk's first key, and sees a
        # key of the chunk where 0 <= ahead + r < window + compute - 1
        ahead = offset * rows - c0
        first = max(0, -ahead // compute * compute)
        last = min(rows, -(-(window + compute - 1 - ahead) // compute)
                   * compute)
        if first < last:
            tiles.append((c0, first, last - first,
                          ahead + first - (compute - 1) < 0,
                          ahead + last - 1 >= window))
    return tuple(tiles)


# the scoped VMEM `mx_window_attention_bwd` may ask for: the v5e has 128 MiB
_BAND_VMEM = 100 << 20


def _band_vmem_bytes(rows, compute, window, d, d_v, groups, itemsize):
    """The scoped VMEM `mx_window_attention_bwd` asks Mosaic for, from
    what it holds (a head of 64 lies in 128 lanes): the blocks of q, dO
    and dQ of all the group's heads and of k, v, dK and dV double
    buffered, the statistics, the float32 sums of dK and dV and of the
    `reach + 1` dQ blocks a head has in flight, some eight float32
    tiles of scores, and 8 MiB for what Mosaic allocates itself."""
    lanes = lambda n: -(-n // 128) * 128
    d, d_v = lanes(d), lanes(d_v)
    held = _band_reach(rows, window) + 1
    blocks = 2 * rows * itemsize * (groups * (2 * d + d_v) + 2 * (d + d_v))
    stats = 2 * groups * 8 * rows * 4
    sums = rows * (d + d_v) * 4 + groups * held * rows * d * 4
    tiles = 8 * compute * min(rows, window + 2 * compute) * 4
    return blocks + stats + sums + tiles + (8 << 20)


def _band_blocks(s, window, d, d_v, groups):
    """(rows of queries and of keys a block, rows of keys a product) of
    `mx_window_attention_bwd`, or None where no block that divides S
    fits its VMEM: the largest block that does, in products of 128
    keys.  One window layer's backward kernel alone on the v5e, ms
    (PERF.md, PR 50), at laguna's shape (B 2, 64 heads over 8 of 128, S
    8192, window 512) / phi4's (B 1, 40 over 20, q and k of 64 and v of
    128, S 16384, window 512), where upstream's two kernels read 12.85 /
    8.30: blocks of 512 rows in products of 128 keys 7.20 / 5.98, 1,024
    5.85 / 4.99, 2,048 5.33 / 4.64, 4,096 5.16 / 4.62 (but 33 MiB of
    held dQ at a group of 8); in products of 256 keys 6.87 / 5.73, 6.12
    / 5.13, 5.97 / 5.04 and 5.94 / 5.32, of 512 keys 7.43 / 6.20 at
    2,048.  Earlier forms of the kernel at (2,048, 128): a product over
    ONE tile of 128 queries at a time, only the tiles an edge cuts
    masked, 8.25 / 6.49 (6.69 / 5.74 at tiles of 256: small products
    cost more than the masks they save); the group's heads as grid
    steps instead of a loop in the step 5.52 / 4.73."""
    for rows in (2048, 1024, 512, 256, 128):
        if s % rows == 0 and _band_vmem_bytes(
                rows, 128, window, d, d_v, groups, 2) <= _BAND_VMEM:
            return rows, 128
    return None


def _window_bwd_pallas(q, k, v, do, lse, di, scale, window, rows, compute,
                       interpret):
    """`mx_window_attention_bwd`: dQ, dK and dV of attention over the band
    0 <= i - j < window from ONE pass over the blocks the band touches.
    Operands as `_causal_bwd_pallas`'s; dQ comes back in q's type, times
    `scale`; blocks of `rows` queries and keys, `compute` keys a product.

    Grid (B, Hkv, steps of `_band_walk`).  A step takes one (key block,
    query block) pair and loops over the group's query heads, k and v
    read once for all of them.  For a head it forms the scores of the
    pair once, as `_score_products` does for the triangle, a chunk of
    keys at a time against the queries `_band_tiles` gives for the
    pair's offset: the queries that see none of the chunk are in no
    product, and an edge of the band is an iota's compare only in the
    chunks it crosses.  All three sums are float32 in VMEM and leave
    once, in the operands' type:

      * dK, dV of a key block over the group's heads and the query
        blocks at and after it, the key block's consecutive steps;
      * dQ of a (head, query block) over the `reach + 1` key blocks of
        its band, which the walk visits in as many key blocks' steps:
        `groups x (reach + 1)` blocks are held, a head's by the query
        block modulo `reach + 1`.  The last visit is the diagonal, the
        key block's last step, and dQ's block index is the key block's
        over all its steps, so the block is written when it is whole.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, kv, groups, s, d = q.shape
    d_v = v.shape[-1]
    blocks = s // rows
    reach = _band_reach(rows, window)
    held = reach + 1
    # Mosaic cuts no 64-lane array by a dynamic index: the held dQ blocks
    # lie in whole 128-lane tiles
    d_whole = -(-d // 128) * 128
    walk = _band_walk(blocks, reach)

    def kernel(walk_ref, q_ref, k_ref, v_ref, do_ref, stat_ref, dq_ref,
               dk_ref, dv_ref, dk_sum, dv_sum, dq_sum):
        step = pl.program_id(2)
        kb, qb = walk_ref[0, step], walk_ref[1, step]
        offset = qb - kb

        @pl.when(qb == jnp.minimum(kb + reach, blocks - 1))
        def _():
            dk_sum[...] = jnp.zeros_like(dk_sum)
            dv_sum[...] = jnp.zeros_like(dv_sum)

        def head(g, _):
            mine = dq_sum.at[g * held + jax.lax.rem(qb, held)]

            @pl.when(jnp.logical_or(offset == reach, kb == 0))
            def _():
                mine[...] = jnp.zeros_like(mine)

            def products(o):
                for c0, r0, n, below, above in _band_tiles(o, rows, compute,
                                                           window):
                    def keep(shape, first=o * rows + r0 - c0, below=below,
                             above=above):
                        ahead = (       # i - j of lane r and sublane c
                            jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                            - jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                            + first)
                        if below and above:
                            return jnp.logical_and(ahead >= 0, ahead < window)
                        return ahead >= 0 if below else ahead < window

                    seen = pl.ds(r0, n)
                    mine[seen, :d] += _score_products(
                        k_ref, v_ref, q_ref, do_ref, stat_ref, dk_sum,
                        dv_sum, pl.ds(c0, compute), seen,
                        keep if below or above else None, (g,))

            for o in range(held):
                pl.when(offset == o)(functools.partial(products, o))

            @pl.when(offset == 0)
            def _():
                dq_ref[g] = (mine[:, :d] * scale).astype(dq_ref.dtype)

        jax.lax.fori_loop(0, groups, head, None)

        @pl.when(offset == 0)
        def _():
            dk_ref[...] = dk_sum[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_sum[...].astype(dv_ref.dtype)

    def heads(width, block):
        """The (G, rows, width) block of a (B, Hkv, G, S, width) array at
        the step's query block or (dQ) its key block."""
        return pl.BlockSpec(
            (None, None, groups, rows, width),
            lambda bi, hi, step, walk: (bi, hi, 0, walk[block, step], 0))

    keys = lambda width: pl.BlockSpec(
        (None, None, rows, width),
        lambda bi, hi, step, walk: (bi, hi, walk[0, step], 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, kv, walk.shape[1]),
            in_specs=[heads(d, 1), keys(d), keys(d_v), heads(d_v, 1),
                      # logsumexp and di of a query block, as two rows
                      pl.BlockSpec((None, None, groups, 2, rows),
                                   lambda bi, hi, step, walk: (
                                       bi, hi, 0, 0, walk[1, step]))],
            out_specs=[heads(d, 0), keys(d), keys(d_v)],
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                            pltpu.VMEM((rows, d_v), jnp.float32),
                            pltpu.VMEM((groups * held, rows, d_whole),
                                       jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_band_vmem_bytes(
                rows, compute, window, d, d_v, groups, q.dtype.itemsize)),
        interpret=interpret,
        name="mx_window_attention_bwd",
    )(jnp.asarray(walk), q, k, v, do, jnp.stack([lse, di], axis=-2))


def _splash_forward(q, k, v, scale, window, interpret, backward, **how):
    """Upstream's splash multi-query kernel at `_splash_blocks`' blocks,
    vmapped over batch and key/value heads, on q (B, H, S, D) scaled
    here, k (B, Hkv, S, D) and v (B, Hkv, S, Dv) as they are: (scaled q
    as (B, Hkv, G, S, D), what the kernel returns).  `backward`: with
    upstream's two backward kernels under upstream's custom VJP, for
    which the mask's blocks are laid out twice more at trace time;
    `how`: `make_splash_mqa_single_device`'s further arguments."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    b, h, s, d = q.shape
    kv = k.shape[1]
    groups = h // kv
    rows, compute = _splash_blocks(s, window)
    blocks = dict(block_q=rows, block_kv=rows, block_kv_compute=compute)
    if backward:
        blocks.update(block_q_dkv=rows, block_kv_dkv=rows,
                      block_kv_dkv_compute=compute, block_q_dq=rows,
                      block_kv_dq=rows)
    mask = (sa.CausalMask((s, s)) if window is None
            else sa.LocalMask((s, s), (window - 1, 0), 0))
    kernel = sa.make_splash_mqa_single_device(
        sa.MultiHeadMask([mask] * groups),
        block_sizes=sa.BlockSizes(**blocks), interpret=interpret, **how)
    # the kernels apply no scale of their own; in float32, so that the
    # scale is not rounded to the operands' type before it is applied
    q = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(
        b, kv, groups, s, d)
    return q, jax.vmap(jax.vmap(kernel))(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _causal_fused(q, k, v, scale, window, name, interpret):
    """The whole triangle or a window's band with the backward of the
    repo's own: upstream's forward kernel, and `mx_causal_attention_bwd`
    or `mx_window_attention_bwd` as the rule."""
    out = _splash_forward(q, k, v, scale, window, interpret, False)[1]
    return out.reshape(q.shape[:3] + out.shape[-1:])


def _causal_fused_fwd(q, k, v, scale, window, name, interpret):
    scaled, (out, (lse,)) = _splash_forward(
        q, k, v, scale, window, interpret, False, save_residuals=True)
    # named, so that a recomputed segment keeps them (ops/residuals.py)
    out = jax.ad_checkpoint.checkpoint_name(out, name)
    lse = jax.ad_checkpoint.checkpoint_name(lse, name)
    return (out.reshape(q.shape[:3] + out.shape[-1:]),
            (scaled, k, v, out, lse))


def _causal_fused_bwd(scale, window, name, interpret, res, do):
    scaled, k, v, out, lse = res
    do = do.reshape(out.shape)
    di = jnp.einsum("bhgsd,bhgsd->bhgs", out.astype(jnp.float32),
                    do.astype(jnp.float32))
    b, kv, groups, s, d = scaled.shape
    if window is not None:
        dq, dk, dv = _window_bwd_pallas(
            scaled, k, v, do, lse, di, scale, window,
            *_band_blocks(s, window, d, v.shape[-1], groups), interpret)
        return dq.reshape(b, kv * groups, s, d), dk, dv
    dq, dk, dv = _causal_bwd_pallas(scaled, k, v, do, lse, di,
                                    *_splash_blocks(s, None), interpret)
    # the float32 sum over the key blocks leaves as q's type after ONE
    # rounding, the scale applied before it
    return ((dq * scale).astype(scaled.dtype).reshape(b, kv * groups, s, d),
            dk, dv)


_causal_fused.defvjp(_causal_fused_fwd, _causal_fused_bwd)


def _causal_splash(q, k, v, scale, window=None, interpret=False,
                   name=None):
    """Upstream's splash multi-query forward kernel (one online-softmax
    pass over the blocks the mask touches) over the band 0 <= i - j <
    window, or over the whole causal triangle (`window` None): q (B, H,
    S, D), k (B, Hkv, S, D) and v (B, Hkv, S, Dv), Dv the output's head
    size.  The H // Hkv query heads of one key/value head are one
    multi-query call, vmapped over batch and key/value heads: k and v
    go in as they are, and dK, dV come out summed over the group.  The
    forward rule names its output and its (H, S) float32 logsumexp by
    the route (`name`; None: `flash_causal` or `splash_window` by the
    mask).  The backward, by `_fused_backward`: one kernel of the repo's
    own under a custom VJP of the repo's own (`mx_causal_attention_bwd`
    over the triangle, `mx_window_attention_bwd` over a band), or
    upstream's two kernels (dK/dV and dQ) under upstream's."""
    b, h, s, d = q.shape
    name = name or ("flash_causal" if window is None else "splash_window")
    if _fused_backward(window, s, d, v.shape[-1], h // k.shape[1]) != "split":
        return _causal_fused(q, k, v, scale, window, name, interpret)
    out = _splash_forward(q, k, v, scale, window, interpret, True,
                          residual_checkpoint_name=name)[1]
    return out.reshape(b, h, s, -1)


@functools.partial(jax.jit, static_argnames=("scale", "window", "interpret",
                                             "name"))
def _attend_causal_once(q, k, v, scale, window, interpret, name):
    xla = (functools.partial(_causal_xla, scale=scale) if window is None
           else functools.partial(_window_xla, scale=scale, window=window))
    return kernel_route.dispatch(
        functools.partial(_causal_splash, scale=scale, window=window,
                          interpret=interpret, name=name),
        xla, q, k, v, interpret=interpret)


def _attend_causal(q, k, v, scale, window, interpret, name=None):
    """q (B, H, S, D), k (B, Hkv, S, D) and v (B, Hkv, S, Dv) -> (B, H,
    S, Dv), causal, under a sliding `window` or none (None): the splash
    kernels or the XLA form.  Jitted, so that a stack of layers traces
    and lowers the kernels once a kind of layer; the form the kernels'
    backward takes is counted outside the jit, once a call whose
    backward is traced (`backward_counts`)."""
    form = _fused_backward(window, q.shape[2], q.shape[3], v.shape[3],
                           q.shape[1] // k.shape[1])
    return kernel_route.counted_backward(
        _attend_causal_once(q, k, v, scale, window, interpret, name),
        "attention_backward", form)


# fused_train / (fused_train + xla_dropout) is the share of training calls
# that engaged the fused route
ROUTES = ("fused_train", "xla_dropout", "kernel_infer", "reference",
          "flash_causal", "splash_window", "eva_splash", "eva_xla",
          "latent_splash", "latent_xla", "diff_splash",
          "diff_window_splash", "diff_xla")
kernel_route.declare("attention", ROUTES)
_FLASH_CAUSAL = kernel_route.Kernel("attention", "flash_causal", None)
_FUSED_TRAIN = kernel_route.Kernel("attention", "fused_train", "xla_dropout",
                                   kernel_route.BATCH_SHARDS)
# never asked the mesh (ROADMAP.md Queue 3 item 3: no cell runs it)
_KERNEL_INFER = kernel_route.Kernel("attention", "kernel_infer", "reference",
                                    kernel_route.ANY_MESH)
_SPLASH_WINDOW = kernel_route.Kernel("attention", "splash_window",
                                     "reference")


# the form of a causal splash call's backward (`_fused_backward`), counted
# where that backward is traced
kernel_route.declare("attention_backward", ("fused", "band", "split"))


def route_counts():
    """{route: calls traced through it} since import: routes chosen at
    trace time, not kernels run.  Read it before and after to count."""
    return kernel_route.counts("attention")


def backward_counts():
    """{"fused", "band", "split"}: the causal and window splash calls
    whose backward was traced since import, by the form it took: the
    repo's one kernel over the triangle, its one kernel over a window's
    band, or upstream's two.  `route_counts()`'s sibling."""
    return kernel_route.counts("attention_backward")


def _splash_kept(b, h, s, d, dtype):
    """What a splash route's forward rule names (`kernel_route.choose`'s
    `kept`): o (b, h, s, d) and its float32 logsumexp rows."""
    return 2, b * h * s * (d * np.dtype(dtype).itemsize + 4)


@register_op("dot_product_attention",
             aliases=("FusedAttention", "_contrib_dot_product_attention"))
def _dot_product_attention(query, key, value, valid_mask=None, rng_key=None,
                           num_heads=1, scale=None, dropout=0.0,
                           causal=False, num_kv_heads=0, _train=False):
    """Multi-head scaled-dot-product attention.

    query/key/value: (B, S, U) with U = num_heads * head_dim, or already
    head-split (B, H, S, D).  num_kv_heads (0: as num_heads): key and
    value carry that many heads, each serving num_heads // num_kv_heads
    query heads.  valid_mask: (B, S_k) 1/0 key-validity mask
    (sequence lengths), or None.  dropout: rate applied to the attention
    probabilities in train mode (key auto-threaded by the frontend).
    Returns the same layout as the input.
    """
    packed = query.ndim == 3
    if packed:
        b, sq, u = query.shape
        h = num_heads
        d = u // h
        sk = key.shape[1]
        h_kv = num_kv_heads or h
    else:
        b, h, sq, d = query.shape
        sk = key.shape[2]
        h_kv = key.shape[1]
    if h % h_kv:
        raise ValueError(f"dot_product_attention: {h} query heads over "
                         f"{h_kv} key/value heads")
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    dropping = _train and dropout > 0.0 and rng_key is not None
    if kernel_route.choose(
            _FLASH_CAUSAL, causal and not dropping and valid_mask is None
            and _causal_flash_shape(h, h_kv, sq, sk, d), b,
            kept=_splash_kept(b, h, sq, d, query.dtype)):
        if packed:
            qh, kh, vh = (_split_to_heads(x, n) for x, n in (
                (query, h), (key, h_kv), (value, h_kv)))
        else:
            qh, kh, vh = query, key, value
        oh = _attend_causal(qh, kh, vh, float(scale), None,
                            kernel_route.interpret())
        return oh.transpose(0, 2, 1, 3).reshape(b, sq, h * d) if packed \
            else oh
    # a call that drops is counted here, `fused_train` or `xla_dropout`
    shard = dropping and kernel_route.choose(
        _FUSED_TRAIN, h_kv == h and _fused_train_shape(h, sq, sk, d, causal),
        b)
    if shard:
        # the kernels work in the packed layout: no head is split off
        pack = (lambda x: x) if packed else (
            lambda x: x.transpose(0, 2, 1, 3).reshape(b, -1, h * d))
        mask = (jnp.ones((b, sk), query.dtype) if valid_mask is None
                else valid_mask.astype(query.dtype))
        out = _attend_train(pack(query), pack(key), pack(value), mask,
                            _key_words(rng_key), h, float(scale),
                            1.0 - float(dropout), shard)
        return out if packed else out.reshape(b, sq, h, d).transpose(
            0, 2, 1, 3)
    if packed:
        qh, kh, vh = (_split_to_heads(x, n) for x, n in (
            (query, h), (key, h_kv), (value, h_kv)))
    else:
        qh, kh, vh = query, key, value
    kh, vh = _repeat_kv(kh, h), _repeat_kv(vh, h)
    qf = qh.reshape(b * h, sq, d)
    kf = kh.reshape(b * h, sk, d)
    vf = vh.reshape(b * h, sk, d)
    if valid_mask is None:
        maskf = jnp.ones((b * h, sk), qf.dtype)
    else:
        maskf = jnp.repeat(valid_mask.astype(qf.dtype), h, axis=0)
    if dropping:
        of = _attention_with_prob_dropout(qf, kf, vf, maskf, float(scale),
                                          float(dropout), rng_key,
                                          causal=causal)
    else:
        kernel_route.choose(_KERNEL_INFER, True, b)     # `_attend` admits
        of = _attend(qf, kf, vf, maskf, float(scale), bool(causal))
    oh = of.reshape(b, h, sq, d)
    if packed:
        return oh.transpose(0, 2, 1, 3).reshape(b, sq, h * d)
    return oh


@register_op("sliding_window_attention")
def _sliding_window_attention(query, key, value, num_heads=1, window=0,
                              scale=None, num_kv_heads=0):
    """Causal sliding-window self-attention without dropout or key mask:
    query i attends to the keys j with 0 <= i - j < `window`.

    query (B, S, num_heads * D), key and value (B, S, num_kv_heads * D)
    (0: as num_heads), each key/value head serving num_heads //
    num_kv_heads query heads.  Returns (B, S, num_heads * D).  A window
    that covers the sequence is causal attention, and is handed to
    `dot_product_attention`'s routes."""
    b, s, u = query.shape
    h, h_kv = num_heads, num_kv_heads or num_heads
    d = u // h
    if window <= 0 or h % h_kv:
        raise ValueError(f"sliding_window_attention: window {window}, "
                         f"{h} query heads over {h_kv} key/value heads")
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    if window >= s:
        return _dot_product_attention(
            query, key, value, num_heads=h, num_kv_heads=h_kv, scale=scale,
            causal=True)
    qh, kh, vh = (_split_to_heads(x, n) for x, n in (
        (query, h), (key, h_kv), (value, h_kv)))
    if kernel_route.choose(
            _SPLASH_WINDOW, _causal_flash_shape(h, h_kv, s, s, d), b,
            kept=_splash_kept(b, h, s, d, query.dtype)):
        oh = _attend_causal(qh, kh, vh, float(scale), int(window),
                            kernel_route.interpret())
    else:
        oh = _window_xla(qh, kh, vh, float(scale), int(window))
    return oh.transpose(0, 2, 1, 3).reshape(b, s, u)
