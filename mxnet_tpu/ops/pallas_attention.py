"""Fused scaled-dot-product attention: Pallas TPU kernel + XLA reference.

The reference's counterpart is the fused attention path in later-1.x
contrib (ref: src/operator/contrib/transformer.cc —
_contrib_interleaved_matmul_selfatt_* used by GluonNLP BERT); this is the
TPU-native equivalent per SURVEY.md §7 ("fused cells (RNN/attention) …
in Pallas").

Design:
  * One Pallas kernel per (batch*head, q-block): the query block lives in
    VMEM, keys/values for the whole sequence stream in as one block
    (BERT-scale S·D fits VMEM easily; long-context goes through
    parallel.ring instead), scores are computed on the MXU in fp32 and
    never materialized in HBM — the flash-attention memory win.
  * Backward = recompute-from-inputs via jax.vjp of the reference
    (XLA) math under custom_vjp — XLA fuses it; activation memory stays
    O(S·D) not O(S²).
  * A program lowered for CPU has no Mosaic and takes the pure-XLA
    path with identical semantics (or the Pallas interpreter under
    MXNET_PALLAS_INTERPRET=1); MXNET_USE_PALLAS=0 selects the XLA path
    anywhere.  Lowered for TPU, a lowering or compile failure raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..util import env
from .registry import register_op

__all__ = ["dot_product_attention_ref"]


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
        interpret=env.get_bool("MXNET_PALLAS_INTERPRET"),
        name="mx_attention_fwd",    # the kernel's name in a device profile
    )(q, k, v, mask[:, None, :])
    return out[:, :s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attend(q, k, v, mask, scale, causal=False):
    """Kernel or reference, chosen from what the code can observe: the
    platform the enclosing program is LOWERED for (a cpu()-resident warm
    pass on a TPU host lowers for CPU and takes the reference; the same
    call inside the TPU step takes the kernel).  Nothing is probed and
    nothing latches: when the program lowers for TPU the kernel runs or
    the call raises with Mosaic's own message."""
    if not env.get_bool("MXNET_USE_PALLAS"):
        return dot_product_attention_ref(q, k, v, mask, scale, causal)
    if env.get_bool("MXNET_PALLAS_INTERPRET"):
        return _attention_pallas(q, k, v, mask, scale, causal)
    return jax.lax.platform_dependent(
        q, k, v, mask,
        tpu=functools.partial(_attention_pallas, scale=scale,
                              causal=causal),
        default=functools.partial(dot_product_attention_ref, scale=scale,
                                  causal=causal))


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


def _attention_with_prob_dropout(q, k, v, mask, scale, p, rng_key,
                                 causal=False):
    """XLA path with dropout on the attention probabilities — the BERT /
    reference training semantics (dropout on softmax(QK^T)).  Used when
    dropout is active; XLA fuses it just as well, and the fused Pallas
    kernel serves the dropout-free (inference / p=0) case."""
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
    drop_mask = jax.random.bernoulli(rng_key, keep, p_attn.shape)
    p_attn = p_attn * drop_mask.astype(p_attn.dtype) / keep
    return jnp.einsum("bqk,bkd->bqd", p_attn, v)


@register_op("dot_product_attention",
             aliases=("FusedAttention", "_contrib_dot_product_attention"))
def _dot_product_attention(query, key, value, valid_mask=None, rng_key=None,
                           num_heads=1, scale=None, dropout=0.0,
                           causal=False, _train=False):
    """Multi-head scaled-dot-product attention.

    query/key/value: (B, S, U) with U = num_heads * head_dim, or already
    head-split (B, H, S, D).  valid_mask: (B, S_k) 1/0 key-validity mask
    (sequence lengths), or None.  dropout: rate applied to the attention
    probabilities in train mode (key auto-threaded by the frontend).
    Returns the same layout as the input.
    """
    packed = query.ndim == 3
    if packed:
        b, sq, u = query.shape
        h = num_heads
        d = u // h
        def split(x):
            bs, s, _ = x.shape
            return x.reshape(bs, s, h, d).transpose(0, 2, 1, 3)
        qh, kh, vh = split(query), split(key), split(value)
    else:
        qh, kh, vh = query, key, value
        b, h, sq, d = qh.shape
    sk = kh.shape[2]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    qf = qh.reshape(b * h, sq, d)
    kf = kh.reshape(b * h, sk, d)
    vf = vh.reshape(b * h, sk, d)
    if valid_mask is None:
        maskf = jnp.ones((b * h, sk), qf.dtype)
    else:
        maskf = jnp.repeat(valid_mask.astype(qf.dtype), h, axis=0)
    if _train and dropout > 0.0 and rng_key is not None:
        of = _attention_with_prob_dropout(qf, kf, vf, maskf, float(scale),
                                          float(dropout), rng_key,
                                          causal=causal)
    else:
        of = _attend(qf, kf, vf, maskf, float(scale), bool(causal))
    oh = of.reshape(b, h, sq, d)
    if packed:
        return oh.transpose(0, 2, 1, 3).reshape(b, sq, h * d)
    return oh
