"""EVA attention as two registered ops: exact softmax attention inside a
query's own window and one pooled key and value for every chunk of every
earlier window, under ONE softmax normaliser (Zheng et al., "Efficient
Attention via Control Variates", arXiv:2302.04542, in the form the
EvaByte release uses: learned per-head pooling vectors in place of
sampled features).

`eva_chunk_summary`: every `chunk` consecutive keys and values of a head
are pooled into one key and one value by a learned per-head softmax,

    alpha_m = softmax over m in chunk j of  scale * (k_m . phi_h)
    K~_j    = sum_m alpha_m k_m + mu_h          V~_j = sum_m alpha_m v_m

`eva_attention`: query i of window w = i // window attends to the keys
m of its own window with m <= i (exactly) AND to the summaries j of
every chunk of every EARLIER window (j < w * window / chunk; none of its
own window's), the two kinds of key sharing one softmax:

    o_i = [sum_m e^{s q_i.k_m} v_m + sum_j e^{s q_i.K~_j} V~_j]
        / [sum_m e^{s q_i.k_m}     + sum_j e^{s q_i.K~_j}]

scores and statistics in float32.  At S = 32768, window 2048, chunk 16 a
query reads at most 2048 keys and 1920 summaries where causal attention
reads up to 32768 keys.

Which call takes which route (counted by `pallas_attention.route_counts()`;
how a route is chosen is `ops/kernel_route.py`'s business):

  * heads of 128 (or a multiple), S and S / chunk multiples of 128:
    `eva_splash`, upstream's splash kernels (forward, dQ, dK/dV under
    their own custom_vjp) over the keys [k ; K~] and a mask computed in
    the kernel, `local | remote` of shape (S, S + S / chunk); only the
    blocks the mask touches are visited.
  * everything else: `eva_xla`, windows as a batch dimension, each
    against its own keys and against all the summaries: scores (B, H, S,
    window + S / chunk) where a dense mask would hold (B, H, S, S + S /
    chunk).
  * window >= S has no earlier window: causal attention, handed to
    `dot_product_attention`'s routes.

`eva_chunk_summary` has one form, plain XLA: it reads k and v once and
writes 1 / chunk of them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import kernel_route
from . import pallas_attention as pa
from .registry import register_op


def _check(op, s, window, chunk):
    if window <= 0 or chunk <= 0 or s % window or window % chunk:
        raise ValueError(f"{op}: {s} positions in windows of {window} and "
                         f"chunks of {chunk}: the window has to divide the "
                         "sequence and the chunk the window")


@register_op("eva_chunk_summary", num_outputs=2)
def _eva_chunk_summary(key, value, phi, mu, num_heads=1, chunk=1,
                       scale=None):
    """key, value (B, S, num_heads * D); phi, mu (num_heads, D) ->
    (key summaries, value summaries), each (B, S / chunk, num_heads * D)
    in the inputs' dtype; the pooling softmax and the sums in float32."""
    b, s, u = key.shape
    h, d = num_heads, u // num_heads
    _check("eva_chunk_summary", s, chunk, chunk)
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    # head-split first, as `eva_attention` wants them anyway (one copy
    # serves both ops): a chunk's rows then lie along the sublanes and a
    # head's dimensions along the lanes
    k, v = (pa._split_to_heads(x, h).reshape(b, h, s // chunk, chunk, d)
            for x in (key, value))
    logit = jnp.einsum("bhncd,hd->bhnc", k, phi.astype(k.dtype),
                       preferred_element_type=jnp.float32) * scale
    alpha = jax.nn.softmax(logit, axis=3)[..., None]
    pooled_k = (alpha * k.astype(jnp.float32)).sum(3) \
        + mu.astype(jnp.float32)[:, None]
    pooled_v = (alpha * v.astype(jnp.float32)).sum(3)
    return tuple(x.astype(key.dtype).transpose(0, 2, 1, 3).reshape(
        b, s // chunk, u) for x in (pooled_k, pooled_v))


def _eva_xla(q, k, v, ks, vs, scale, window, chunk):
    """q, k, v (B, H, S, D), ks, vs (B, H, S / chunk, D): the windows as
    a batch dimension, each window's queries against its own keys under
    the causal triangle and against all the summaries, those of its own
    and later windows masked; one softmax over both."""
    b, h, s, d = q.shape
    n, per_window = s // window, window // chunk
    qw, kw, vw = (x.reshape(b, h, n, window, d) for x in (q, k, v))
    local = jnp.einsum("bhnqd,bhnkd->bhnqk", qw, kw,
                       preferred_element_type=jnp.float32) * scale
    remote = jnp.einsum("bhnqd,bhjd->bhnqj", qw, ks,
                        preferred_element_type=jnp.float32) * scale
    causal = jnp.arange(window)[:, None] >= jnp.arange(window)[None]
    earlier = (jnp.arange(s // chunk)[None]
               < per_window * jnp.arange(n)[:, None])        # (n, S / C)
    score = jnp.concatenate(
        [jnp.where(causal, local, -1e30),
         jnp.where(earlier[:, None, :], remote, -1e30)], axis=-1)
    prob = jax.nn.softmax(score, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhnqk,bhnkd->bhnqd", prob[..., :window], vw) \
        + jnp.einsum("bhnqj,bhjd->bhnqd", prob[..., window:], vs)
    return out.reshape(b, h, s, d)


def _splash_kernel(heads, s, window, chunk, blk, interpret):
    """The splash kernels over (S, S + S / chunk): the mask's blocks are
    classified on the host (4 s at S = 32768, once a trace of
    `_attend_eva`), the partly visible ones computed in the kernel from
    the positions.  Made inside the trace that uses it: its tables are
    then constants of that program."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sa_mask)

    per_window = window // chunk
    compute = min(blk, 512)     # the rows of keys one product takes

    def visible(q_ids, kv_ids):
        """Key column c < S is position c; column S + j is summary j."""
        local = (q_ids // window == kv_ids // window) & (q_ids >= kv_ids)
        remote = (kv_ids >= s) & (
            kv_ids - s < (q_ids // window) * per_window)
        return local | remote

    class EvaMask(sa_mask._ComputableMask):
        sizes = (s, window, chunk)

        def __getitem__(self, idx):
            """A block of the mask for the host's classification.  Most
            blocks are all visible or all hidden, which the corners
            say: `visible` on 512 x 512 positions costs 1.5 ms, and
            classifying the 17,408 blocks of S = 32768 that way took
            26 s of every process's set-up."""
            rows, cols = (sa_mask._fill_slice(sl, n)
                          for sl, n in zip(idx, self.shape))
            r0, r1 = rows.start // window, (rows.stop - 1) // window
            shape = (rows.stop - rows.start, cols.stop - cols.start)
            same = None
            if cols.start >= s:             # summaries only
                if cols.stop - 1 - s < r0 * per_window:
                    same = True
                elif cols.start - s >= r1 * per_window:
                    same = False
            elif cols.stop <= s:            # positions only
                c0, c1 = cols.start // window, (cols.stop - 1) // window
                if r1 < c0 or c1 < r0 or rows.stop <= cols.start:
                    same = False
                elif r0 == r1 == c0 == c1 and rows.start >= cols.stop - 1:
                    same = True
            if same is None:
                return super().__getitem__(idx)
            return np.broadcast_to(np.bool_(same), shape)

        def __eq__(self, other):
            return getattr(other, "sizes", None) == self.sizes

        def __hash__(self):
            return hash(self.sizes)

    mask = EvaMask((s, s + s // chunk), visible)
    return sa.make_splash_mha_single_device(
        sa.MultiHeadMask([mask] * heads),
        block_sizes=sa.BlockSizes(
            block_q=blk, block_kv=blk, block_kv_compute=compute,
            block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=compute,
            block_q_dq=blk, block_kv_dq=blk),
        # the forward rule names its output and logsumexp: a recomputed
        # segment keeps them (ops/residuals.py)
        residual_checkpoint_name="eva_splash", interpret=interpret)


def _splash_block(s, summaries):
    """Rows of queries and of keys a block: the largest that divides
    both kinds of key, or None.  One layer at S = 32768, window 2048,
    chunk 16, 32 heads, forward + backward on the v5e (PERF.md, PR 34):
    512 95.8 ms, 1024 (in products of 512 keys) 76.6, 1024 in one
    product 77.9, (1024, 512) 82.8, (512, 1024) 86.6, the fused backward
    84.0; blocks of 2048 do not fit VMEM."""
    return next((n for n in (1024, 512, 256, 128)
                 if s % n == 0 and summaries % n == 0), None)


def _eva_splash(q, k, v, ks, vs, scale, window, chunk, interpret=False):
    h, s = q.shape[1], q.shape[2]
    kernel = _splash_kernel(h, s, window, chunk,
                            _splash_block(s, ks.shape[2]), interpret)
    # the kernels apply no scale of their own
    return jax.vmap(kernel)(q * jnp.asarray(scale, q.dtype),
                            jnp.concatenate([k, ks], axis=2),
                            jnp.concatenate([v, vs], axis=2))


def _packed(core, heads, **sizes):
    """`core` over head-split arrays (B, H, rows, D) as a function of the
    ops' packed ones (B, rows, H * D)."""
    def run(*packed):
        out = core(*(pa._split_to_heads(x, heads) for x in packed), **sizes)
        return out.transpose(0, 2, 1, 3).reshape(packed[0].shape)
    return run


@functools.partial(jax.jit, static_argnames=("heads", "scale", "window",
                                             "chunk", "interpret"))
def _attend_eva(q, k, v, ks, vs, heads, scale, window, chunk, interpret):
    """Packed in, packed out: the splash kernels or the windowed XLA
    form.  Jitted, so that a stack of layers traces and lowers the
    kernels once."""
    sizes = dict(scale=scale, window=window, chunk=chunk)
    return kernel_route.dispatch(
        _packed(_eva_splash, heads, interpret=interpret, **sizes),
        _packed(_eva_xla, heads, **sizes), q, k, v, ks, vs,
        interpret=interpret)


_EVA_SPLASH = kernel_route.Kernel("attention", "eva_splash", "eva_xla")


@register_op("eva_attention")
def _eva_attention(query, key, value, key_summary, value_summary,
                   num_heads=1, window=0, chunk=1, scale=None):
    """query, key, value (B, S, num_heads * D); key_summary,
    value_summary (B, S / chunk, num_heads * D) from `eva_chunk_summary`
    -> (B, S, num_heads * D).  `window` has to divide S (or cover it:
    then nothing is remote and the call is causal attention) and `chunk`
    the window."""
    b, s, u = query.shape
    h, d = num_heads, u // num_heads
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    _check("eva_attention", s, min(window, s), chunk)
    if window >= s:
        return pa._dot_product_attention(query, key, value, num_heads=h,
                                         scale=scale, causal=True)
    if key_summary.shape != (b, s // chunk, u) \
            or value_summary.shape != key_summary.shape:
        raise ValueError(
            f"eva_attention: summaries {key_summary.shape} / "
            f"{value_summary.shape} for {s} positions in chunks of {chunk}")
    packed = (query, key, value, key_summary, value_summary)
    sizes = dict(scale=float(scale), window=int(window), chunk=int(chunk))
    if kernel_route.choose(
            _EVA_SPLASH, d % 128 == 0 and _splash_block(s, s // chunk), b,
            kept=pa._splash_kept(b, h, s, d, query.dtype)):
        return _attend_eva(*packed, heads=h, **sizes,
                           interpret=kernel_route.interpret())
    return _packed(_eva_xla, h, **sizes)(*packed)
