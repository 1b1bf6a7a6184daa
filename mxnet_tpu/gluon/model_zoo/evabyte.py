"""EvaByte: a tokenizer-free decoder over bytes whose every layer mixes
with EVA attention (ref: the `evabyte` family's config.json, EvaByte/
EvaByte; Zheng et al., "Efficient Attention via Control Variates",
arXiv:2302.04542) and whose head predicts the next `num_pred_heads`
bytes of every position at once.

Every layer is two pre-norm residual sub-layers, `h <- h + attention(
RMSNorm(h))` then `h <- h + mlp(RMSNorm(h))`, the MLP the gated SiLU
form; RMSNorm carries the unit offset (`norm_add_unit_offset`: the gain
is stored from zero and 1 is added in float32); a final RMSNorm and an
untied head follow; no bias anywhere.  The attention sub-layer rotates
queries and keys (rotate-half, every dimension of a head), pools every
`chunk_size` keys and values of a head into one summary by a learned
per-head softmax (`eva_chunk_summary`: the layer's two own parameters
`phi` and `mu`, (heads, head size)), and lets each query attend exactly
inside its own window of `window_size` positions and to the summaries of
every earlier window under one softmax (`eva_attention`).  The head is
ONE projection to `num_pred_heads` x `vocab_size` logits in float32
(`fp32_logits`); head p of position t predicts byte t + 1 + p, and
`multibyte_loss` is the mean cross-entropy over every (t, p) whose
target exists.

The model is a plain HybridBlock stack over registered ops
(`rotary_embedding`, `eva_chunk_summary`, `eva_attention`, `RMSNorm`,
`FullyConnected`), so `SPMDTrainer` compiles it into one program and a
profile reads it by those names.  Each layer owns its parameters
directly: under `SPMDTrainer(remat=True)` a layer is ONE recomputed
segment, which keeps its input and what the EVA kernel wrote for the
backward (`ops/residuals.py`: the output and its logsumexp) and computes
everything else again.  The rotary tables are made once a forward pass,
in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ... import initializer
from ...ops import rotary
from .. import nn
from ..block import HybridBlock
from ._decoder import FP32, Head, Layer, gated_mlp, norm_residual, project

__all__ = ["EvaByteModel", "EvaByteLayer", "multibyte_loss"]


class ClippedNormal(initializer.Initializer):
    """Normal(0, 1) clipped to [-1, 1], times `scale`: the pooling
    vectors start inside the keys' own scale."""

    def __init__(self, scale):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, name, arr):
        arr[:] = self.scale * np.clip(
            np.random.normal(0.0, 1.0, arr.shape), -1.0, 1.0)


class EvaByteLayer(Layer):
    """EVA attention, then the gated MLP.  forward(h, cos, sin) -> h."""

    def __init__(self, hidden_size, num_heads, head_dim, intermediate_size,
                 window_size, chunk_size, eps, **kwargs):
        super().__init__(hidden_size, eps, norm_offset=1.0, **kwargs)
        self._heads, self._window, self._chunk = (num_heads, window_size,
                                                  chunk_size)
        self._attn_scale = head_dim ** -0.5
        d, width = hidden_size, num_heads * head_dim
        with self.name_scope():
            for name, shape in (("q_proj_weight", (width, d)),
                                ("k_proj_weight", (width, d)),
                                ("v_proj_weight", (width, d)),
                                ("o_proj_weight", (d, width)),
                                ("mlp_gate_weight", (intermediate_size, d)),
                                ("mlp_up_weight", (intermediate_size, d)),
                                ("mlp_down_weight", (d, intermediate_size))):
                setattr(self, name, self.params.get(name, shape=shape))
            for name in ("phi", "mu"):
                setattr(self, name, self.params.get(
                    name, shape=(num_heads, head_dim),
                    init=ClippedNormal(head_dim ** -0.5)))
            self.mlp_norm_weight = self._norm_gain("mlp_norm_weight")

    def hybrid_forward(self, F, x, cos, sin, norm_weight, q_proj_weight,
                       k_proj_weight, v_proj_weight, o_proj_weight, phi, mu,
                       mlp_norm_weight, mlp_gate_weight, mlp_up_weight,
                       mlp_down_weight):
        h = norm_residual(F, x, norm_weight, self._eps, self.attend, cos,
                          sin, q_proj_weight, k_proj_weight, v_proj_weight,
                          o_proj_weight, phi, mu, offset=self._offset)
        return norm_residual(F, h, mlp_norm_weight, self._eps, gated_mlp,
                             mlp_gate_weight, mlp_up_weight,
                             mlp_down_weight, offset=self._offset)

    def attend(self, F, u, cos, sin, q_proj_weight, k_proj_weight,
               v_proj_weight, o_proj_weight, phi, mu):
        # rotated first: the summaries pool rotated keys
        q, k = F.rotary_embedding(
            project(F, u, q_proj_weight), project(F, u, k_proj_weight),
            cos, sin, num_heads=self._heads)
        v = project(F, u, v_proj_weight)
        sizes = dict(num_heads=self._heads, chunk=self._chunk,
                     scale=self._attn_scale)
        key_summary, value_summary = F.eva_chunk_summary(k, v, phi, mu,
                                                         **sizes)
        out = F.eva_attention(q, k, v, key_summary, value_summary,
                              window=self._window, **sizes)
        return project(F, out, o_proj_weight)


def multibyte_loss(logits, tokens):
    """logits (B, S, P, V) float32, tokens (B, S) int: head p of
    position t predicts byte t + 1 + p.  -> the mean cross-entropy over
    every (t, p) with t + 1 + p < S, in float32."""
    s, heads, vocab = logits.shape[1:]
    tokens = tokens.astype(jnp.int32)
    # target[b, t, p] = tokens[b, t + 1 + p]: slices, not a gather
    ahead = jnp.pad(tokens, ((0, 0), (0, heads)))
    target = jnp.stack([ahead[:, 1 + p:1 + p + s] for p in range(heads)],
                       axis=-1)
    exists = (jnp.arange(s)[:, None] + 1 + jnp.arange(heads)[None]) < s
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    # the target's log-probability by comparison: no gather either
    picked = jnp.where(jnp.arange(vocab) == target[..., None], logp,
                       0.0).sum(-1)
    count = tokens.shape[0] * int(np.sum(np.maximum(
        s - 1 - np.arange(heads), 0)))
    return -jnp.where(exists, picked, 0.0).sum() / count


class EvaByteModel(HybridBlock):
    """forward(bytes (B, S) int) -> logits (B, S, num_pred_heads,
    vocab_size) float32.  Keys are the family's own (`config.json`); S
    has to be a multiple of `window_size` (or at most one window) and
    the window of `chunk_size`."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, num_attention_heads, window_size,
                 chunk_size, num_pred_heads=1, rope_theta=10000.0,
                 rms_norm_eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        head_dim = hidden_size // num_attention_heads
        self._vocab, self._pred_heads = vocab_size, num_pred_heads
        self._inv_freq = rotary.default_inv_freq(rope_theta, head_dim)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, hidden_size,
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i in range(num_hidden_layers):
                self.layers.add(EvaByteLayer(
                    hidden_size, num_attention_heads, head_dim,
                    intermediate_size, window_size, chunk_size,
                    rms_norm_eps, prefix=f"layer{i}_"))
            self.head = Head(hidden_size, num_pred_heads * vocab_size,
                             rms_norm_eps, norm_offset=1.0,
                             logits_dtype=FP32, prefix="head_")

    def hybrid_forward(self, F, tokens):
        h = self.embed(tokens)
        cos, sin = rotary.rotary_tables(self._inv_freq, tokens.shape[1])
        for layer in self.layers._children.values():
            h = layer(h, cos, sin)
        return F.reshape(self.head(h), shape=(
            *tokens.shape, self._pred_heads, self._vocab))
