"""Laguna: a decoder-only language model whose layers mix sliding-window
and full causal attention, each with rotary positions of its own kind
and a gate of one scalar a head, over a dense gated MLP (the leading
layer) or a gated mixture of experts with a shared expert (ref: the
`laguna` family's config.json, e.g. poolside/Laguna-XS.2).

Every layer is two pre-norm residual sub-layers, `h <- h + attention(
RMSNorm(h))` then `h <- h + mlp(RMSNorm(h))`; a final RMSNorm and an
untied head follow; no bias anywhere.  Layer l has
`num_attention_heads_per_layer[l]` query heads over `num_key_value_heads`
key/value heads, attends causally over everything (`full_attention`) or
over the last `sliding_window` positions (`sliding_attention`), and
rotates its queries and keys by the `rope_parameters` of that kind
(`default`, or `yarn` with its attention factor on the tables; a
`partial_rotary_factor` below 1 leaves the upper dimensions of a head
alone).  The model is a plain HybridBlock stack over registered ops
(`rotary_embedding`, `dot_product_attention`,
`sliding_window_attention`, `moe_route`, `moe_experts`, `RMSNorm`,
`FullyConnected`), so `SPMDTrainer` compiles it into one program and a
profile reads it by those names.

Each layer owns its parameters directly: under `SPMDTrainer(remat=True)`
a layer is then ONE recomputed segment, which keeps its input and what
its attention kernel wrote for the backward (`ops/residuals.py`: the
output and softmax statistics; the forward kernel runs once a step) and
computes everything else again.  The rotary tables are made once a
forward pass, in float32, and handed to every layer.

A sparse layer HOLDS `experts_held` of the `num_experts` the router
scores (ids from `first_expert`), as `nemotron_h.LatentMoELayer` does
(`_decoder.MLPLayer`, which `joyai.py` shares).
"""
from __future__ import annotations

from ...base import MXNetError
from ...ops import rotary
from .. import nn
from ..block import HybridBlock
from ._decoder import Head, MLPLayer, norm_residual, project

__all__ = ["LagunaModel", "LagunaLayer"]

KINDS = ("full_attention", "sliding_attention")


class LagunaLayer(MLPLayer):
    """Attention (full or windowed, rotary, gated a head), then a dense
    gated MLP or the expert layer.  forward(h, cos, sin) -> h, or (h,
    [rows of each held expert..., dropped]) from a sparse layer."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 eps, window=None, mlp_size=None, num_experts=0, top_k=0,
                 expert_size=0, shared_size=0, scale=1.0,
                 experts_held=None, first_expert=0, **kwargs):
        """`window` None: full causal attention.  The rest:
        `MLPLayer._mlp_params`."""
        super().__init__(hidden_size, eps, **kwargs)
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._attn_scale, self._window = head_dim ** -0.5, window
        d = hidden_size
        with self.name_scope():
            for name, rows in (("q", num_heads * head_dim),
                               ("k", num_kv_heads * head_dim),
                               ("v", num_kv_heads * head_dim),
                               ("gate", num_heads)):
                self._matrix(f"{name}_proj_weight", (rows, d))
            self._matrix("o_proj_weight", (d, num_heads * head_dim))
            self._mlp_params(mlp_size, num_experts, top_k, expert_size,
                             shared_size, scale, experts_held, first_expert)

    def hybrid_forward(self, F, x, cos, sin, norm_weight, q_proj_weight,
                       k_proj_weight, v_proj_weight, gate_proj_weight,
                       o_proj_weight, mlp_norm_weight, **mlp):
        h = norm_residual(F, x, norm_weight, self._eps, self.attend, cos,
                          sin, q_proj_weight, k_proj_weight, v_proj_weight,
                          gate_proj_weight, o_proj_weight)
        return self.mlp(F, h, mlp_norm_weight, **mlp)

    def attend(self, F, u, cos, sin, q_proj_weight, k_proj_weight,
               v_proj_weight, gate_proj_weight, o_proj_weight):
        b, s = u.shape[0], u.shape[1]
        q, k = F.rotary_embedding(
            project(F, u, q_proj_weight), project(F, u, k_proj_weight),
            cos, sin, num_heads=self._heads, num_kv_heads=self._kv_heads)
        v = project(F, u, v_proj_weight)
        heads = dict(num_heads=self._heads, num_kv_heads=self._kv_heads,
                     scale=self._attn_scale)
        if self._window is None:
            out = F.dot_product_attention(q, k, v, None, causal=True,
                                          **heads)
        else:
            out = F.sliding_window_attention(q, k, v, window=self._window,
                                             **heads)
        # one scalar a head and token, from the normed input
        gate = F.Activation(project(F, u, gate_proj_weight),
                            act_type="sigmoid")
        out = F.reshape(out, shape=(b, s, self._heads, -1)) \
            * F.expand_dims(gate, axis=3)
        return project(F, F.reshape(out, shape=(b, s, -1)), o_proj_weight)


def _inv_freq(head_dim, rope_type="default", rope_theta=10000.0,
              partial_rotary_factor=1.0, factor=1.0,
              original_max_position_embeddings=0, beta_fast=32.0,
              beta_slow=1.0, attention_factor=1.0):
    """One entry of `rope_parameters` -> (r / 2 frequencies, the factor on
    cos and sin)."""
    r = int(head_dim * partial_rotary_factor)
    if rope_type == "default":
        return rotary.default_inv_freq(rope_theta, r), 1.0
    if rope_type == "yarn":
        return rotary.yarn_inv_freq(
            rope_theta, r, factor, original_max_position_embeddings,
            beta_fast, beta_slow), float(attention_factor)
    raise MXNetError(f"rope_type {rope_type!r}: only default and yarn")


class LagunaModel(HybridBlock):
    """forward(tokens (B, S)) -> (logits (B, S, vocab), expert statistics
    (sparse layers, experts_held + 1) int32: rows of each held expert and
    the assignments dropped, which is 0), or the logits alone from a
    model without a sparse layer.  Keys are the family's own
    (`config.json`); the three per-layer lists give the depth."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 num_attention_heads_per_layer, num_key_value_heads,
                 head_dim, layer_types, mlp_layer_types, sliding_window,
                 rope_parameters, num_experts, num_experts_per_tok,
                 moe_intermediate_size, shared_expert_intermediate_size,
                 moe_routed_scaling_factor, rms_norm_eps=1e-6,
                 experts_held=None, first_expert=0, **kwargs):
        super().__init__(**kwargs)
        depth = len(layer_types)
        if not depth or set(layer_types) - set(KINDS) \
                or set(mlp_layer_types) - {"dense", "sparse"} \
                or not depth == len(mlp_layer_types) \
                == len(num_attention_heads_per_layer):
            raise MXNetError(
                f"layers: {layer_types}, {mlp_layer_types}, heads "
                f"{num_attention_heads_per_layer}")
        self._kinds = tuple(layer_types)
        self._rope = {kind: _inv_freq(head_dim, **rope_parameters[kind])
                      for kind in sorted(set(layer_types))}
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, hidden_size,
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i, (kind, mlp, heads) in enumerate(zip(
                    layer_types, mlp_layer_types,
                    num_attention_heads_per_layer)):
                self.layers.add(LagunaLayer(
                    hidden_size, heads, num_key_value_heads, head_dim,
                    rms_norm_eps,
                    window=sliding_window
                    if kind == "sliding_attention" else None,
                    mlp_size=intermediate_size if mlp == "dense" else None,
                    num_experts=num_experts, top_k=num_experts_per_tok,
                    expert_size=moe_intermediate_size,
                    shared_size=shared_expert_intermediate_size,
                    scale=moe_routed_scaling_factor,
                    experts_held=experts_held, first_expert=first_expert,
                    prefix=f"layer{i}_"))
            self.head = Head(hidden_size, vocab_size, rms_norm_eps,
                             prefix="head_")

    def hybrid_forward(self, F, tokens):
        h = self.embed(tokens)
        tables = {kind: rotary.rotary_tables(inv_freq, tokens.shape[1],
                                             factor)
                  for kind, (inv_freq, factor) in self._rope.items()}
        stats = []
        for kind, layer in zip(self._kinds, self.layers._children.values()):
            out = layer(h, *tables[kind])
            if isinstance(out, (list, tuple)):
                h, layer_stats = out
                stats.append(layer_stats)
            else:
                h = out
        logits = self.head(h)
        if not stats:
            return logits
        return logits, F.stack(*stats, axis=0)
