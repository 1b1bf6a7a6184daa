"""LFM2 (`lfm2_moe`): a decoder-only language model whose layers open
with one of two operators, a gated short convolution or grouped-query
attention with a norm a head, over a dense gated MLP (the leading
layers) or a gated mixture of experts without a shared expert (ref: the
`lfm2_moe` family's config.json, e.g. LiquidAI/LFM2-8B-A1B; Liquid AI's
LFM2 technical report for the equations).

Every layer is two pre-norm residual sub-layers, `h <- h + operator(
RMSNorm(h))` then `h <- h + mlp(RMSNorm(h))`; a final RMSNorm and a head
tied to the embedding follow; no bias anywhere.  `layer_types[l]` names
layer l's operator:

  * `conv`: `[B ; C ; x~] = u W_in`, `y = (C * conv(B * x~)) W_out`, the
    convolution depthwise and causal over `conv_L_cache` positions (op
    `short_conv`); the two projections and the op are traced under
    `CONV_NAME`, a component of their name stacks.
  * `full_attention`: `num_attention_heads` query heads over
    `num_key_value_heads` key/value heads of hidden / heads dimensions;
    q and k each pass an RMSNorm over a head's dimensions (one gain for
    q, one for k) and then the rotation (all dimensions, i paired with
    i + d / 2, `rope_theta`); causal softmax at d^-1/2.

The first `num_dense_layers` layers have the dense MLP, the rest
`num_experts` routed experts, `num_experts_per_tok` chosen by sigmoid
score + a selection bias, the chosen scores normalised to sum to
`routed_scaling_factor` (`_decoder.MLPLayer`, `parallel/moe.py`).  A
sparse layer HOLDS `experts_held` of the experts its router scores (ids
from `first_expert`), as `laguna.py`'s and `joyai.py`'s do.

The model is a plain HybridBlock stack over registered ops
(`short_conv`, `rotary_embedding`, `dot_product_attention`, `moe_route`,
`moe_experts`, `RMSNorm`, `FullyConnected`), so `SPMDTrainer` compiles
it into one program and a profile reads it by those names.  Each layer
owns its parameters directly: under `SPMDTrainer(remat=True)` a layer is
ONE recomputed segment, which keeps its input and, of an attention
layer, what the attention kernel wrote (`ops/residuals.py`); a conv
layer keeps nothing more.  The rotary tables are made once a forward
pass, in float32, and handed to every layer.

`Lfm2Model(operator_outputs=(l, ...))` also returns what the operators
of those layers add to the residual stream: a comparison there sees a
fault inside an operator that the logits, many layers on, do not.
"""
from __future__ import annotations

import jax

from ... import initializer
from ...base import MXNetError
from ...ops import rotary
from .. import nn
from ..block import HybridBlock
from ._decoder import Head, MLPLayer, project

__all__ = ["Lfm2Model", "Lfm2Layer", "CONV_NAME"]

KINDS = ("conv", "full_attention")

#: the scope a conv layer's operator is traced under (its two projections
#: and `short_conv`): a component of the name stack of every instruction
CONV_NAME = "conv"


class Lfm2Layer(MLPLayer):
    """An operator (`kind`: the gated short convolution, or attention),
    then a dense gated MLP or the expert layer.  forward(h, cos, sin) ->
    h, or (h, [rows of each held expert..., dropped]) from a sparse
    layer; a conv layer takes the tables and leaves them alone.  With
    `operator_output` the operator's own result, operator(RMSNorm(h)),
    comes last beside them."""

    def __init__(self, hidden_size, eps, kind, num_heads=1, num_kv_heads=1,
                 conv_taps=3, mlp_size=None, num_experts=0, top_k=0,
                 expert_size=0, scale=1.0, experts_held=None,
                 first_expert=0, operator_output=False, **kwargs):
        """The rest: `MLPLayer._mlp_params`."""
        super().__init__(hidden_size, eps, **kwargs)
        self._operator_output = operator_output
        if kind not in KINDS or hidden_size % num_heads \
                or num_heads % num_kv_heads:
            raise MXNetError(f"layer kind {kind!r} (of {KINDS}), "
                             f"{num_heads} heads over {num_kv_heads} at "
                             f"hidden {hidden_size}")
        self._conv = kind == "conv"
        self._heads, self._kv_heads = num_heads, num_kv_heads
        d, head = hidden_size, hidden_size // num_heads
        self._attn_scale = head ** -0.5
        with self.name_scope():
            if self._conv:
                self._matrix("conv_in_proj_weight", (3 * d, d))
                # (channel, tap): tap conv_taps - 1 on the current position
                self.conv_weight = self.params.get(
                    "conv_weight", shape=(d, conv_taps),
                    init=initializer.Uniform(conv_taps ** -0.5))
                self._matrix("conv_out_proj_weight", (d, d))
            else:
                for name, rows in (("q", d), ("k", num_kv_heads * head)):
                    self._matrix(f"{name}_proj_weight", (rows, d))
                    setattr(self, f"{name}_norm_weight", self.params.get(
                        f"{name}_norm_weight", shape=(head,), init="ones"))
                self._matrix("v_proj_weight", (num_kv_heads * head, d))
                self._matrix("o_proj_weight", (d, d))
            # the operator's parameters, in the order its method takes them
            self._operator = tuple(n for n in self._reg_params
                                   if n != "norm_weight")
            self._mlp_params(mlp_size, num_experts, top_k, expert_size, 0,
                             scale, experts_held, first_expert)

    def hybrid_forward(self, F, x, cos, sin, norm_weight, mlp_norm_weight,
                       **params):
        operator = [params.pop(name) for name in self._operator]
        mix, tables = (self.convolve, ()) if self._conv \
            else (self.attend, (cos, sin))
        mixed = mix(F, F.RMSNorm(x, norm_weight, eps=self._eps), *tables,
                    *operator)
        out = self.mlp(F, x + mixed, mlp_norm_weight, **params)
        if not self._operator_output:
            return out
        return (*out, mixed) if self._sparse else (out, mixed)

    def convolve(self, F, u, conv_in_proj_weight, conv_weight,
                 conv_out_proj_weight):
        with jax.named_scope(CONV_NAME):
            return project(F, F.short_conv(
                project(F, u, conv_in_proj_weight), conv_weight),
                conv_out_proj_weight)

    def _head_norm(self, F, x, weight, heads):
        """RMSNorm over each head's dimensions: x (B, S, heads * d)."""
        b, s = x.shape[0], x.shape[1]
        return F.reshape(
            F.RMSNorm(F.reshape(x, shape=(b, s, heads, -1)), weight,
                      eps=self._eps), shape=(b, s, -1))

    def attend(self, F, u, cos, sin, q_proj_weight, q_norm_weight,
               k_proj_weight, k_norm_weight, v_proj_weight, o_proj_weight):
        q, k = F.rotary_embedding(
            self._head_norm(F, project(F, u, q_proj_weight), q_norm_weight,
                            self._heads),
            self._head_norm(F, project(F, u, k_proj_weight), k_norm_weight,
                            self._kv_heads),
            cos, sin, num_heads=self._heads, num_kv_heads=self._kv_heads)
        out = F.dot_product_attention(
            q, k, project(F, u, v_proj_weight), None, causal=True,
            num_heads=self._heads, num_kv_heads=self._kv_heads,
            scale=self._attn_scale)
        return project(F, out, o_proj_weight)


class Lfm2Model(HybridBlock):
    """forward(tokens (B, S)) -> (logits (B, S, vocab), expert statistics
    (sparse layers, experts_held + 1) int32: rows of each held expert and
    the assignments dropped, which is 0), or the logits alone from a
    model without a sparse layer.  Keys are the family's own
    (`config.json`); `layer_types` gives the depth.  The head reads the
    embedding's array (the published parameter count is the tied one).
    `operator_outputs`: indices of layers whose operator's result (B, S,
    hidden) follows, in that order."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 moe_intermediate_size, num_dense_layers,
                 num_attention_heads, num_key_value_heads, layer_types,
                 conv_L_cache, rope_theta, num_experts, num_experts_per_tok,
                 routed_scaling_factor=1.0, norm_eps=1e-5, conv_bias=False,
                 norm_topk_prob=True, experts_held=None, first_expert=0,
                 operator_outputs=(), **kwargs):
        super().__init__(**kwargs)
        if not layer_types or set(layer_types) - set(KINDS) or conv_bias \
                or not norm_topk_prob \
                or not 0 <= num_dense_layers <= len(layer_types):
            raise MXNetError(
                f"layers {layer_types}, {num_dense_layers} of them dense, "
                f"conv_bias {conv_bias} (only False), norm_topk_prob "
                f"{norm_topk_prob} (only True)")
        self._inv_freq = rotary.default_inv_freq(
            rope_theta, hidden_size // num_attention_heads)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, hidden_size,
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i, kind in enumerate(layer_types):
                self.layers.add(Lfm2Layer(
                    hidden_size, norm_eps, kind, num_attention_heads,
                    num_key_value_heads, conv_L_cache,
                    mlp_size=intermediate_size
                    if i < num_dense_layers else None,
                    num_experts=num_experts, top_k=num_experts_per_tok,
                    expert_size=moe_intermediate_size,
                    scale=routed_scaling_factor,
                    experts_held=experts_held, first_expert=first_expert,
                    operator_output=i in operator_outputs,
                    prefix=f"layer{i}_"))
            self.head = Head(hidden_size, vocab_size, norm_eps,
                             tied=self.embed.weight, prefix="head_")

    def hybrid_forward(self, F, tokens):
        h = self.embed(tokens)
        tables = rotary.rotary_tables(self._inv_freq, tokens.shape[1])
        stats, operators = [], []
        for layer in self.layers._children.values():
            out = layer(h, *tables)
            if not isinstance(out, (list, tuple)):
                h = out
                continue
            h, *rest = out
            if layer._operator_output:
                operators.append(rest.pop())
            stats.extend(rest)
        outputs = [self.head(h)]
        if stats:
            outputs.append(F.stack(*stats, axis=0))
        outputs.extend(operators)
        return outputs[0] if len(outputs) == 1 else tuple(outputs)
