"""JoyAI-LLM-Flash: a decoder-only language model of multi-head latent
attention (MLA) layers over a dense gated MLP (the leading layers) or a
gated mixture of experts with a shared expert, with a multi-token-
prediction module behind the stack (ref: the `joyai_llm_flash` family's
config.json, e.g. jdopensource/JoyAI-LLM-Flash; the keys and the
equations are DeepSeek-V2's, arXiv:2405.04434 section 2.1, and
DeepSeek-V3's, arXiv:2412.19437 sections 2.1.2 and 2.2).

Every layer is two pre-norm residual sub-layers, `h <- h + attention(
RMSNorm(h))` then `h <- h + mlp(RMSNorm(h))`; a final RMSNorm and an
untied head follow; no bias anywhere.  Attention is MLA in its training
form (`ops/latent_attention.py`): queries through a rank-`q_lora_rank`
chain, keys and values through a shared rank-`kv_lora_rank` latent, each
chain with an RMSNorm inside, and a decoupled rotary part: the last
`qk_rope_head_dim` dimensions of every query head and ONE key vector all
heads share, rotated in pairs (2i, 2i + 1) (`rope_interleave`).

The prediction module (depth 1) predicts the token after next.  For
position i it joins the main stack's normed last hidden state with the
embedding of token i + 1,

    h'_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh,

runs one whole sparse layer of its own over h', norms the result with a
gain of its own and projects it with the main model's head.  `Emb` and
the head are the main model's own blocks, called a second time: one
array each, which both losses' gradients reach.  The module runs over
all S positions (the kernels' shapes): the last one is joined with the
sequence's FIRST token (a roll), is seen by no other position under the
causal mask, and is for the loss to leave out.

The model is a plain HybridBlock stack over registered ops
(`latent_projection`, `rotary_embedding`, `latent_attention`,
`moe_route`, `moe_experts`, `RMSNorm`, `FullyConnected`), so
`SPMDTrainer` compiles it into one program and a profile reads it by
those names; the module is a block of its own, `MTP_NAME` in every one
of its instructions' name stacks.  Each layer owns its parameters
directly: under `SPMDTrainer(remat=True)` a layer is ONE recomputed
segment, which keeps its input and what its attention kernel wrote
(`ops/residuals.py`).  The rotary tables are made once a forward pass,
in float32, and handed to every layer and to the module.
"""
from __future__ import annotations

from ...base import MXNetError
from ...ops import rotary
from .. import nn
from ..block import HybridBlock
from ._decoder import MLPLayer, norm_residual, project

__all__ = ["JoyAIModel", "LatentLayer", "PredictionModule", "MTP_NAME"]

#: the prediction module's block name: a component of the name stack of
#: every instruction it traces (its join, its layer, its pass through
#: the embedding and the head), and the scope a step block gives its loss
MTP_NAME = "mtp"


class LatentLayer(MLPLayer):
    """Latent attention, then a dense gated MLP or the expert layer.
    forward(h, cos, sin) -> h, or (h, [rows of each held expert...,
    dropped]) from a sparse layer."""

    def __init__(self, hidden_size, num_heads, q_rank, kv_rank, nope_dim,
                 rope_dim, v_dim, eps, mlp_size=None, num_experts=0,
                 top_k=0, expert_size=0, shared_size=0, scale=1.0,
                 experts_held=None, first_expert=0, **kwargs):
        """Heads of `nope_dim` + `rope_dim` for queries and keys and of
        `v_dim` for values.  The rest: `MLPLayer._mlp_params`."""
        super().__init__(hidden_size, eps, **kwargs)
        self._heads, self._nope, self._rope = num_heads, nope_dim, rope_dim
        # no rotary scaling in this family's config: no factor on the scale
        self._attn_scale = (nope_dim + rope_dim) ** -0.5
        d = hidden_size
        with self.name_scope():
            self._matrix("q_a_proj_weight", (q_rank, d))
            self.q_a_norm_weight = self.params.get(
                "q_a_norm_weight", shape=(q_rank,), init="ones")
            self._matrix("q_b_proj_weight",
                         (num_heads * (nope_dim + rope_dim), q_rank))
            self._matrix("kv_a_proj_weight", (kv_rank + rope_dim, d))
            self.kv_a_norm_weight = self.params.get(
                "kv_a_norm_weight", shape=(kv_rank,), init="ones")
            self._matrix("kv_b_proj_weight",
                         (num_heads * (nope_dim + v_dim), kv_rank))
            self._matrix("o_proj_weight", (d, num_heads * v_dim))
            self._mlp_params(mlp_size, num_experts, top_k, expert_size,
                             shared_size, scale, experts_held, first_expert)

    def hybrid_forward(self, F, x, cos, sin, norm_weight, q_a_proj_weight,
                       q_a_norm_weight, q_b_proj_weight, kv_a_proj_weight,
                       kv_a_norm_weight, kv_b_proj_weight, o_proj_weight,
                       mlp_norm_weight, **mlp):
        h = norm_residual(F, x, norm_weight, self._eps, self.attend, cos,
                          sin, q_a_proj_weight, q_a_norm_weight,
                          q_b_proj_weight, kv_a_proj_weight,
                          kv_a_norm_weight, kv_b_proj_weight, o_proj_weight)
        return self.mlp(F, h, mlp_norm_weight, **mlp)

    def attend(self, F, u, cos, sin, q_a_proj_weight, q_a_norm_weight,
               q_b_proj_weight, kv_a_proj_weight, kv_a_norm_weight,
               kv_b_proj_weight, o_proj_weight):
        q, k_nope, k_rope, v = F.latent_projection(
            u, q_a_proj_weight, q_a_norm_weight, q_b_proj_weight,
            kv_a_proj_weight, kv_a_norm_weight, kv_b_proj_weight,
            num_heads=self._heads, nope_dim=self._nope,
            rope_dim=self._rope, eps=self._eps)
        # the last rope_dim of every query head, and the one key
        q, k_rope = F.rotary_embedding(
            q, k_rope, cos, sin, num_heads=self._heads, num_kv_heads=1,
            interleaved=True, rotate_last=True)
        out = F.latent_attention(q, k_nope, k_rope, v,
                                 num_heads=self._heads,
                                 scale=self._attn_scale)
        return project(F, out, o_proj_weight)


class _Norm(HybridBlock):
    """RMSNorm with a gain of its own."""

    def __init__(self, size, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(size,),
                                          init="ones")

    def hybrid_forward(self, F, x, weight):
        return F.RMSNorm(x, weight, eps=self._eps)


class _Projection(HybridBlock):
    """x W^T, no bias: the untied vocabulary head."""

    def __init__(self, in_size, out_size, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.weight = self.params.get("weight",
                                          shape=(out_size, in_size))

    def hybrid_forward(self, F, x, weight):
        return project(F, x, weight)


class _Join(HybridBlock):
    """[RMSNorm_e(e) ; RMSNorm_h(h)] W_eh: the next token's embedding
    first, the hidden state second."""

    def __init__(self, hidden_size, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            for name in ("embed_norm_weight", "hidden_norm_weight"):
                setattr(self, name, self.params.get(
                    name, shape=(hidden_size,), init="ones"))
            self.proj_weight = self.params.get(
                "proj_weight", shape=(hidden_size, 2 * hidden_size))

    def hybrid_forward(self, F, h, e, embed_norm_weight, hidden_norm_weight,
                       proj_weight):
        joined = F.concat(F.RMSNorm(e, embed_norm_weight, eps=self._eps),
                          F.RMSNorm(h, hidden_norm_weight, eps=self._eps),
                          dim=2)
        return project(F, joined, proj_weight)


class PredictionModule(HybridBlock):
    """One multi-token-prediction depth.  forward(h (B, S, D): the main
    stack's normed output, tokens (B, S), cos, sin) -> (logits (B, S,
    vocab) for the token after next, its layer's expert statistics).
    `embed` and `head` are the main model's blocks: called here, owned
    (collected, initialised, cast) there."""

    def __init__(self, embed, head, hidden_size, eps, **layer):
        super().__init__(prefix=MTP_NAME + "_")
        self._shared = (embed, head)    # a tuple: not children of this block
        with self.name_scope():
            self.join = _Join(hidden_size, eps, prefix="join_")
            self.layer = LatentLayer(hidden_size, eps=eps, prefix="layer_",
                                     **layer)
            self.norm = _Norm(hidden_size, eps, prefix="norm_")

    def hybrid_forward(self, F, h, tokens, cos, sin):
        embed, head = self._shared
        following = F.concat(F.slice_axis(tokens, axis=1, begin=1, end=None),
                             F.slice_axis(tokens, axis=1, begin=0, end=1),
                             dim=1)
        h, stats = self.layer(self.join(h, embed(following)), cos, sin)
        return head(self.norm(h)), stats


class JoyAIModel(HybridBlock):
    """forward(tokens (B, S)) -> (logits (B, S, vocab), the prediction
    module's logits (B, S, vocab), expert statistics (sparse layers + 1,
    experts_held + 1) int32: rows of each held expert and the
    assignments dropped, which is 0; the module's layer last); without a
    module (`num_nextn_predict_layers` 0) (logits, statistics).  Keys
    are the family's own (`config.json`): the first
    `first_k_dense_replace` layers have the dense MLP, the rest the
    experts; a sparse layer HOLDS `experts_held` of the
    `n_routed_experts` its router scores (ids from `first_expert`)."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, first_k_dense_replace,
                 num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim, rope_theta,
                 n_routed_experts, num_experts_per_tok,
                 moe_intermediate_size, n_shared_experts,
                 routed_scaling_factor, num_nextn_predict_layers=0,
                 rms_norm_eps=1e-6, experts_held=None, first_expert=0,
                 **kwargs):
        super().__init__(**kwargs)
        if num_nextn_predict_layers not in (0, 1) \
                or not 0 <= first_k_dense_replace < num_hidden_layers:
            raise MXNetError(
                f"{num_nextn_predict_layers} prediction modules (0 or 1), "
                f"{first_k_dense_replace} dense layers of "
                f"{num_hidden_layers}")
        self._inv_freq = rotary.default_inv_freq(rope_theta,
                                                 qk_rope_head_dim)
        attention = dict(
            num_heads=num_attention_heads, q_rank=q_lora_rank,
            kv_rank=kv_lora_rank, nope_dim=qk_nope_head_dim,
            rope_dim=qk_rope_head_dim, v_dim=v_head_dim)
        sparse = dict(
            num_experts=n_routed_experts, top_k=num_experts_per_tok,
            expert_size=moe_intermediate_size,
            shared_size=n_shared_experts * moe_intermediate_size,
            scale=routed_scaling_factor, experts_held=experts_held,
            first_expert=first_expert, **attention)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, hidden_size,
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i in range(num_hidden_layers):
                mlp = (dict(mlp_size=intermediate_size, **attention)
                       if i < first_k_dense_replace else sparse)
                self.layers.add(LatentLayer(hidden_size, eps=rms_norm_eps,
                                            prefix=f"layer{i}_", **mlp))
            self.norm = _Norm(hidden_size, rms_norm_eps, prefix="norm_")
            self.head = _Projection(hidden_size, vocab_size, prefix="head_")
            self.mtp = PredictionModule(
                self.embed, self.head, hidden_size, rms_norm_eps,
                **sparse) if num_nextn_predict_layers else None

    def hybrid_forward(self, F, tokens):
        h = self.embed(tokens)
        tables = rotary.rotary_tables(self._inv_freq, tokens.shape[1],
                                      interleaved=True)
        stats = []
        for layer in self.layers._children.values():
            out = layer(h, *tables)
            if isinstance(out, (list, tuple)):
                h, layer_stats = out
                stats.append(layer_stats)
            else:
                h = out
        h = self.norm(h)
        logits = self.head(h)
        if self.mtp is None:
            return logits, F.stack(*stats, axis=0)
        ahead, module_stats = self.mtp(h, tokens, *tables)
        return logits, ahead, F.stack(*stats, module_stats, axis=0)
