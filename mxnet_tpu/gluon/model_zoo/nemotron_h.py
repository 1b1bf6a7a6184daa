"""Nemotron-H: a decoder-only language model whose layers are of three
kinds in one published pattern: Mamba-2 (`M`), grouped-query causal
attention (`*`) and a latent mixture of experts (`E`) (ref: the
`nemotron_h` family's config.json, e.g. NVIDIA-Nemotron-3-Super-120B-A12B;
Mamba-2 is arXiv:2405.21060).

Every layer is pre-norm and residual, `h <- h + mixer(RMSNorm(h))`; a
final RMSNorm and an untied head follow; no bias anywhere except the
convolution's.  The model is a plain HybridBlock stack over registered ops
(`causal_conv1d`, `ssd_scan`, `dot_product_attention`, `moe_route`,
`moe_experts`, `RMSNorm`, `FullyConnected`), so `SPMDTrainer` compiles it
into one program and a profile reads it by those names.

Each layer owns its parameters directly: under `SPMDTrainer(remat=True)`
a layer is then ONE recomputed segment, not a nest of them: its input
and what its attention kernel wrote for the backward (`ops/residuals.py`:
the output and softmax statistics) are all the forward keeps.

An expert layer HOLDS `experts_held` of the `n_routed_experts` the router
scores (ids from `first_expert`), as one chip of an expert-parallel group
does: it routes over all of them and adds its own experts' part
(`parallel/moe.py`).  The default holds them all.
"""
from __future__ import annotations

import math

import numpy as np

from ... import initializer as init_mod
from ...base import MXNetError
from .. import nn
from ..block import HybridBlock
from ._decoder import FP32 as _FP32
from ._decoder import Head as _Head
from ._decoder import Layer as _Layer
from ._decoder import project as _project

__all__ = ["NemotronHModel", "MambaLayer", "AttentionLayer",
           "LatentMoELayer", "nemotron_h"]


class _ALog(init_mod.Initializer):
    """A = uniform(1, 16), stored as its logarithm (Mamba-2)."""

    def _init_weight(self, name, arr):
        arr[:] = np.log(np.random.uniform(1.0, 16.0, arr.shape))


class _DtBias(init_mod.Initializer):
    """softplus^-1 of a step drawn log-uniform in [dt_min, dt_max] and
    held above `floor` (Mamba-2)."""

    def __init__(self, dt_min, dt_max, floor):
        super().__init__(dt_min=dt_min, dt_max=dt_max, floor=floor)
        self._range, self._floor = (dt_min, dt_max), floor

    def _init_weight(self, name, arr):
        lo, hi = (math.log(v) for v in self._range)
        dt = np.maximum(np.exp(np.random.uniform(lo, hi, arr.shape)),
                        self._floor)
        arr[:] = dt + np.log(-np.expm1(-dt))


class MambaLayer(_Layer):
    """Mamba-2 mixer: in-projection to [z | x B C | dt], causal depthwise
    convolution and SiLU on x B C, the selective scan, a gated group
    RMSNorm and the out-projection."""

    _FLOAT32 = ("A_log", "D", "dt_bias")

    def __init__(self, hidden_size, num_heads, head_dim, n_groups,
                 state_size, conv_kernel, chunk_size, eps,
                 time_step=(0.001, 0.1, 1e-4), **kwargs):
        super().__init__(hidden_size, eps, **kwargs)
        if num_heads % n_groups:
            raise MXNetError(f"{num_heads} heads over {n_groups} groups")
        self._heads, self._head_dim = num_heads, head_dim
        self._groups, self._state = n_groups, state_size
        self._chunk = chunk_size
        inner = num_heads * head_dim
        self._inner = inner
        self._conv_dim = inner + 2 * n_groups * state_size
        with self.name_scope():
            self.in_proj_weight = self.params.get(
                "in_proj_weight",
                shape=(inner + self._conv_dim + num_heads, hidden_size))
            self.conv_weight = self.params.get(
                "conv_weight", shape=(self._conv_dim, conv_kernel))
            self.conv_bias = self.params.get(
                "conv_bias", shape=(self._conv_dim,), init="zeros")
            self.dt_bias = self.params.get(
                "dt_bias", shape=(num_heads,), init=_DtBias(*time_step))
            self.A_log = self.params.get(
                "A_log", shape=(num_heads,), init=_ALog())
            self.D = self.params.get("D", shape=(num_heads,), init="ones")
            self.gate_norm_weight = self.params.get(
                "gate_norm_weight", shape=(inner,), init="ones")
            self.out_proj_weight = self.params.get(
                "out_proj_weight", shape=(hidden_size, inner))

    def mix(self, F, u, in_proj_weight, conv_weight, conv_bias, dt_bias,
            A_log, D, gate_norm_weight, out_proj_weight):
        b, s = u.shape[0], u.shape[1]
        inner, bc = self._inner, self._groups * self._state
        proj = _project(F, u, in_proj_weight)
        z = F.slice_axis(proj, axis=2, begin=0, end=inner)
        xbc = F.slice_axis(proj, axis=2, begin=inner,
                           end=inner + self._conv_dim)
        dt = F.slice_axis(proj, axis=2, begin=inner + self._conv_dim,
                          end=None)
        xbc = F.Activation(F.causal_conv1d(xbc, conv_weight, conv_bias),
                           act_type="silu")
        x = F.reshape(F.slice_axis(xbc, axis=2, begin=0, end=inner),
                      shape=(b, s, self._heads, self._head_dim))
        bmat = F.reshape(
            F.slice_axis(xbc, axis=2, begin=inner, end=inner + bc),
            shape=(b, s, self._groups, self._state))
        cmat = F.reshape(
            F.slice_axis(xbc, axis=2, begin=inner + bc, end=None),
            shape=(b, s, self._groups, self._state))
        y = F.ssd_scan(x, dt, A_log, bmat, cmat, D, dt_bias,
                       chunk=self._chunk)
        gated = F.reshape(y, shape=(b, s, inner)) \
            * F.Activation(z, act_type="silu")
        width = inner // self._groups
        normed = F.RMSNorm(
            F.reshape(gated, shape=(b, s, self._groups, width)),
            F.reshape(gate_norm_weight, shape=(self._groups, width)),
            eps=self._eps)
        return _project(F, F.reshape(normed, shape=(b, s, inner)),
                        out_proj_weight)


class AttentionLayer(_Layer):
    """Causal grouped-query self-attention, no positional embedding (the
    family applies none), no bias."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim, eps,
                 **kwargs):
        super().__init__(hidden_size, eps, **kwargs)
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._scale = head_dim ** -0.5
        with self.name_scope():
            self.q_proj_weight = self.params.get(
                "q_proj_weight", shape=(num_heads * head_dim, hidden_size))
            self.k_proj_weight = self.params.get(
                "k_proj_weight", shape=(num_kv_heads * head_dim, hidden_size))
            self.v_proj_weight = self.params.get(
                "v_proj_weight", shape=(num_kv_heads * head_dim, hidden_size))
            self.o_proj_weight = self.params.get(
                "o_proj_weight", shape=(hidden_size, num_heads * head_dim))

    def mix(self, F, u, q_proj_weight, k_proj_weight, v_proj_weight,
            o_proj_weight):
        out = F.dot_product_attention(
            _project(F, u, q_proj_weight), _project(F, u, k_proj_weight),
            _project(F, u, v_proj_weight), None, num_heads=self._heads,
            num_kv_heads=self._kv_heads, scale=self._scale, causal=True)
        return _project(F, out, o_proj_weight)


class LatentMoELayer(_Layer):
    """Latent mixture of experts: a float32 sigmoid router over all
    `n_routed_experts` on the full-width input; the chosen experts work
    in a `latent_size`-wide space behind one shared down- and
    up-projection; a shared expert at full width beside them; relu^2,
    not gated.  Returns (output, [rows of each held expert..., dropped])."""

    _FLOAT32 = ("router_weight", "router_bias")

    def __init__(self, hidden_size, n_routed_experts, top_k, latent_size,
                 expert_size, shared_size, scale, eps, experts_held=None,
                 first_expert=0, **kwargs):
        super().__init__(hidden_size, eps, **kwargs)
        held = n_routed_experts if experts_held is None else experts_held
        if first_expert + held > n_routed_experts:
            raise MXNetError(
                f"experts {first_expert}..{first_expert + held - 1} of "
                f"{n_routed_experts}")
        self._top_k, self._scale = top_k, float(scale)
        self._held, self._first = held, first_expert
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(n_routed_experts, hidden_size),
                dtype=_FP32)
            # moves the selection only; balanced from load statistics in
            # the published recipe, never by the optimizer
            self.router_bias = self.params.get(
                "router_bias", shape=(n_routed_experts,), dtype=_FP32,
                init="zeros", grad_req="null")
            self.latent_down_weight = self.params.get(
                "latent_down_weight", shape=(latent_size, hidden_size))
            self.latent_up_weight = self.params.get(
                "latent_up_weight", shape=(hidden_size, latent_size))
            self.experts_w1 = self.params.get(
                "experts_w1", shape=(held, latent_size, expert_size))
            self.experts_w2 = self.params.get(
                "experts_w2", shape=(held, expert_size, latent_size))
            self.shared_up_weight = self.params.get(
                "shared_up_weight", shape=(shared_size, hidden_size))
            self.shared_down_weight = self.params.get(
                "shared_down_weight", shape=(hidden_size, shared_size))

    def mix(self, F, u, router_weight, router_bias, latent_down_weight,
            latent_up_weight, experts_w1, experts_w2, shared_up_weight,
            shared_down_weight):
        b, s = u.shape[0], u.shape[1]
        tokens = F.reshape(u, shape=(b * s, self._hidden))
        token, weight, group_sizes, dropped = F.moe_route(
            tokens, router_weight, router_bias, top_k=self._top_k,
            scale=self._scale, first_expert=self._first,
            num_local=self._held)
        routed = F.moe_experts(_project(F, tokens, latent_down_weight),
                               token, weight, group_sizes, experts_w1,
                               experts_w2)
        shared = _project(F, F.square(F.relu(
            _project(F, tokens, shared_up_weight))), shared_down_weight)
        out = _project(F, routed, latent_up_weight) + shared
        stats = F.concat(group_sizes, F.reshape(dropped, shape=(1,)), dim=0)
        return F.reshape(out, shape=(b, s, self._hidden)), stats


class NemotronHModel(HybridBlock):
    """forward(tokens (B, S)) -> (logits (B, S, vocab), expert statistics
    (expert layers, experts_held + 1) int32: rows of each held expert and
    the assignments dropped, which is 0).  Keys are the family's own
    (`config.json`); `pattern` is `hybrid_override_pattern`, or the part
    of it this model keeps."""

    def __init__(self, pattern, vocab_size, hidden_size,
                 mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size,
                 conv_kernel, chunk_size, num_attention_heads,
                 num_key_value_heads, head_dim, n_routed_experts,
                 num_experts_per_tok, moe_latent_size, moe_intermediate_size,
                 moe_shared_expert_intermediate_size, routed_scaling_factor,
                 layer_norm_epsilon=1e-5, time_step_min=0.001,
                 time_step_max=0.1, time_step_floor=1e-4,
                 experts_held=None, first_expert=0, **kwargs):
        super().__init__(**kwargs)
        eps = layer_norm_epsilon
        if set(pattern) - set("M*E") or not pattern:
            raise MXNetError(f"layer pattern {pattern!r}: only M, * and E")
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, hidden_size,
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i, kind in enumerate(pattern):
                if kind == "M":
                    layer = MambaLayer(
                        hidden_size, mamba_num_heads, mamba_head_dim,
                        n_groups, ssm_state_size, conv_kernel, chunk_size,
                        eps, (time_step_min, time_step_max, time_step_floor),
                        prefix=f"layer{i}_mamba_")
                elif kind == "*":
                    layer = AttentionLayer(
                        hidden_size, num_attention_heads,
                        num_key_value_heads, head_dim, eps,
                        prefix=f"layer{i}_attn_")
                else:
                    layer = LatentMoELayer(
                        hidden_size, n_routed_experts, num_experts_per_tok,
                        moe_latent_size, moe_intermediate_size,
                        moe_shared_expert_intermediate_size,
                        routed_scaling_factor, eps, experts_held,
                        first_expert, prefix=f"layer{i}_moe_")
                self.layers.add(layer)
            self.head = _Head(hidden_size, vocab_size, eps, prefix="head_")

    def hybrid_forward(self, F, tokens):
        h = self.embed(tokens)
        stats = []
        for layer in self.layers._children.values():
            out = layer(h)
            if isinstance(out, (list, tuple)):
                h, layer_stats = out
                stats.append(layer_stats)
            else:
                h = out
        logits = self.head(h)
        if not stats:
            return logits
        return logits, F.stack(*stats, axis=0)


def nemotron_h(pattern="MEM*EMEMEME", **widths):
    """A Nemotron-H stack; `widths` are NemotronHModel's arguments."""
    return NemotronHModel(pattern, **widths)
