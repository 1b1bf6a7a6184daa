"""What the zoo's decoder stacks share (`nemotron_h.py`, `laguna.py`,
`evabyte.py`, `joyai.py`, `lfm2.py`, `ouro.py`, `phi4flash.py`): the
pre-norm residual sub-layer (with a second gain, normed on both sides:
`ouro.py`), the norm itself (RMSNorm, or with a bias LayerNorm:
`phi4flash.py`), the bias-free projection, the gated MLP, a layer's MLP half (dense, or
the gated mixture of experts, with a shared expert or without one), the
final norm and the head (an array of its own, or the embedding's: tied),
and the rule that named parameters keep float32 under `cast`.  A norm's
`offset` is added to its stored gain (1: the unit offset, the gain
stored from zero)."""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock

FP32 = "float32"


def project(F, x, weight):
    return F.FullyConnected(x, weight, None, num_hidden=weight.shape[0],
                            no_bias=True, flatten=False)


def gated_mlp(F, x, gate_weight, up_weight, down_weight):
    """(silu(x G) * (x U)) Dn."""
    return project(F, F.Activation(project(F, x, gate_weight),
                                   act_type="silu")
                   * project(F, x, up_weight), down_weight)


NORMS = ("rms", "layer")


def normed(F, x, weight, eps, offset=0.0, bias=None):
    """RMSNorm(x; weight), or with a `bias` LayerNorm(x; weight, bias):
    the mean subtracted, gain and bias; float32 inside either way (the
    `LayerNorm` op computes in its input's type, so the input is cast)."""
    if bias is None:
        return F.RMSNorm(x, weight, eps=eps, offset=offset)
    return F.cast(F.LayerNorm(F.cast(x, dtype=FP32), weight, bias, eps=eps),
                  dtype=x.dtype)


def norm_residual(F, x, norm_weight, eps, mix, *args, offset=0.0,
                  post=None, norm_bias=None, **params):
    """x + mix(F, norm(x), ...), the norm `normed`'s (LayerNorm where a
    `norm_bias` comes), or with a second gain `post` the sub-layer normed
    on both sides, x + RMSNorm(mix(...); post).  Where `mix` gives
    (output, statistics...) the statistics pass through beside the
    sum."""
    mixed = mix(F, normed(F, x, norm_weight, eps, offset, norm_bias),
                *args, **params)
    mixed, *stats = mixed if isinstance(mixed, (list, tuple)) else (mixed,)
    if post is not None:
        mixed = F.RMSNorm(mixed, post, eps=eps, offset=offset)
    return (x + mixed, *stats) if stats else x + mixed


class KeepsFloat32(HybridBlock):
    """A block whose parameters named in `_FLOAT32` keep float32 under
    `cast`, as the published model keeps them."""

    _FLOAT32 = ()

    def cast(self, dtype):
        self._clear_cached_op()
        for name, p in self._reg_params.items():
            p.cast(FP32 if name in self._FLOAT32 else dtype)


class Layer(KeepsFloat32):
    """h + mixer(norm(h)); subclasses give `mix`.  `norm`: "rms", or
    "layer" for LayerNorm, which brings a `norm_bias` beside every gain
    `_norm_gain` makes."""

    def __init__(self, hidden_size, eps, norm_offset=0.0, norm="rms",
                 **kwargs):
        super().__init__(**kwargs)
        if norm not in NORMS:
            raise MXNetError(f"norm {norm!r} (of {NORMS})")
        self._hidden, self._eps, self._offset = hidden_size, eps, norm_offset
        self._layer_norm = norm == "layer"
        with self.name_scope():
            self.norm_weight = self._norm_gain("norm_weight")

    def _norm_gain(self, name):
        """A gain that starts the norm at identity, and under LayerNorm
        its bias `<name less _weight>_bias`, zero."""
        if self._layer_norm:
            bias = name[:-len("weight")] + "bias"
            setattr(self, bias, self.params.get(
                bias, shape=(self._hidden,), init="zeros"))
        return self.params.get(name, shape=(self._hidden,),
                               init="zeros" if self._offset else "ones")

    def hybrid_forward(self, F, x, norm_weight, **params):
        return norm_residual(F, x, norm_weight, self._eps, self.mix,
                             offset=self._offset, **params)


class MLPLayer(Layer):
    """A layer whose second pre-norm sub-layer is a dense gated MLP or a
    gated mixture of experts, with a shared expert (`laguna.py`,
    `joyai.py`) or without one (`lfm2.py`: `shared_size` 0); subclasses
    bring the first sub-layer and call
    `_mlp_params` last in their `name_scope`, `mlp` last in their
    `hybrid_forward`.  A sparse layer HOLDS `experts_held` of the
    `num_experts` its router scores (ids from `first_expert`) and tells
    `moe_experts` the rows it expects under even routing."""

    _FLOAT32 = ("router_weight", "router_bias")

    def _matrix(self, name, shape):
        setattr(self, name, self.params.get(name, shape=shape))

    def _gated(self, name, width):
        self._matrix(f"{name}_gate_weight", (width, self._hidden))
        self._matrix(f"{name}_up_weight", (width, self._hidden))
        self._matrix(f"{name}_down_weight", (self._hidden, width))

    def _mlp_params(self, mlp_size=None, num_experts=0, top_k=0,
                    expert_size=0, shared_size=0, scale=1.0,
                    experts_held=None, first_expert=0):
        """`mlp_size`: the dense MLP's width; None: the expert layer,
        `num_experts` scored, `top_k` chosen, `expert_size` wide, beside
        a shared expert `shared_size` wide (0: none)."""
        d = self._hidden
        self._sparse = mlp_size is None
        self.mlp_norm_weight = self._norm_gain("mlp_norm_weight")
        if not self._sparse:
            self._gated("mlp", mlp_size)
            return
        held = num_experts if experts_held is None else experts_held
        if first_expert + held > num_experts:
            raise MXNetError(
                f"experts {first_expert}..{first_expert + held - 1} "
                f"of {num_experts}")
        self._top_k, self._scale = top_k, float(scale)
        self._held, self._first = held, first_expert
        # a token's assignments that land here under even routing
        self._held_share = top_k * held / num_experts
        self.router_weight = self.params.get(
            "router_weight", shape=(num_experts, d), dtype=FP32)
        # moves the selection only, never trained by the optimizer
        self.router_bias = self.params.get(
            "router_bias", shape=(num_experts,), dtype=FP32,
            init="zeros", grad_req="null")
        # gate and up side by side: one grouped product for both
        self._matrix("experts_w1", (held, d, 2 * expert_size))
        self._matrix("experts_w2", (held, expert_size, d))
        if shared_size:
            self._gated("shared", shared_size)

    def mlp(self, F, h, mlp_norm_weight, mlp_norm_bias=None, **params):
        """h + MLP(norm(h)) (with `mlp_norm_bias` LayerNorm), and from a
        sparse layer [rows of each held expert..., dropped] beside it."""
        if mlp_norm_bias is not None:
            params["norm_bias"] = mlp_norm_bias
        return norm_residual(F, h, mlp_norm_weight, self._eps,
                             self.experts if self._sparse else self.dense,
                             **params)

    def dense(self, F, u, mlp_gate_weight, mlp_up_weight, mlp_down_weight):
        return gated_mlp(F, u, mlp_gate_weight, mlp_up_weight,
                         mlp_down_weight)

    def experts(self, F, u, router_weight, router_bias, experts_w1,
                experts_w2, **shared):
        b, s = u.shape[0], u.shape[1]
        tokens = F.reshape(u, shape=(b * s, self._hidden))
        token, weight, group_sizes, dropped = F.moe_route(
            tokens, router_weight, router_bias, top_k=self._top_k,
            scale=self._scale, first_expert=self._first,
            num_local=self._held)
        out = F.moe_experts(tokens, token, weight, group_sizes, experts_w1,
                            experts_w2, form="silu_gated",
                            expected_rows=int(b * s * self._held_share))
        if shared:
            out = out + gated_mlp(F, tokens, shared["shared_gate_weight"],
                                  shared["shared_up_weight"],
                                  shared["shared_down_weight"])
        stats = F.concat(group_sizes, F.reshape(dropped, shape=(1,)), dim=0)
        return F.reshape(out, shape=(b, s, self._hidden)), stats


class Head(HybridBlock):
    """Final norm (`norm`: "rms", or "layer" for LayerNorm with its
    bias) and the vocabulary projection, by an array of the
    head's own or by `tied`, the embedding's (vocab_size, hidden_size)
    Parameter: one array, which the gradients of both uses reach; with
    `logits_dtype` the product's operands are cast to it first (float32
    logits from bfloat16 weights)."""

    def __init__(self, hidden_size, vocab_size, eps, norm_offset=0.0,
                 logits_dtype=None, tied=None, norm="rms", **kwargs):
        super().__init__(**kwargs)
        if norm not in NORMS:
            raise MXNetError(f"norm {norm!r} (of {NORMS})")
        self._eps, self._offset, self._dtype = eps, norm_offset, logits_dtype
        with self.name_scope():
            self.norm_weight = self.params.get(
                "norm_weight", shape=(hidden_size,),
                init="zeros" if norm_offset else "ones")
            if norm == "layer":
                self.norm_bias = self.params.get(
                    "norm_bias", shape=(hidden_size,), init="zeros")
            self.weight = tied if tied is not None else self.params.get(
                "weight", shape=(vocab_size, hidden_size))

    def hybrid_forward(self, F, x, norm_weight, weight, norm_bias=None):
        x = normed(F, x, norm_weight, self._eps, self._offset, norm_bias)
        if self._dtype is not None:
            x, weight = (F.cast(a, dtype=self._dtype) for a in (x, weight))
        return project(F, x, weight)
