"""Phi-4-mini-flash (`phi4flash`, the SambaY decoder-hybrid-decoder): a
self-decoder of Mamba-1 and sliding-window layers, one full-attention
layer, and a cross-decoder whose layers compute no scan and no keys:
they read ONE memory (a Mamba layer's scan output) through Gated Memory
Units and ONE key/value set (the full layer's) through cross-attention
(ref: microsoft/Phi-4-mini-flash-reasoning config.json; arXiv:2507.06607
over Samba, arXiv:2406.07522, Mamba-1, arXiv:2312.00752, and
Differential Attention, arXiv:2410.05258).

Which layer is what (`layer_kinds`), n = `num_hidden_layers`, n / 2 even:
layer i is a state-space layer if i % `mb_per_layer` == 0, else an
attention layer.  i < n / 2: `mamba`, then `window` (`sliding_window`).
i = n / 2: `mamba`, whose scan output y is also kept as the memory m;
i = n / 2 + 1: `full`, whose k and v are also kept.  Later layers:
`gmu` (reads m) where a state-space layer would stand, `cross` (reads k,
v) where an attention layer would.  n = 8: M W M W M F G X.

Every layer: x <- x + mixer(LN(x)); x <- x + MLP(LN(x)), LN a LayerNorm
with gain and bias, the MLP gated (SiLU), no bias; then a final LayerNorm
and the head, the embedding's array.  No positional encoding.

  * `mamba`: [x ; z] = u W_in; x = SiLU(causal_conv1d(x)); [delta ; B ;
    C] = x W_x; y = selective_scan(x, delta W_dt, A_log, B, C, D,
    dt_bias) (op `selective_scan`: softplus inside, float32 state); out =
    (y * SiLU(z)) W_out.  Traced under `MAMBA_NAME`.
  * `gmu`: out = (m * SiLU(u W_1)) W_2.  Traced under `GMU_NAME`.
  * `window` / `full` / `cross`: differential attention (op
    `differential_attention`) over q = u W_q and k, v = u W_k, u W_v, a
    cross layer's k and v the full layer's; lambda_init = 0.8 - 0.6
    exp(-0.3 i) for layer i; then W_o.

A plain HybridBlock stack over registered ops, each layer owning its
parameters directly: under `SPMDTrainer(remat=True)` a layer is ONE
recomputed segment.  m, k and v cross segment boundaries as outputs of
one segment and arguments of others: kept once, their cotangents summed
over every reader by autodiff (the mirror image of `ouro.py`, where one
parameter has many readers).
"""
from __future__ import annotations

import math

import jax
import numpy as np

from ... import initializer
from ...base import MXNetError
from .. import nn
from ..block import HybridBlock
from ._decoder import FP32, Head, MLPLayer, normed, project
from .nemotron_h import _DtBias

__all__ = ["Phi4FlashModel", "MambaLayer", "AttentionLayer", "GMULayer",
           "CrossLayer", "layer_kinds", "MAMBA_NAME", "GMU_NAME"]

#: the scopes a Mamba mixer and a Gated Memory Unit are traced under:
#: components of the name stack of every instruction of theirs
MAMBA_NAME = "mamba"
GMU_NAME = "gmu"


def layer_kinds(num_hidden_layers, mb_per_layer=2):
    """The kind of every layer, by the published rule."""
    n, half = num_hidden_layers, num_hidden_layers // 2
    if n < 4 or n % 2 or half % mb_per_layer:
        raise MXNetError(
            f"{n} layers: the self-decoder's {half} must be whole periods "
            f"of {mb_per_layer}, and the cross-decoder as deep")
    kinds = []
    for i in range(n):
        ssm = i % mb_per_layer == 0
        if i <= half + 1:
            kinds.append("mamba" if ssm else "full" if i == half + 1
                         else "window")
        else:
            kinds.append("gmu" if ssm else "cross")
    return tuple(kinds)


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


class _ALog(initializer.Initializer):
    """A = 1 .. N along every channel's row, stored as its logarithm
    (Mamba-1's S4D-real)."""

    def _init_weight(self, name, arr):
        arr[:] = np.log(np.arange(1, arr.shape[1] + 1, dtype=np.float64))


#: Mamba-1's step range: dt_min, dt_max, floor (`nemotron_h._DtBias`:
#: softplus^-1 of a step drawn log-uniform between the first two)
_TIME_STEP = (1e-3, 1e-1, 1e-4)


class _Layer(MLPLayer):
    """x + mixer(LN(x)), then x + MLP(LN(x)).  forward(x, *read) -> x, or
    (x, *handed): what the mixer reads from and hands to other layers.
    Subclasses make their parameters in `_mixer_params` and give `mix(F,
    u, *read, *parameters in that order) -> mixed or (mixed, *handed)`."""

    def __init__(self, hidden_size, intermediate_size, eps, **kwargs):
        super().__init__(hidden_size, eps, norm="layer", **kwargs)
        with self.name_scope():
            before = set(self._reg_params)
            self._mixer_params()
            self._mixer = tuple(n for n in self._reg_params
                                if n not in before)
            self._mlp_params(intermediate_size)

    def hybrid_forward(self, F, x, *read, norm_weight, norm_bias,
                       mlp_norm_weight, **params):
        mixer = [params.pop(name) for name in self._mixer]
        mixed = self.mix(F, normed(F, x, norm_weight, self._eps,
                                   bias=norm_bias), *read, *mixer)
        mixed, *handed = mixed if isinstance(mixed, tuple) else (mixed,)
        out = self.mlp(F, x + mixed, mlp_norm_weight, **params)
        return (out, *handed) if handed else out


class MambaLayer(_Layer):
    """Mamba-1: forward(x) -> x, or with `memory_output` (x, y): the
    scan's output before the gate, the memory of the Gated Memory
    Units; with `scan_probe` also the scan's own inputs (x, delta, B,
    C) after it, so that a comparison can hold the op alone to a
    reference on the very inputs it had."""

    _FLOAT32 = ("A_log", "D", "dt_bias")

    def __init__(self, hidden_size, intermediate_size, eps, d_state=16,
                 d_conv=4, expand=2, dt_rank=None, memory_output=False,
                 scan_probe=False, **kwargs):
        self._inner = expand * hidden_size
        self._state, self._taps = d_state, d_conv
        self._rank = dt_rank or -(-hidden_size // 16)
        self._memory_output = memory_output or scan_probe
        self._scan_probe = scan_probe
        super().__init__(hidden_size, intermediate_size, eps, **kwargs)

    def _mixer_params(self):
        d, inner, taps = self._hidden, self._inner, self._taps
        self._matrix("in_proj_weight", (2 * inner, d))
        self.conv_weight = self.params.get(
            "conv_weight", shape=(inner, taps),
            init=initializer.Uniform(taps ** -0.5))
        self.conv_bias = self.params.get(
            "conv_bias", shape=(inner,),
            init=initializer.Uniform(taps ** -0.5))
        self._matrix("x_proj_weight", (self._rank + 2 * self._state, inner))
        self._matrix("dt_proj_weight", (inner, self._rank))
        self.dt_bias = self.params.get("dt_bias", shape=(inner,),
                                       dtype=FP32, init=_DtBias(*_TIME_STEP))
        self.A_log = self.params.get("A_log", shape=(inner, self._state),
                                     dtype=FP32, init=_ALog())
        self.D = self.params.get("D", shape=(inner,), dtype=FP32,
                                 init="ones")
        self._matrix("out_proj_weight", (d, inner))

    def mix(self, F, u, in_proj_weight, conv_weight, conv_bias,
            x_proj_weight, dt_proj_weight, dt_bias, A_log, D,
            out_proj_weight):
        rank, n = self._rank, self._state
        with jax.named_scope(MAMBA_NAME):
            # the matrix is cut, not what it gives
            w_x, w_z = F.split(in_proj_weight, num_outputs=2, axis=0)
            x = F.Activation(
                F.causal_conv1d(project(F, u, w_x), conv_weight, conv_bias),
                act_type="silu")
            dbc = project(F, x, x_proj_weight)
            delta = project(F, F.slice_axis(dbc, axis=2, begin=0, end=rank),
                            dt_proj_weight)
            b = F.slice_axis(dbc, axis=2, begin=rank, end=rank + n)
            c = F.slice_axis(dbc, axis=2, begin=rank + n, end=None)
            y = F.selective_scan(x, delta, A_log, b, c, D, dt_bias)
            out = project(
                F, y * F.Activation(project(F, u, w_z), act_type="silu"),
                out_proj_weight)
        if self._scan_probe:
            return out, y, x, delta, b, c
        return (out, y) if self._memory_output else out


class GMULayer(_Layer):
    """A Gated Memory Unit: forward(x, m) -> x; out = (m * SiLU(u W_1))
    W_2, m another layer's scan output."""

    def __init__(self, hidden_size, intermediate_size, eps, expand=2,
                 **kwargs):
        self._inner = expand * hidden_size
        super().__init__(hidden_size, intermediate_size, eps, **kwargs)

    def _mixer_params(self):
        self._matrix("gmu_in_proj_weight", (self._inner, self._hidden))
        self._matrix("gmu_out_proj_weight", (self._hidden, self._inner))

    def mix(self, F, u, memory, gmu_in_proj_weight, gmu_out_proj_weight):
        with jax.named_scope(GMU_NAME):
            return project(
                F, memory * F.Activation(project(F, u, gmu_in_proj_weight),
                                         act_type="silu"),
                gmu_out_proj_weight)


class _Differential(_Layer):
    """What the three attention kinds share: W_q, W_o, the four lambda
    vectors, the sub-norm's gain, and the op."""

    _FLOAT32 = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
                "subln_weight")

    def __init__(self, hidden_size, intermediate_size, eps, num_heads,
                 num_kv_heads, layer_index, window=0, **kwargs):
        if hidden_size % num_heads or num_heads % num_kv_heads \
                or num_kv_heads % 2:
            raise MXNetError(f"{num_heads} heads over {num_kv_heads} in "
                             f"pairs at hidden {hidden_size}")
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._head = hidden_size // num_heads
        self._window = window
        self._lambda_init = lambda_init(layer_index)
        super().__init__(hidden_size, intermediate_size, eps, **kwargs)

    def _own_keys(self):
        """W_k and W_v, where the layer has its own."""

    def _mixer_params(self):
        d, head = self._hidden, self._head
        self._matrix("q_proj_weight", (d, d))
        self._own_keys()
        for name in self._FLOAT32[:4]:
            setattr(self, name, self.params.get(
                name, shape=(head,), dtype=FP32,
                init=initializer.Normal(0.1)))
        self.subln_weight = self.params.get(
            "subln_weight", shape=(2 * head,), dtype=FP32, init="ones")
        self._matrix("o_proj_weight", (d, d))

    def attend(self, F, q, k, v, lambdas, subln_weight, o_proj_weight):
        return project(F, F.differential_attention(
            q, k, v, *lambdas, subln_weight, num_heads=self._heads,
            num_kv_heads=self._kv_heads, window=self._window,
            lambda_init=self._lambda_init, eps=self._eps), o_proj_weight)


class AttentionLayer(_Differential):
    """Differential self-attention under a sliding `window` (0: the one
    full layer): forward(x) -> x, or with `kv_output` (x, k, v), the
    key/value set of the cross-decoder."""

    def __init__(self, *args, kv_output=False, **kwargs):
        self._kv_output = kv_output
        super().__init__(*args, **kwargs)

    def _own_keys(self):
        rows = self._kv_heads * self._head
        self._matrix("k_proj_weight", (rows, self._hidden))
        self._matrix("v_proj_weight", (rows, self._hidden))

    def mix(self, F, u, q_proj_weight, k_proj_weight, v_proj_weight,
            *rest):
        k, v = project(F, u, k_proj_weight), project(F, u, v_proj_weight)
        out = self.attend(F, project(F, u, q_proj_weight), k, v, rest[:4],
                          *rest[4:])
        return (out, k, v) if self._kv_output else out


class CrossLayer(_Differential):
    """Differential cross-attention: forward(x, k, v) -> x, the keys and
    values the full layer's; W_q and W_o alone."""

    def mix(self, F, u, k, v, q_proj_weight, *rest):
        return self.attend(F, project(F, u, q_proj_weight), k, v, rest[:4],
                           *rest[4:])


class Phi4FlashModel(HybridBlock):
    """forward(tokens (B, S)) -> logits (B, S, vocab), or with
    `memory_output` (logits, m (B, S, expand * hidden)): layer n / 2's
    scan output, what every Gated Memory Unit reads; with `scan_probe`
    that scan's inputs x, delta, B, C follow m.  Keys are the
    config's own; `num_hidden_layers` gives the depth under
    `layer_kinds`; `d_state`, `d_conv`, `expand`, `dt_rank` (None: hidden
    / 16, rounded up) are Mamba-1's, which the config leaves out."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, num_attention_heads,
                 num_key_value_heads, sliding_window, mb_per_layer=2,
                 layer_norm_eps=1e-5, tie_word_embeddings=True,
                 mlp_bias=False, lm_head_bias=False, d_state=16, d_conv=4,
                 expand=2, dt_rank=None, memory_output=False,
                 scan_probe=False, **kwargs):
        super().__init__(**kwargs)
        if not tie_word_embeddings or mlp_bias or lm_head_bias:
            raise MXNetError(
                f"tie_word_embeddings {tie_word_embeddings} (only True), "
                f"mlp_bias {mlp_bias}, lm_head_bias {lm_head_bias} (only "
                "False)")
        self.kinds = layer_kinds(num_hidden_layers, mb_per_layer)
        self._memory_output = memory_output or scan_probe
        self._half = half = num_hidden_layers // 2
        common = (hidden_size, intermediate_size, layer_norm_eps)
        heads = (num_attention_heads, num_key_value_heads)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, hidden_size,
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i, kind in enumerate(self.kinds):
                prefix = f"layer{i}_"
                if kind == "mamba":
                    layer = MambaLayer(
                        *common, d_state, d_conv, expand, dt_rank,
                        memory_output=i == half,
                        scan_probe=scan_probe and i == half, prefix=prefix)
                elif kind == "gmu":
                    layer = GMULayer(*common, expand, prefix=prefix)
                elif kind == "cross":
                    layer = CrossLayer(*common, *heads, i, prefix=prefix)
                else:
                    layer = AttentionLayer(
                        *common, *heads, i,
                        window=sliding_window if kind == "window" else 0,
                        kv_output=kind == "full", prefix=prefix)
                self.layers.add(layer)
            self.head = Head(hidden_size, vocab_size, layer_norm_eps,
                             tied=self.embed.weight, norm="layer",
                             prefix="head_")

    def hybrid_forward(self, F, tokens):
        h = self.embed(tokens)
        memory = keys = None
        probe = ()
        for i, (kind, layer) in enumerate(zip(
                self.kinds, self.layers._children.values())):
            if kind == "gmu":
                h = layer(h, memory)
            elif kind == "cross":
                h = layer(h, *keys)
            elif kind == "full":
                h, *keys = layer(h)
            elif i == self._half:
                h, memory, *probe = layer(h)
            else:
                h = layer(h)
        logits = self.head(h)
        return (logits, memory, *probe) if self._memory_output else logits
