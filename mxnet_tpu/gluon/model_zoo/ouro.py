"""Ouro (`ouro`): a decoder-only language model whose stack of layers
runs `total_ut_steps` times on its own output, with an exit after every
pass (ref: the `ouro` family's config.json, e.g. ByteDance/Ouro-2.6B,
and the modeling file beside it; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741, for the objective).

A layer is two residual sub-layers normed on BOTH sides (four gains),

    h <- h + RMSNorm(attention(RMSNorm(h; g1)); g2)
    h <- h + RMSNorm(mlp(RMSNorm(h; g3)); g4)

attention over `num_attention_heads` query heads and
`num_key_value_heads` key/value heads of `head_dim`, rotary on all of a
head's dimensions (i paired with i + d / 2, `rope_theta`), causal
softmax at d^-1/2; the MLP the gated three-matrix form; no bias.  One
pass is layers 0 .. N - 1 in order and the model's final RMSNorm; for
t = 1 .. T the SAME layers read the normed state z^(t-1) of the pass
before (z^(0) the embeddings) and give z^(t), and exit t reads z^(t):

    logits^(t) = z^(t) W_head           (one untied array, all T exits)
    gate^(t)   = z^(t) w_gate + b_gate  (a scalar a token, float32)

lambda_t = sigmoid(gate^(t)) is the share of what is left that leaves at
exit t: p_t = lambda_t prod_{j<t} (1 - lambda_j), p_T the remainder
(`exit_pdf`).  Training weighs the T cross-entropies by that
distribution and rewards its entropy (`exit_loss`); all T passes run
for every token.

The model is a plain HybridBlock stack over registered ops
(`rotary_embedding`, `dot_product_attention`, `RMSNorm`,
`FullyConnected`), so `SPMDTrainer` compiles it
into one program and a profile reads it by those names.  The pass is
traced ONCE, under `PASS_NAME` (a component of the name stack of every
instruction of the looped stack), and run T times as the body of one
`lax.scan` that closes over the weights and hands out the T normed
states: the program holds N layer applications, not T N (the unrolled
form cost four times the compile and 0.75 GB of kernel code, PERF.md
PR 46), a weight's gradient is the sum over the trips, and the trace
counts a read in the body once a trip (`ActiveTrace.repeated`).  The T
exits are T calls of one block under its scope (`EXIT_NAME`), each
reading the same traced value of the head's and the gate's Parameters
(`ActiveTrace.value_of`).  Under `SPMDTrainer(remat=True)` every layer
APPLICATION is one recomputed segment (each keeps its input and what the
attention kernel wrote, `ops/residuals.py`; the scan stacks them over
the trips) and so is every exit: with `targets` an exit hands back a
token's loss and its gate logit, and the (tokens, vocabulary) logits
never leave its segment.  A model that is not traced (an eager call
before `hybridize()`) is refused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ... import random as rnd
from ...base import MXNetError
from ...ops import rotary
from .. import nn
from ..block import HybridBlock, current_trace
from ._decoder import (FP32, KeepsFloat32, Layer, gated_mlp, norm_residual,
                       project)

__all__ = ["OuroModel", "OuroLayer", "OuroExit", "exit_pdf", "exit_loss",
           "PASS_NAME", "EXIT_NAME"]

#: the scope the one traced pass of the stack (the scan's body) is under
PASS_NAME = "ut"
#: the exit block's scope, a component of its instructions' name stacks
EXIT_NAME = "exit"


class OuroLayer(Layer):
    """Both sub-layers, each normed before and after.  forward(h, cos,
    sin) -> h."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 mlp_size, eps, **kwargs):
        super().__init__(hidden_size, eps, **kwargs)
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_heads} heads over {num_kv_heads}")
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._attn_scale = head_dim ** -0.5
        d = hidden_size
        with self.name_scope():
            for name in ("post_norm_weight", "mlp_norm_weight",
                         "mlp_post_norm_weight"):
                setattr(self, name, self._norm_gain(name))
            for name, shape in (
                    ("q_proj", (num_heads * head_dim, d)),
                    ("k_proj", (num_kv_heads * head_dim, d)),
                    ("v_proj", (num_kv_heads * head_dim, d)),
                    ("o_proj", (d, num_heads * head_dim)),
                    ("mlp_gate", (mlp_size, d)), ("mlp_up", (mlp_size, d)),
                    ("mlp_down", (d, mlp_size))):
                setattr(self, f"{name}_weight",
                        self.params.get(f"{name}_weight", shape=shape))

    def hybrid_forward(self, F, x, cos, sin, norm_weight, post_norm_weight,
                       mlp_norm_weight, mlp_post_norm_weight, q_proj_weight,
                       k_proj_weight, v_proj_weight, o_proj_weight,
                       mlp_gate_weight, mlp_up_weight, mlp_down_weight):
        h = norm_residual(F, x, norm_weight, self._eps, self.attend, cos,
                          sin, q_proj_weight, k_proj_weight, v_proj_weight,
                          o_proj_weight, post=post_norm_weight)
        return norm_residual(F, h, mlp_norm_weight, self._eps, gated_mlp,
                             mlp_gate_weight, mlp_up_weight,
                             mlp_down_weight, post=mlp_post_norm_weight)

    def attend(self, F, u, cos, sin, q_proj_weight, k_proj_weight,
               v_proj_weight, o_proj_weight):
        q, k = F.rotary_embedding(
            project(F, u, q_proj_weight), project(F, u, k_proj_weight),
            cos, sin, num_heads=self._heads, num_kv_heads=self._kv_heads)
        out = F.dot_product_attention(
            q, k, project(F, u, v_proj_weight), None, causal=True,
            num_heads=self._heads, num_kv_heads=self._kv_heads,
            scale=self._attn_scale)
        return project(F, out, o_proj_weight)


class _FinalNorm(Layer):
    """The model's one final RMSNorm, applied after every pass."""

    def hybrid_forward(self, F, x, norm_weight):
        return F.RMSNorm(x, norm_weight, eps=self._eps)


class OuroExit(KeepsFloat32):
    """The head's array and the exit gate, read at every exit.
    forward(z (B, S, hidden)) -> (logits (B, S, vocab), gate logit (B, S)
    float32); forward(z, targets (B, S - 1)) -> (the cross-entropy of
    positions 0 .. S - 2 against `targets` (B, S - 1) float32, gate logit
    (B, S) float32): the form a training step takes, so that under remat
    the logits live and die inside this block's segment.  The gate's two
    parameters keep float32 under `cast`."""

    _FLOAT32 = ("gate_weight", "gate_bias")

    def __init__(self, hidden_size, vocab_size, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.head_weight = self.params.get(
                "head_weight", shape=(vocab_size, hidden_size))
            self.gate_weight = self.params.get(
                "gate_weight", shape=(hidden_size,), dtype=FP32)
            self.gate_bias = self.params.get(
                "gate_bias", shape=(1,), dtype=FP32, init="zeros")

    def hybrid_forward(self, F, z, targets=None, *, head_weight,
                       gate_weight, gate_bias):
        # a product of one column: summed in float32, off the MXU
        gate = F.sum(F.cast(z, dtype=FP32) * gate_weight, axis=-1) \
            + gate_bias
        logits = project(F, z, head_weight)
        if targets is None:
            return logits, gate
        # log-sum-exp less the target's score, the target taken by a
        # one-hot product: the reductions and their gradient are passes
        # over the logits in their own dtype, where log_softmax and pick
        # kept a float32 (tokens, vocabulary) array and scattered into a
        # second one
        x = F.cast(F.slice_axis(logits, axis=1, begin=0, end=-1), dtype=FP32)
        top = F.stop_gradient(F.max(x, axis=-1, keepdims=True))
        lse = F.log(F.sum(F.exp(x - top), axis=-1)) + F.squeeze(top, axis=-1)
        hit = F.one_hot(targets, depth=x.shape[-1])
        return lse - F.sum(hit * x, axis=-1), gate


class OuroModel(HybridBlock):
    """forward(tokens (B, S)) -> (logits of exit 1, ..., of exit T, each
    (B, S, vocab), gate logits (T, B, S) float32); forward(tokens,
    targets (B, S - 1)) -> (cross-entropies (T, B, S - 1) float32, gate
    logits (T, B, S) float32), what `exit_loss` takes.  Keys are the
    family's own (`config.json`); `num_hidden_layers` layers run
    `total_ut_steps` times."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, num_attention_heads,
                 num_key_value_heads, head_dim, rope_theta, total_ut_steps,
                 rms_norm_eps=1e-6, tie_word_embeddings=False,
                 hidden_act="silu", **kwargs):
        super().__init__(**kwargs)
        if num_hidden_layers < 1 or total_ut_steps < 1 \
                or tie_word_embeddings or hidden_act != "silu":
            raise MXNetError(
                f"{num_hidden_layers} layers run {total_ut_steps} times, "
                f"tie_word_embeddings {tie_word_embeddings} (only False), "
                f"hidden_act {hidden_act!r} (only 'silu')")
        self._steps = total_ut_steps
        self._inv_freq = rotary.default_inv_freq(rope_theta, head_dim)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, hidden_size,
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i in range(num_hidden_layers):
                self.layers.add(OuroLayer(
                    hidden_size, num_attention_heads, num_key_value_heads,
                    head_dim, intermediate_size, rms_norm_eps,
                    prefix=f"layer{i}_"))
            self.norm = _FinalNorm(hidden_size, rms_norm_eps,
                                   prefix="final_")
            self.exit = OuroExit(hidden_size, vocab_size,
                                 prefix=f"{EXIT_NAME}_")

    def hybrid_forward(self, F, tokens, targets=None):
        trace = current_trace()
        if trace is None:
            raise MXNetError(
                "OuroModel runs traced: hybridize() it, or hand it to "
                "SPMDTrainer (its passes are the trips of one lax.scan)")
        tables = rotary.rotary_tables(self._inv_freq, tokens.shape[1])
        embedded = self.embed(tokens)

        def one_pass(z, _):
            with jax.named_scope(PASS_NAME):
                for layer in self.layers._children.values():
                    z = layer(z, *tables)
                z = self.norm(z)
            return z, z

        # the pass is traced ONCE and run `total_ut_steps` times: the
        # weights are closed over (their gradient the sum over the trips),
        # the normed states come out stacked, and so does what the
        # layers' recomputed segments keep.  The body draws its keys,
        # which nothing in it uses, from a stream of its own: a key split
        # inside the body may not outlive it
        with trace.repeated(self._steps), \
                rnd.key_provider(rnd.KeyProvider(rnd.next_key())):
            _, states = lax.scan(one_pass, embedded, None,
                                 length=self._steps)
        exits = [self.exit(states[t]) if targets is None
                 else self.exit(states[t], targets)
                 for t in range(self._steps)]
        first, gates = zip(*exits)
        gates = F.stack(*gates, axis=0)
        if targets is None:
            return (*first, gates)
        return F.stack(*first, axis=0), gates


def _log_pdf(gate_logits):
    """gate logits (T, ...) -> log p (T, ...) float32: log p_t =
    log lambda_t + sum_{j<t} log(1 - lambda_j), the last exit the
    remainder, sum_{j<T} log(1 - lambda_j)."""
    g = jnp.asarray(gate_logits, jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)   # log S_t
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], 0)
    return jnp.concatenate(
        [jax.nn.log_sigmoid(g[:-1]) + before[:-1], before[-1:]], 0)


def exit_pdf(gate_logits):
    """gate logits (T, B, S) -> the exit distribution (B, S, T) float32:
    p_t = lambda_t S_{t-1} for t < T, p_T = S_{T-1}, with lambda =
    sigmoid(gate logit) and S_t = prod_{j<=t} (1 - lambda_j); it sums to
    one over t."""
    return jnp.moveaxis(jnp.exp(_log_pdf(gate_logits)), 0, -1)


def exit_loss(nll, gate_logits, beta):
    """The expected loss over the exits less an entropy bonus, a token,
    mean over the B x (S - 1) predicted positions, float32:

        sum_t p_t nll_t - beta H(p),    H(p) = -sum_t p_t log p_t

    `nll` (T, B, S - 1): exit t's cross-entropy of positions 0 .. S - 2;
    `gate_logits` (T, B, S): the last position predicts nothing and its
    gate is left out."""
    logp = _log_pdf(gate_logits[:, :, :nll.shape[2]])
    p = jnp.exp(logp)
    expected = jnp.sum(p * jnp.asarray(nll, jnp.float32), axis=0)
    entropy = -jnp.sum(p * logp, axis=0)
    return jnp.mean(expected - beta * entropy)
