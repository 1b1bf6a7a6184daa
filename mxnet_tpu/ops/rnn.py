"""Fused multi-layer RNN op (ref: src/operator/rnn.cc + cudnn_rnn-inl.h).

The reference's fused LSTM/GRU kernels exist to make long unrolls cheap on
GPU; the TPU-native equivalent is a single ``lax.scan`` over time per
layer/direction inside one XLA program — the scan body is one fused
matmul+gates kernel on the MXU, and XLA pipelines the whole stack.

Packed parameter layout follows the reference's cudnn convention:
all layer weights first (per layer, per direction: W_i2h then W_h2h,
row-major flattened), then all biases (b_i2h then b_h2h).
Gate order: LSTM [i, f, g, o]; GRU [r, z, n].

Layout: data is TNC (seq, batch, input).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .dropout_mask import inverted_dropout
from .registry import register_op

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def _cell_step(mode):
    if mode == "rnn_relu":
        def step(h_c, pre):
            return (jnp.maximum(pre, 0),), jnp.maximum(pre, 0)
    elif mode == "rnn_tanh":
        def step(h_c, pre):
            return (jnp.tanh(pre),), jnp.tanh(pre)
    elif mode == "lstm":
        def step(h_c, pre):
            h, c = h_c
            i, f, g, o = jnp.split(pre, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            c_new = f * c + i * g
            h_new = o * jnp.tanh(c_new)
            return (h_new, c_new), h_new
    else:
        raise ValueError(mode)
    return step


def _layer_forward(x, w_i2h, w_h2h, b_i2h, b_h2h, h0, c0, mode, reverse):
    """One direction of one layer: scan over time. x: (T, N, I)."""
    if reverse:
        x = jnp.flip(x, axis=0)

    if mode == "gru":
        # hoist the input projection out of the scan: one big MXU matmul.
        # GRU keeps b_h2h separate (applied before the r-gate product).
        xw = jnp.einsum("tni,gi->tng", x, w_i2h) + b_i2h

        def body(carry, xt):
            (h,) = carry
            hw = h @ w_h2h.T + b_h2h
            xr, xz, xn = jnp.split(xt, 3, axis=-1)
            hr, hz, hn = jnp.split(hw, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h_new = (1 - z) * n + z * h
            return (h_new,), h_new

        carry0 = (h0,)
        carry, ys = lax.scan(body, carry0, xw)
        hT = carry[0]
        cT = None
    else:
        xw = jnp.einsum("tni,gi->tng", x, w_i2h) + b_i2h + b_h2h
        step = _cell_step(mode)

        def body(carry, xt):
            pre = xt + carry[0] @ w_h2h.T
            return step(carry, pre)

        carry0 = (h0,) if mode != "lstm" else (h0, c0)
        carry, ys = lax.scan(body, carry0, xw)
        hT = carry[0]
        cT = carry[1] if mode == "lstm" else None
    if reverse:
        ys = jnp.flip(ys, axis=0)
    return ys, hT, cT


def _unpack_params(params, mode, input_size, hidden, num_layers, dirs):
    """Slice the flat cudnn-style parameter vector into per-layer mats."""
    g = _GATES[mode]
    mats = []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else hidden * dirs
        for d in range(dirs):
            wi = params[off:off + g * hidden * in_sz].reshape(g * hidden, in_sz)
            off += g * hidden * in_sz
            wh = params[off:off + g * hidden * hidden].reshape(g * hidden, hidden)
            off += g * hidden * hidden
            mats.append((wi, wh))
    biases = []
    for layer in range(num_layers):
        for d in range(dirs):
            bi = params[off:off + g * hidden]
            off += g * hidden
            bh = params[off:off + g * hidden]
            off += g * hidden
            biases.append((bi, bh))
    return mats, biases


def rnn_param_size(mode, input_size, hidden, num_layers, bidirectional):
    g = _GATES[mode]
    dirs = 2 if bidirectional else 1
    total = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else hidden * dirs
        total += dirs * (g * hidden * in_sz + g * hidden * hidden
                         + 2 * g * hidden)
    return total


def _rnn_nout(attrs):
    mode = attrs.get("mode", "lstm")
    if not attrs.get("state_outputs", True):
        return 1
    return 3 if mode == "lstm" else 2


@register_op("RNN", num_outputs=_rnn_nout)
def _rnn(data, parameters, state=None, state_cell=None, key=None,
         state_size=0, num_layers=1, mode="lstm", bidirectional=False,
         p=0.0, state_outputs=True, projection_size=None,
         lstm_state_clip_min=None, lstm_state_clip_max=None,
         lstm_state_clip_nan=False, use_sequence_length=False, _train=False):
    """data: (T, N, I); state: (L*dirs, N, H); returns out (T, N, H*dirs).
    Omitted state/state_cell default to zeros (the symbolic path's
    begin_state contract — cudnn_rnn-inl.h starts from zeros too)."""
    T, N, I = data.shape
    H = state_size
    dirs = 2 if bidirectional else 1
    if state is None:
        state = jnp.zeros((num_layers * dirs, N, H), data.dtype)
    if state_cell is None and mode == "lstm":
        state_cell = jnp.zeros((num_layers * dirs, N, H), data.dtype)
    mats, biases = _unpack_params(parameters, mode, I, H, num_layers, dirs)
    x = data
    h_outs, c_outs = [], []
    idx = 0
    for layer in range(num_layers):
        ys_dirs = []
        for d in range(dirs):
            wi, wh = mats[idx]
            bi, bh = biases[idx]
            h0 = state[layer * dirs + d]
            c0 = state_cell[layer * dirs + d] if mode == "lstm" else None
            ys, hT, cT = _layer_forward(x, wi, wh, bi, bh, h0, c0, mode,
                                        reverse=(d == 1))
            ys_dirs.append(ys)
            h_outs.append(hT)
            if mode == "lstm":
                c_outs.append(cT)
            idx += 1
        x = jnp.concatenate(ys_dirs, axis=-1) if dirs > 1 else ys_dirs[0]
        if p > 0 and _train and layer < num_layers - 1 and key is not None:
            x = inverted_dropout(x, jax.random.fold_in(key, layer), p)
    if not state_outputs:
        return x
    h_stack = jnp.stack(h_outs)
    if mode == "lstm":
        return x, h_stack, jnp.stack(c_outs)
    return x, h_stack
