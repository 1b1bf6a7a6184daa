"""One chip's share of microsoft/Phi-4-mini-flash-reasoning (`phi4flash`,
SambaY) as plain float32 jax.numpy, written from the equations of
arXiv:2507.06607 (the decoder-hybrid-decoder), arXiv:2312.00752 (Mamba-1)
and arXiv:2410.05258 (differential attention), under
`default_matmul_precision("highest")`.  The yardstick's own: nothing of
mxnet_tpu is imported, parameters go by name (the zoo's names less the
block's prefix; projection weights are (out, in), the taps (channel,
tap), A_log (channel, state)).

Which layer is what, n = num_hidden_layers (n / 2 even): layer i is a
state-space layer if i % mb_per_layer == 0, else an attention layer.
i < n / 2: Mamba, then attention under the window.  i = n / 2: a Mamba
layer whose scan output y is also the memory m; i = n / 2 + 1: the one
full-attention layer, whose k and v are also kept.  i >= n / 2 + 2: a
Gated Memory Unit reading m where a state-space layer would stand,
cross-attention reading k, v where an attention layer would.

Every layer l:  h <- h + mixer_l(LN(h; norm));  h <- h + MLP(LN(h;
mlp_norm)), LN a LayerNorm (mean subtracted, gain and bias), MLP(u) =
(silu(u G) * (u U)) Dn; then a final LayerNorm and logits = h W_embed^T.
No bias in any projection, no positions.  Written the slow, obvious way:

  mamba      [x ; z] = u W_in; x = silu(conv(x) + bias), the K taps one
             after another (tap K - 1 on the current position); [delta ;
             B ; C] = x W_x; dt = softplus(delta W_dt + dt_bias); A =
             -exp(A_log); the recurrence h_t = exp(dt_t A) * h_{t-1} +
             (dt_t x_t) B_t^T step by step under `lax.scan`; y_t = h_t
             C_t + D x_t; out = (y * silu(z)) W_out;
  gmu        out = (m * silu(u W_1)) W_2;
  attention  heads in pairs (2j, 2j + 1); query pair j reads key/value
             pair j // (query pairs / key pairs).  The FOUR products as
             published: a1 = [softmax(q1 k1^T / sqrt d) v1 ; softmax(q1
             k1^T / sqrt d) v2], a2 the same of (q2, k2); lam = exp(lq1 .
             lk1) - exp(lq2 . lk2) + lambda_init(l); out = (1 -
             lambda_init) RMSNorm(a1 - lam a2; subln) through W_o.
             Blocks of queries, a pair at a time, each block against ALL
             the keys under a dense mask (causal; a window layer also i -
             j < window).

Loss = mean cross-entropy of the logits over the S - 1 next tokens.
`forward` also hands out m.  Departures from the published model are
config.json's `assumed`.  Two keys no configuration file has make a
faulty reference of this one, for
benchmark/tools/phi4_precision_readings.py alone: `_scan_state_dtype`
(the type the scan's state is held in between steps) and `_drop_lambda`
(lam = 0: a2 left out).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 512


def layer_kinds(config):
    n, period = config["num_hidden_layers"], config["mb_per_layer"]
    half = n // 2
    if n % 2 or half % period:
        raise ValueError(f"{n} layers in periods of {period}")
    kinds = []
    for i in range(n):
        ssm = i % period == 0
        if i <= half + 1:
            kinds.append("mamba" if ssm else
                         "full" if i == half + 1 else "window")
        else:
            kinds.append("gmu" if ssm else "cross")
    return kinds


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * weight + bias


def rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(x, taps, bias):
    """x (S, D), taps (D, K): out_t = sum_j taps[:, j] x_{t-(K-1)+j} +
    bias, x zero before position 0."""
    s, k = x.shape[0], taps.shape[1]
    x = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], 0)
    return sum(taps[:, j] * x[j:j + s] for j in range(k)) + bias


def selective_scan(x, dt, a, b, c, d_skip, state_dtype=jnp.float32):
    """x, dt (S, D), a (D, N), b, c (S, N) -> y (S, D): the recurrence
    step by step.  `state_dtype`: the type the state is held in between
    steps (float32; a precision reading rounds it lower)."""
    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs
        h = (jnp.exp(dt_t[:, None] * a) * h.astype(jnp.float32)
             + (dt_t * x_t)[:, None] * b_t[None, :])
        h = h.astype(state_dtype)
        return h, h.astype(jnp.float32) @ c_t

    h0 = jnp.zeros(a.shape, state_dtype)
    return lax.scan(step, h0, (x, dt, b, c))[1] + d_skip * x


def mamba(p, pre, u, cfg):
    """-> (what the mixer adds, the scan's output y)."""
    n = cfg["d_state"]
    rank = p[pre + "dt_proj_weight"].shape[1]
    xz = u @ p[pre + "in_proj_weight"].T
    x, z = jnp.split(xz, 2, axis=-1)
    x = silu(causal_conv(x, p[pre + "conv_weight"], p[pre + "conv_bias"]))
    dbc = x @ p[pre + "x_proj_weight"].T
    delta, b, c = dbc[:, :rank], dbc[:, rank:rank + n], dbc[:, rank + n:]
    dt = jax.nn.softplus(delta @ p[pre + "dt_proj_weight"].T
                         + p[pre + "dt_bias"])
    y = selective_scan(x, dt, -jnp.exp(p[pre + "A_log"]), b, c,
                       p[pre + "D"],
                       jnp.dtype(cfg.get("_scan_state_dtype", "float32")))
    return (y * silu(z)) @ p[pre + "out_proj_weight"].T, y


def gmu(p, pre, u, memory):
    return (memory * silu(u @ p[pre + "gmu_in_proj_weight"].T)) \
        @ p[pre + "gmu_out_proj_weight"].T


def softmax_rows(first, q, k, scale, window):
    """One block of queries (rows first ..) against ALL keys: (block, S)
    probabilities under the causal (and window) mask."""
    block, s = q.shape[0], k.shape[0]
    rows = (first + jnp.arange(block))[:, None]
    cols = jnp.arange(s)[None]
    seen = rows >= cols
    if window:
        seen &= rows - cols < window
    return jax.nn.softmax(jnp.where(seen, q @ k.T * scale, -jnp.inf), -1)


def differential_attention(p, pre, u, k, v, index, cfg, window=0):
    """u (S, hidden) -> what the mixer adds; k, v (S, kv heads, d) this
    layer's own or the full layer's."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    s, eps = u.shape[0], cfg["layer_norm_eps"]
    q = (u @ p[pre + "q_proj_weight"].T).reshape(s, heads, -1)
    d = q.shape[-1]
    per_key = heads // kv_heads             # query pairs a key pair
    init = lambda_init(index)
    if cfg.get("_drop_lambda"):
        lam = 0.0
    else:
        lam = (jnp.exp(jnp.sum(p[pre + "lambda_q1"] * p[pre + "lambda_k1"]))
               - jnp.exp(jnp.sum(p[pre + "lambda_q2"]
                                 * p[pre + "lambda_k2"])) + init)
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of {block}")

    def pair_rows(first, qb, j):
        g = j // per_key
        k1, k2 = k[:, 2 * g], k[:, 2 * g + 1]
        v1, v2 = v[:, 2 * g], v[:, 2 * g + 1]
        p1 = softmax_rows(first, qb[:, 2 * j], k1, d ** -0.5, window)
        p2 = softmax_rows(first, qb[:, 2 * j + 1], k2, d ** -0.5, window)
        a1 = jnp.concatenate([p1 @ v1, p1 @ v2], -1)    # the four products
        a2 = jnp.concatenate([p2 @ v1, p2 @ v2], -1)
        return (1.0 - init) * rms_norm(a1 - lam * a2,
                                       p[pre + "subln_weight"], eps)

    def block_rows(first):
        qb = lax.dynamic_slice_in_dim(q, first, block)
        out = lax.map(lambda j: pair_rows(first, qb, j),
                      jnp.arange(heads // 2))       # (pairs, block, 2 d)
        return out.transpose(1, 0, 2).reshape(block, -1)

    out = lax.map(block_rows, jnp.arange(0, s, block)).reshape(s, -1)
    return out @ p[pre + "o_proj_weight"].T


def keys_values(p, pre, u, cfg):
    s, kv_heads = u.shape[0], cfg["num_key_value_heads"]
    return ((u @ p[pre + "k_proj_weight"].T).reshape(s, kv_heads, -1),
            (u @ p[pre + "v_proj_weight"].T).reshape(s, kv_heads, -1))


def gated_mlp(x, gate, up, down):
    """Weights (out, in)."""
    return (silu(x @ gate.T) * (x @ up.T)) @ down.T


def hidden(p, tokens, config):
    """tokens (S,) -> (the last layer's output after the final norm
    (S, hidden), the memory m (S, expand * hidden))."""
    eps = config["layer_norm_eps"]
    h = p["embed_weight"][tokens]
    half = config["num_hidden_layers"] // 2
    memory = keys = None
    for i, kind in enumerate(layer_kinds(config)):
        pre = f"layer{i}_"
        u = layer_norm(h, p[pre + "norm_weight"], p[pre + "norm_bias"], eps)
        if kind == "mamba":
            mixed, y = mamba(p, pre, u, config)
            if i == half:
                memory = y
        elif kind == "gmu":
            mixed = gmu(p, pre, u, memory)
        elif kind == "cross":
            mixed = differential_attention(p, pre, u, *keys, i, config)
        else:
            own = keys_values(p, pre, u, config)
            if kind == "full":
                keys = own
            mixed = differential_attention(
                p, pre, u, *own, i, config,
                window=config["sliding_window"] if kind == "window" else 0)
        h = h + mixed
        h = h + gated_mlp(
            layer_norm(h, p[pre + "mlp_norm_weight"],
                       p[pre + "mlp_norm_bias"], eps),
            p[pre + "mlp_gate_weight"], p[pre + "mlp_up_weight"],
            p[pre + "mlp_down_weight"])
    return layer_norm(h, p["head_norm_weight"], p["head_norm_bias"],
                      eps), memory


def forward(params, tokens, config):
    """tokens (B, S) int -> (logits (B, S, vocabulary held), memory (B,
    S, expand * hidden)) float32: the head is the embedding's array."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}

    def one(row):
        h, memory = hidden(p, row, config)
        return h @ p["embed_weight"].T, memory

    with jax.default_matmul_precision("highest"):
        return lax.map(one, tokens)


def logits(params, tokens, config):
    return forward(params, tokens, config)[0]


def loss_of(scores, tokens):
    """Mean cross-entropy of scores (B, S, V) over the S - 1 next
    tokens."""
    logp = jax.nn.log_softmax(scores[:, :-1], -1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()


def loss(params, tokens, config):
    return loss_of(logits(params, tokens, config), tokens)
