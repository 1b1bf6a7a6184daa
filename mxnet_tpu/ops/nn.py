"""Neural-net ops: FC, Conv, BatchNorm, Pooling, LayerNorm, Dropout, …

TPU-native counterpart of the reference's src/operator/nn/** (CUDA/cuDNN
kernels: fully_connected, convolution + cudnn_convolution, batch_norm,
pooling, activation, dropout, softmax, layer_norm, embedding in
indexing_op).  Everything lowers to XLA HLO via lax — convolutions map
straight onto the MXU via lax.conv_general_dilated; normalisations are
fused elementwise chains XLA folds into neighbouring ops; there is no
hand-written kernel or autotune cache (XLA owns scheduling).

Stateful training-mode ops follow a functional contract:
  * Dropout takes an explicit PRNG key input (threaded by the frontend
    from mxnet_tpu.random's provider) and a static `train` attr; its mask
    is an integer hash of (the key's words, the element's index): the
    repo's one dropout generator, ops/dropout_mask.py, which attention's
    dropout shares.
  * BatchNorm in train mode returns (out, new_running_mean, new_running_var);
    the Gluon layer rebinds its running-stat buffers — the TPU-safe way to
    express the reference's in-place aux-state update.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..base import MXNetError
from .dropout_mask import inverted_dropout
from .registry import register_op


# ---------------------------------------------------------------------------
# FullyConnected (ref: src/operator/nn/fully_connected-inl.h)
# ---------------------------------------------------------------------------

@register_op("FullyConnected", aliases=("fully_connected",))
def _fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                     flatten=True):
    """Dense layer: data @ weight.T + bias, flattening trailing dims
    first when ``flatten`` (ref: fully_connected-inl.h)."""
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = jnp.matmul(data, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution (ref: src/operator/nn/convolution-inl.h, cudnn_convolution)
# ---------------------------------------------------------------------------

def _conv_dims(kernel):
    return len(kernel)


@register_op("Convolution", aliases=("convolution",))
def _convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                 pad=(), num_filter=0, num_group=1, no_bias=False,
                 layout=None, cudnn_tune=None, cudnn_off=False, workspace=1024):
    """N-D grouped convolution, NCHW-family layouts, with optional bias
    (ref: convolution-inl.h)."""
    nd = len(kernel) if kernel else data.ndim - 2
    stride = tuple(stride) if stride else (1,) * nd
    dilate = tuple(dilate) if dilate else (1,) * nd
    pad = tuple(pad) if pad else (0,) * nd
    # weight stays (O, I/g, *k) for EVERY layout (param shapes / checkpoints
    # are layout-independent); XLA's layout assignment folds the logical
    # permutation into the conv, so NHWC costs nothing extra on TPU.
    default = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[nd]
    lay = layout or default
    dn_in = dn_out = lay
    dn_k = "OI" + default[2:]
    # NB: no preferred_element_type here — the MXU accumulates bf16 convs in
    # fp32 internally, and an fp32 primal output would make the weight-grad
    # transpose conv see mixed (bf16, fp32) operands, which lax rejects.
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=(dn_in, dn_k, dn_out),
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        if dn_out[-1] == "C":
            out = out + bias
        else:
            out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@register_op("Deconvolution", aliases=("deconvolution",))
def _deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                   pad=(), adj=(), num_filter=0, num_group=1, no_bias=False,
                   target_shape=None, layout=None, workspace=1024,
                   cudnn_tune=None, cudnn_off=False):
    """Transposed conv as lhs-dilated direct conv (full dilate/adj/groups/
    target_shape support).  out = (in-1)*s - 2p + (k-1)*d + 1 + adj."""
    nd = len(kernel)
    stride = tuple(stride) if stride else (1,) * nd
    pad = tuple(pad) if pad else (0,) * nd
    dilate = tuple(dilate) if dilate else (1,) * nd
    k_eff = tuple((k - 1) * d + 1 for k, d in zip(kernel, dilate))
    sp0 = 1 if (layout and layout[-1] == "C") else 2   # first spatial axis
    if target_shape:
        adj = tuple(
            t - ((data.shape[sp0 + i] - 1) * stride[i] - 2 * pad[i] + k_eff[i])
            for i, t in enumerate(target_shape))
    else:
        adj = tuple(adj) if adj else (0,) * nd
    # weight (in, out/g, *k) -> flipped, regrouped to (out, in/g, *k)
    in_c = weight.shape[0]
    out_g = weight.shape[1]
    spatial = tuple(range(2, 2 + nd))
    w = jnp.flip(weight, axis=spatial)
    w = w.reshape((num_group, in_c // num_group, out_g) + tuple(kernel))
    w = jnp.swapaxes(w, 1, 2)
    w = w.reshape((num_group * out_g, in_c // num_group) + tuple(kernel))
    default = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[nd]
    lay = layout or default
    dn = (lay, "OI" + default[2:], lay)
    pads = [(k_eff[i] - 1 - pad[i], k_eff[i] - 1 - pad[i] + adj[i])
            for i in range(nd)]
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group)
    if bias is not None and not no_bias:
        if lay[-1] == "C":
            out = out + bias
        else:
            out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling (ref: src/operator/nn/pooling-inl.h)
# ---------------------------------------------------------------------------

def pool_window(data_shape, kernel, stride, pad, pooling_convention,
                channels_last):
    """Shared pooling geometry: (window, strides, padding) over the FULL
    rank, honoring the valid/full (ceil-mode) convention.  Single source
    of truth for fp32 Pooling AND quantized_pooling — their shapes must
    agree exactly."""
    nd = len(data_shape) - 2
    kernel = tuple(kernel)
    if len(kernel) != nd:
        raise MXNetError(
            f"pooling: kernel must have {nd} dims for "
            f"{len(data_shape)}-d input (got {kernel!r})")
    stride = tuple(stride) if stride else (1,) * nd
    pad = tuple(pad) if pad else (0,) * nd
    sp0 = 1 if channels_last else 2   # first spatial axis

    sp_pad = tuple((p, p) for p in pad)
    if pooling_convention == "full":
        # ceil-mode: extend padding on the right so ceil division is covered
        extra = []
        for i in range(nd):
            in_sz = data_shape[sp0 + i] + 2 * pad[i]
            rem = (in_sz - kernel[i]) % stride[i]
            extra.append(0 if rem == 0 else stride[i] - rem)
        sp_pad = tuple((p, p + e) for p, e in zip(pad, extra))
    elif pooling_convention != "valid":
        raise MXNetError("pooling_convention must be valid/full "
                         f"(got {pooling_convention!r})")
    if channels_last:
        return ((1,) + kernel + (1,), (1,) + stride + (1,),
                ((0, 0),) + sp_pad + ((0, 0),))
    return ((1, 1) + kernel, (1, 1) + stride,
            ((0, 0), (0, 0)) + sp_pad)


@register_op("Pooling", aliases=("pooling",))
def _pooling(data, kernel=(), pool_type="max", stride=(), pad=(),
             global_pool=False, pooling_convention="valid", count_include_pad=True,
             cudnn_off=False, layout=None):
    """max/avg/sum/lp pooling with valid/full conventions and global
    mode (ref: pooling-inl.h)."""
    channels_last = bool(layout) and layout[-1] == "C"
    if global_pool:
        axes = (tuple(range(1, data.ndim - 1)) if channels_last
                else tuple(range(2, data.ndim)))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = tuple(kernel)
    window, strides, padding = pool_window(
        data.shape, kernel, stride, pad, pooling_convention, channels_last)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            return s / float(np.prod(kernel))
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return s / cnt
    if pool_type == "lp":
        s = lax.reduce_window(jnp.abs(data) ** 2, 0.0, lax.add, window, strides, padding)
        return jnp.sqrt(s)
    raise ValueError(f"unknown pool_type {pool_type}")


# ---------------------------------------------------------------------------
# Normalisation (ref: batch_norm.cc/.cu, layer_norm.cc, instance/group norm)
# ---------------------------------------------------------------------------

def _bn_nout(attrs):
    return 3 if attrs.get("_train", False) else 1


def _bn_exact_var_default() -> bool:
    # read once per process: the compiled-op cache is keyed on attrs, so a
    # mid-process env flip could not take effect anyway.  Per-call control
    # is the explicit `exact_var` attr.
    from ..util import env

    return env.get_bool("MXNET_BN_EXACT_VAR")


_BN_EXACT_VAR = None  # resolved lazily so base import order doesn't matter


@register_op("BatchNorm", aliases=("batch_norm",), num_outputs=_bn_nout)
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
                momentum=0.9, fix_gamma=False, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False, _train=False,
                exact_var=None):
    """Batch normalization over ``axis`` using batch stats in training
    and moving stats in inference (ref: batch_norm-inl.h)."""
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    # mixed-precision HBM discipline: the big tensor is touched ONLY in its
    # own (bf16) dtype — stats accumulate in the fp32 stat dtype inside the
    # reduction (convert fused into the reduce, nothing materialized), and
    # the normalize is a C-sized fp32 scale/bias precomputed once then
    # applied as one bf16 fused multiply-add.  An fp32 activation copy
    # would double the dominant HBM traffic of conv nets.
    odtype = data.dtype
    sdt = moving_mean.dtype

    def apply_affine(mean, var):
        # C-sized fp32 coefficients; the per-element convert→fma→convert
        # happens in-register inside one fusion (bf16 in, bf16 out)
        scale = g.astype(sdt) * lax.rsqrt(var + eps)
        bias = beta.astype(sdt) - mean * scale
        return (data.astype(sdt) * scale.reshape(shape)
                + bias.reshape(shape)).astype(odtype)

    if _train and not use_global_stats:
        red = tuple(i for i in range(data.ndim) if i != axis)
        n = np.prod([data.shape[i] for i in red])
        # two reduction passes, both reading x ONLY in bf16 with the
        # convert/center/square fused into the reduce input: mean first,
        # then centered variance.  E[x²]−mean² would save nothing (XLA
        # runs the two reduces as separate passes either way — measured)
        # and catastrophically cancels for large-mean channels; a
        # variadic lax.reduce computing both in one op measured 6x
        # slower (only monoid reduces hit XLA's fast tiled emitter).
        global _BN_EXACT_VAR
        if _BN_EXACT_VAR is None:
            _BN_EXACT_VAR = _bn_exact_var_default()
        exact = _BN_EXACT_VAR if exact_var is None else bool(exact_var)
        s1 = jnp.sum(data, axis=red, dtype=sdt)
        mean = s1 / n
        if exact:
            # exact two-pass centering: the second reduce depends on the
            # first, so XLA cannot sibling-fuse them into one HBM read —
            # one extra pass over x (~9% on the ResNet-50 bench)
            xc = data.astype(sdt) - mean.reshape(shape)
            var = jnp.sum(xc * xc, axis=red) / n
        else:
            # SINGLE-pass stats (default): var = E[(x−c)²] − (mean−c)²
            # shifted by the running mean.  Both reduces are independent
            # reads of x, so XLA sibling-fuses them into ONE pass.  The
            # shift cancellation is negligible whenever stats are warm or
            # activations are roughly centered (any realistic training);
            # the relative floor bounds the one cold pathological case
            # (fresh zero stats + |mean| >> std) instead of letting
            # rsqrt blow up.  MXNET_BN_EXACT_VAR=1 selects the exact
            # path.  Other one-pass routes measured on-chip and rejected:
            # variadic lax.reduce (6× slower, off the fast reduce path),
            # subsample-estimated shift (10× — broke reduce fusion).
            c = lax.stop_gradient(moving_mean.astype(sdt))
            d = data.astype(sdt) - c.reshape(shape)
            s2 = jnp.sum(d * d, axis=red)
            dm = mean - c
            raw = s2 / n
            var = jnp.maximum(raw - dm * dm, 1e-6 * raw)
        out = apply_affine(mean, var)
        unbiased = var * (n / max(n - 1, 1))
        new_mean = momentum * moving_mean + (1 - momentum) * mean
        new_var = momentum * moving_var + (1 - momentum) * unbiased
        return out, new_mean, new_var
    return apply_affine(moving_mean, moving_var)


@register_op("LayerNorm", aliases=("layer_norm",))
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """Layer normalization over ``axis`` with learned scale and shift."""
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register_op("InstanceNorm", aliases=("instance_norm",))
def _instance_norm(data, gamma, beta, eps=1e-3):
    """Instance normalization: normalize each (sample, channel) over its
    spatial dims."""
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register_op("GroupNorm", aliases=("group_norm",))
def _group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    """Group normalization: normalize over channel groups + spatial dims
    (batch-size independent)."""
    b, c = data.shape[:2]
    rest = data.shape[2:]
    x = data.reshape((b, num_groups, c // num_groups) + rest)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    out = ((x - mean) * lax.rsqrt(var + eps)).reshape(data.shape)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register_op("RMSNorm", aliases=("rms_norm",))
def _rms_norm(data, gamma, axis=-1, eps=1e-6, offset=0.0):
    """RMS normalization over ``axis``: scale by 1/RMS and gamma +
    ``offset``, no mean subtraction.  Computed in float32 whatever the
    input's dtype (a bfloat16 mean of squares over thousands of features
    is not a mean), returned in the input's.  ``offset`` 1 is the unit
    offset: the stored gain starts at zero, and in bfloat16 keeps the
    resolution near zero that a gain near one has lost."""
    x = data.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=axis, keepdims=True)
    gain = gamma.astype(jnp.float32)
    if offset:
        gain = gain + offset
    return (x * lax.rsqrt(ms + eps) * gain).astype(data.dtype)


# ---------------------------------------------------------------------------
# Activations (ref: activation-inl.h, leaky_relu-inl.h)
# ---------------------------------------------------------------------------

@register_op("Activation", aliases=("activation",))
def _activation(data, act_type="relu"):
    """Elementwise activation selected by ``act_type`` (relu, sigmoid,
    tanh, softrelu, gelu, silu, ...)."""
    return {
        "relu": lambda x: jnp.maximum(x, 0),
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": lambda x: x / (1 + jnp.abs(x)),
        "gelu": partial(jax.nn.gelu, approximate=False),
        "gelu_tanh": partial(jax.nn.gelu, approximate=True),
        "silu": jax.nn.silu,
    }[act_type](data)


@register_op("LeakyReLU", aliases=("leaky_relu",))
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334):
    """Leaky-ReLU family: leaky/prelu/elu/selu/gelu/rrelu (rrelu uses
    the deterministic midpoint slope, the reference's inference path)."""
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        shape = (1, -1) + (1,) * (data.ndim - 2) if data.ndim > 1 else (-1,)
        g = gamma.reshape(shape) if gamma.size > 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2
        return jnp.where(data >= 0, data, mid * data)
    raise ValueError(f"unknown act_type {act_type}")


# ---------------------------------------------------------------------------
# Softmax family (ref: softmax-inl.h, softmax_output-inl.h)
# ---------------------------------------------------------------------------

@register_op("softmax")
def _softmax(data, axis=-1, temperature=None, length=None):
    """Softmax over ``axis`` with optional temperature and per-row valid
    ``length`` masking."""
    x = data / temperature if temperature else data
    if length is not None:
        pos = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = -1
        mask = pos.reshape(shape) < length.reshape((-1,) + (1,) * (x.ndim - 1))
        x = jnp.where(mask, x, -jnp.inf)
    return jax.nn.softmax(x, axis=axis)


@register_op("log_softmax")
def _log_softmax(data, axis=-1, temperature=None):
    """Numerically-stable log(softmax) over ``axis`` with optional
    temperature."""
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register_op("softmin")
def _softmin(data, axis=-1):
    """Softmax of the negated input (small values get large weights)."""
    return jax.nn.softmax(-data, axis=axis)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _softmax_output_core(data, label, grad_scale, ignore_label, use_ignore,
                         normalization):
    return jax.nn.softmax(data, axis=-1)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        normalization):
    out = jax.nn.softmax(data, axis=-1)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, ignore_label, use_ignore, normalization,
                        res, g):
    out, label = res
    onehot = jax.nn.one_hot(label.astype(jnp.int32), out.shape[-1],
                            dtype=out.dtype)
    # reference semantics (softmax_output-inl.h): backward ignores the
    # upstream grad and emits (softmax - one_hot) * grad_scale, normalized
    # per the `normalization` attr ('null' | 'batch' | 'valid')
    grad = out - onehot
    valid = None
    if use_ignore:
        keep = (label.astype(jnp.int32) != int(ignore_label))
        grad = grad * keep[..., None].astype(grad.dtype)
        valid = jnp.maximum(jnp.sum(keep), 1)
    if normalization == "batch":
        grad = grad / out.shape[0]
    elif normalization == "valid":
        denom = valid if valid is not None else out.shape[0]
        grad = grad / denom
    return (grad * grad_scale, jnp.zeros_like(label))


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register_op("SoftmaxOutput", aliases=("softmax_output",))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1,
                    use_ignore=False, multi_output=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    """Legacy symbolic loss head (ref: softmax_output-inl.h): forward =
    softmax, backward = (softmax - one_hot(label)) * grad_scale with the
    requested normalization, via custom_vjp."""
    return _softmax_output_core(data, label, grad_scale, ignore_label,
                                use_ignore, normalization)


# ---------------------------------------------------------------------------
# Dropout (ref: dropout-inl.h) — explicit key input, static train attr
# ---------------------------------------------------------------------------

@register_op("Dropout", aliases=("dropout",))
def _dropout(data, key, p=0.5, mode="training", axes=(), _train=False):
    """Inverted dropout: zero with probability p and rescale by 1/(1-p)
    in training (``axes`` broadcast one shared mask); identity in
    inference unless mode='always'."""
    apply_it = (mode == "always") or _train
    if not apply_it or p == 0.0:
        return data
    return inverted_dropout(data, key, p, axes)


# ---------------------------------------------------------------------------
# Embedding (ref: indexing_op.h Embedding)
# ---------------------------------------------------------------------------

@register_op("Embedding", aliases=("embedding",))
def _embedding(data, weight, input_dim=0, output_dim=0, dtype="float32",
               sparse_grad=False):
    """Integer-index row lookup into the (input_dim, output_dim) weight
    table, out-of-range indices clipped."""
    idx = data.astype(jnp.int32)
    return jnp.take(weight, idx, axis=0, mode="clip")


# ---------------------------------------------------------------------------
# Losses as ops (ref: ctc_loss, MakeLoss)
# ---------------------------------------------------------------------------

@register_op("MakeLoss", aliases=("make_loss",))
def _make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    """Mark a symbol as a loss head: identity forward, gradient of 1
    flows back (ref: make_loss.cc)."""
    return data


@register_op("stop_gradient", aliases=("BlockGrad", "block_grad"))
def _stop_gradient(data):
    """Identity forward, zero gradient back (ref: BlockGrad)."""
    return lax.stop_gradient(data)


@register_op("CTCLoss", aliases=("ctc_loss",))
def _ctc_loss(data, label, data_lengths=None, label_lengths=None,
              use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    """CTC via dynamic-programming in log space (lax.scan over time).

    data: (seq, batch, alphabet) activations (pre-softmax).
    label: (batch, label_seq) padded with -1 (or 0s when blank_label='last').
    """
    seq_len, batch, alphabet = data.shape
    logp = jax.nn.log_softmax(data, axis=-1)
    blank = 0 if blank_label == "first" else alphabet - 1
    lab = label.astype(jnp.int32)
    L = lab.shape[1]
    # 'first': blank=0, real labels live in [1, alphabet); 0/-1 pad.
    # 'last': blank=alphabet-1, real labels in [0, alphabet-1); -1 pads.
    lab_valid = lab > 0 if blank_label == "first" else lab >= 0
    lab_len = (jnp.sum(lab_valid, axis=1) if not use_label_lengths
               else label_lengths.astype(jnp.int32))
    # extended label sequence with blanks: length 2L+1
    ext = jnp.full((batch, 2 * L + 1), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(jnp.where(lab_valid, lab, blank))
    S = 2 * L + 1
    neg_inf = -1e30
    alpha0 = jnp.full((batch, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    first_lab = ext[:, 1]
    alpha0 = alpha0.at[:, 1].set(
        jnp.take_along_axis(logp[0], first_lab[:, None], axis=1)[:, 0])

    def step(alpha, logp_t):
        prev1 = jnp.concatenate([jnp.full((batch, 1), neg_inf), alpha[:, :-1]], axis=1)
        prev2 = jnp.concatenate([jnp.full((batch, 2), neg_inf), alpha[:, :-2]], axis=1)
        ext_shift = jnp.concatenate([jnp.full((batch, 2), -2, jnp.int32), ext[:, :-2]], axis=1)
        allow_skip = (ext != blank) & (ext != ext_shift)
        merged = jnp.logaddexp(alpha, prev1)
        merged = jnp.where(allow_skip, jnp.logaddexp(merged, prev2), merged)
        emit = jnp.take_along_axis(logp_t, ext, axis=1)
        new_alpha = merged + emit
        return new_alpha, new_alpha

    _, alpha_hist = lax.scan(step, alpha0, logp[1:])
    alphas = jnp.concatenate([alpha0[None], alpha_hist], axis=0)  # (T, B, S)
    if use_data_lengths and data_lengths is not None:
        dl = jnp.clip(data_lengths.astype(jnp.int32), 1, seq_len)
    else:
        dl = jnp.full((batch,), seq_len, jnp.int32)
    # per-sequence final alpha: alpha at t = len-1 (padding frames excluded)
    alpha_T = jnp.take_along_axis(
        alphas, (dl - 1).reshape(1, batch, 1), axis=0)[0]
    end1 = 2 * lab_len
    end2 = 2 * lab_len - 1
    a1 = jnp.take_along_axis(alpha_T, end1[:, None], axis=1)[:, 0]
    a2 = jnp.take_along_axis(alpha_T, jnp.maximum(end2, 0)[:, None], axis=1)[:, 0]
    return -jnp.logaddexp(a1, a2)


# ---------------------------------------------------------------------------
# UpSampling + spatial transformer family
# (ref: src/operator/nn/upsampling-inl.h, spatial_transformer-inl.h,
#  bilinear_sampler-inl.h, grid_generator-inl.h)
# ---------------------------------------------------------------------------

@register_op("UpSampling", aliases=("upsampling",))
def _upsampling(*datas, scale=1, sample_type="nearest", num_args=1,
                num_filter=0, multi_input_mode="concat", workspace=512):
    """Spatial upsampling, NCHW.  'nearest' repeats pixels; 'bilinear'
    resizes with align-corners-false bilinear interpolation (played here
    by jax.image.resize instead of the reference's fixed deconv
    kernel).  Multiple inputs are each upsampled to the first input's
    scaled size, then concatenated on channels (reference semantics)."""
    import jax as _jax

    scale = int(scale)
    outs = []
    n, _, h0, w0 = datas[0].shape
    th, tw = h0 * scale, w0 * scale
    for d in datas:
        if sample_type == "nearest":
            s = th // d.shape[2]
            up = jnp.repeat(jnp.repeat(d, s, axis=2), tw // d.shape[3],
                            axis=3)
        elif sample_type == "bilinear":
            up = _jax.image.resize(
                d, d.shape[:2] + (th, tw), method="bilinear")
        else:
            raise MXNetError(f"UpSampling: unknown sample_type "
                             f"{sample_type!r}")
        outs.append(up)
    if len(outs) == 1:
        return outs[0]
    if multi_input_mode == "sum":
        out = outs[0]
        for o in outs[1:]:
            out = out + o
        return out
    return jnp.concatenate(outs, axis=1)


def _grid_sample_bilinear(data, grid):
    """Sample NCHW `data` at normalized grid coords (N, 2, Ho, Wo) in
    [-1, 1] (x, y order), zero padding outside — the BilinearSampler
    contract (ref: bilinear_sampler-inl.h)."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1.0) * (w - 1) / 2.0   # (N, Ho, Wo)
    gy = (grid[:, 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def tap(yi, xi):
        inb = ((yi >= 0) & (yi <= h - 1) & (xi >= 0) & (xi <= w - 1))
        yc = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        xc = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        # gather per batch: (N, C, Ho, Wo)
        v = jax.vmap(lambda img, ys, xs: img[:, ys, xs])(data, yc, xc)
        return v * inb[:, None].astype(data.dtype)

    v00 = tap(y0, x0)
    v01 = tap(y0, x0 + 1)
    v10 = tap(y0 + 1, x0)
    v11 = tap(y0 + 1, x0 + 1)
    wx = wx[:, None].astype(data.dtype)
    wy = wy[:, None].astype(data.dtype)
    return ((1 - wy) * ((1 - wx) * v00 + wx * v01)
            + wy * ((1 - wx) * v10 + wx * v11))


@register_op("BilinearSampler", aliases=("bilinear_sampler",))
def _bilinear_sampler(data, grid, cudnn_off=False):
    """Sample NCHW data at normalized grid coords ([-1, 1]) with
    bilinear interpolation, zero padding outside (ref: STN sampler)."""
    return _grid_sample_bilinear(data, grid)


@register_op("GridGenerator", aliases=("grid_generator",))
def _grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    """Build a sampling grid: 'affine' from (N, 6) theta over
    target_shape, 'warp' from (N, 2, H, W) pixel offsets
    (ref: grid_generator-inl.h)."""
    if transform_type == "affine":
        th, tw = int(target_shape[0]), int(target_shape[1])
        if th <= 0 or tw <= 0:
            raise MXNetError("GridGenerator(affine) needs target_shape")
        theta = data.reshape((-1, 2, 3)).astype(jnp.float32)
        ys = jnp.linspace(-1.0, 1.0, th)
        xs = jnp.linspace(-1.0, 1.0, tw)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        base = jnp.stack([gx.ravel(), gy.ravel(),
                          jnp.ones(th * tw)], axis=0)  # (3, HW)
        out = theta @ base                              # (N, 2, HW)
        return out.reshape((-1, 2, th, tw))
    if transform_type == "warp":
        n, _, h, w = data.shape
        gy, gx = jnp.meshgrid(jnp.arange(h), jnp.arange(w), indexing="ij")
        fx = (gx[None] + data[:, 0]) * 2.0 / max(w - 1, 1) - 1.0
        fy = (gy[None] + data[:, 1]) * 2.0 / max(h - 1, 1) - 1.0
        return jnp.stack([fx, fy], axis=1)
    raise MXNetError(f"GridGenerator: unknown transform_type "
                     f"{transform_type!r}")


@register_op("SpatialTransformer", aliases=("spatial_transformer",))
def _spatial_transformer(data, loc, target_shape=(0, 0),
                         transform_type="affine",
                         sampler_type="bilinear", cudnn_off=False):
    """Affine spatial transformer network layer = GridGenerator +
    BilinearSampler (ref: spatial_transformer-inl.h)."""
    if transform_type != "affine" or sampler_type != "bilinear":
        raise MXNetError("SpatialTransformer supports affine+bilinear")
    grid = _grid_generator(loc, transform_type="affine",
                           target_shape=target_shape)
    return _grid_sample_bilinear(data, grid)


# ---------------------------------------------------------------------------
# activation parity batch + legacy regression loss heads
# (ref: elemwise_unary_op, softmax_activation-inl.h, regression_output-inl.h)
# ---------------------------------------------------------------------------

@register_op("hard_sigmoid")
def _hard_sigmoid(data, alpha=0.2, beta=0.5):
    """Piecewise-linear sigmoid: clip(alpha * x + beta, 0, 1)."""
    return jnp.clip(alpha * data + beta, 0.0, 1.0)


@register_op("hard_swish")
def _hard_swish(data):
    """x * hard_sigmoid(x) with the MobileNetV3 constants (x * clip(
    x/6 + 0.5, 0, 1))."""
    return data * jnp.clip(data / 6.0 + 0.5, 0.0, 1.0)


@register_op("mish")
def _mish(data):
    """Mish activation: x * tanh(softplus(x))."""
    return data * jnp.tanh(jax.nn.softplus(data))


@register_op("SoftmaxActivation", aliases=("softmax_activation",))
def _softmax_activation(data, mode="instance"):
    """Deprecated standalone softmax (ref: softmax_activation-inl.h):
    'instance' over the flattened trailing dims, 'channel' over dim 1."""
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    flat = data.reshape((data.shape[0], -1))
    return jax.nn.softmax(flat, axis=-1).reshape(data.shape)


def _regression_head(name, fwd, bwd_grad):
    """Loss-head ops: forward is a transform of the scores; backward
    IGNORES the upstream cotangent and emits grad_scale * residual —
    the reference regression_output-inl.h contract."""

    @partial(jax.custom_vjp, nondiff_argnums=(2,))
    def core(data, label, grad_scale):
        return fwd(data)

    def core_fwd(data, label, grad_scale):
        out = fwd(data)
        return out, (out, data, label)

    def core_bwd(grad_scale, res, g):
        out, data, label = res
        lab = label.reshape(out.shape).astype(out.dtype)
        # reference scaling: grad_scale / num_output where num_output =
        # label.Size()/batch (per-sample output count, NOT batch size)
        num_output = 1
        for s in out.shape[1:]:
            num_output *= s
        grad = bwd_grad(out, lab) * (grad_scale / num_output)
        return grad, jnp.zeros_like(label)

    core.defvjp(core_fwd, core_bwd)

    import re

    snake = re.sub(r"(?<=[a-z0-9])(?=[A-Z])|(?<=[A-Z])(?=[A-Z][a-z])",
                   "_", name).lower()

    @register_op(name, aliases=(snake,))
    def head(data, label, grad_scale=1.0):
        """Regression output head: forward transform of data, backward
        (out - label) * grad_scale / batch (ref: regression_output-inl.h)."""
        return core(data, label, float(grad_scale))

    return head


_regression_head("LinearRegressionOutput", lambda d: d,
                 lambda out, lab: out - lab)
_regression_head("MAERegressionOutput", lambda d: d,
                 lambda out, lab: jnp.sign(out - lab))
_regression_head("LogisticRegressionOutput", jax.nn.sigmoid,
                 lambda out, lab: out - lab)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _svm_core(data, label, margin, reg_coef, use_linear):
    return data


def _svm_fwd(data, label, margin, reg_coef, use_linear):
    return data, (data, label)


def _svm_bwd(margin, reg_coef, use_linear, res, g):
    scores, label = res
    k = scores.shape[-1]
    y = jax.nn.one_hot(label.astype(jnp.int32), k, dtype=scores.dtype)
    s_y = (scores * y).sum(axis=-1, keepdims=True)
    viol = jnp.maximum(0.0, margin - (s_y - scores)) * (1.0 - y)
    if use_linear:  # L1-SVM hinge
        gj = (viol > 0).astype(scores.dtype)
    else:           # L2-SVM squared hinge (reference default)
        gj = 2.0 * viol
    grad = gj - y * gj.sum(axis=-1, keepdims=True)
    return (reg_coef * grad / scores.shape[0],
            jnp.zeros_like(label))


_svm_core.defvjp(_svm_fwd, _svm_bwd)


@register_op("SVMOutput", aliases=("svm_output",))
def _svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
                use_linear=False):
    """Multiclass SVM loss head (ref: svm_output-inl.h): forward =
    identity, backward = hinge (L2 by default) gradient."""
    return _svm_core(data, label, float(margin),
                     float(regularization_coefficient), bool(use_linear))


# ---------------------------------------------------------------------------
# im2col / col2im (ref: src/operator/nn/im2col.h) — patch extraction via
# XLA's native conv_general_dilated_patches; col2im is its exact adjoint
# (jax.vjp), which is also how the reference implements it (col2im is
# im2col's backward).
# ---------------------------------------------------------------------------

def _im2col_impl(data, kernel, stride, dilate, pad):
    nd_ = len(kernel)
    patches = lax.conv_general_dilated_patches(
        data, filter_shape=tuple(kernel),
        window_strides=tuple(stride) if stride else (1,) * nd_,
        padding=[(p, p) for p in (tuple(pad) if pad else (0,) * nd_)],
        rhs_dilation=tuple(dilate) if dilate else (1,) * nd_)
    # (N, C*prod(k), *out_spatial) -> (N, C*prod(k), prod(out_spatial))
    return patches.reshape(patches.shape[0], patches.shape[1], -1)


@register_op("im2col")
def _im2col(data, kernel=(), stride=(), dilate=(), pad=()):
    """Unfold sliding kernel patches of NCHW data into columns
    (N, C*prod(kernel), L) (ref: im2col.h)."""
    return _im2col_impl(data, kernel, stride, dilate, pad)


@register_op("col2im")
def _col2im(data, output_size=(), kernel=(), stride=(), dilate=(),
            pad=()):
    """Scatter columns back to an image: the adjoint of im2col
    (overlapping patches SUM — ref: col2im in im2col.h)."""
    n, ck, _ = data.shape
    prod_k = 1
    for k in kernel:
        prod_k *= k
    c = ck // prod_k
    img_shape = (n, c) + tuple(output_size)
    zero = jnp.zeros(img_shape, data.dtype)
    _, vjp = jax.vjp(
        lambda img: _im2col_impl(img, kernel, stride, dilate, pad), zero)
    return vjp(data)[0]


# ---------------------------------------------------------------------------
# Correlation (ref: src/operator/correlation.cc — FlowNet cost volume):
# for each displacement within max_displacement, the channel-mean dot
# product of f1 and shifted f2.  The displacement set is static, so the
# loop unrolls into a fused stack of elementwise multiplies + reductions.
# ---------------------------------------------------------------------------

@register_op("Correlation", aliases=("correlation",))
def _correlation(data1, data2, kernel_size=1, max_displacement=1,
                 stride1=1, stride2=1, pad_size=0, is_multiply=True):
    """FlowNet correlation layer: per-displacement patch similarity of
    two NCHW feature maps over a (2d+1)^2 window."""
    if kernel_size != 1 or stride1 != 1 or stride2 != 1:
        raise MXNetError("Correlation: this build supports "
                         "kernel_size=1, stride1=1, stride2=1")
    n, c, h, w = data1.shape
    d = int(max_displacement)
    p = int(pad_size)
    # reference output geometry (correlation-inl.h, stride1=1):
    # out_spatial = in + 2*pad - 2*max_displacement
    ho = h + 2 * p - 2 * d
    wo = w + 2 * p - 2 * d
    if ho <= 0 or wo <= 0:
        raise MXNetError(
            f"Correlation: non-positive output size {(ho, wo)}; "
            f"pad_size must satisfy in + 2*pad > 2*max_displacement")
    f1 = jnp.pad(data1, ((0, 0), (0, 0), (p, p), (p, p)))
    f2 = jnp.pad(data2, ((0, 0), (0, 0), (p, p), (p, p)))
    base = lax.dynamic_slice(f1, (0, 0, d, d), (n, c, ho, wo))
    outs = []
    for dy in range(-d, d + 1):
        for dx in range(-d, d + 1):
            shifted = lax.dynamic_slice(
                f2, (0, 0, d + dy, d + dx), (n, c, ho, wo))
            if is_multiply:
                outs.append((base * shifted).mean(axis=1))
            else:
                outs.append(jnp.abs(base - shifted).mean(axis=1))
    return jnp.stack(outs, axis=1)  # (N, (2d+1)^2, Ho, Wo)


# ---------------------------------------------------------------------------
# DeformableConvolution (ref: src/operator/contrib/deformable_convolution
# .cc, DCN v1): each kernel tap samples the input at a learned offset via
# bilinear interpolation, then the taps contract against the weight — on
# TPU this is prod(k) grid-samples (reusing the BilinearSampler math)
# feeding one dot_general, all fused by XLA.
# ---------------------------------------------------------------------------

@register_op("_contrib_DeformableConvolution",
             aliases=("DeformableConvolution", "deformable_convolution"))
def _deformable_convolution(data, offset, weight, bias=None, kernel=(),
                            stride=(), dilate=(), pad=(), num_filter=0,
                            num_group=1, num_deformable_group=1,
                            no_bias=False, layout=None, workspace=1024):
    """Deformable convolution v1: bilinear-sample inputs at learned
    per-position offsets, then convolve (ref: deformable_convolution)."""
    if num_group != 1 or num_deformable_group != 1:
        raise MXNetError("DeformableConvolution: this build supports "
                         "num_group=num_deformable_group=1")
    kh, kw = kernel
    sh, sw = stride if stride else (1, 1)
    dh, dw = dilate if dilate else (1, 1)
    ph, pw = pad if pad else (0, 0)
    n, c, h, w = data.shape
    ho = (h + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    wo = (w + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
    if offset.shape != (n, 2 * kh * kw, ho, wo):
        raise MXNetError(
            f"DeformableConvolution: offset must be "
            f"{(n, 2 * kh * kw, ho, wo)} (N, 2*prod(kernel), out_h, "
            f"out_w); got {tuple(offset.shape)}")
    oy, ox = jnp.meshgrid(jnp.arange(ho) * sh - ph,
                          jnp.arange(wo) * sw - pw, indexing="ij")

    def bilinear(img, y, x):  # img (C,H,W); y/x (Ho,Wo) absolute coords
        y0 = jnp.floor(y)
        x0 = jnp.floor(x)
        wy = (y - y0)[None]
        wx = (x - x0)[None]

        def tap(yi, xi):
            inb = ((yi >= 0) & (yi <= h - 1) & (xi >= 0) & (xi <= w - 1))
            yc = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
            xc = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
            return img[:, yc, xc] * inb[None].astype(img.dtype)

        return ((1 - wy) * ((1 - wx) * tap(y0, x0) + wx * tap(y0, x0 + 1))
                + wy * ((1 - wx) * tap(y0 + 1, x0)
                        + wx * tap(y0 + 1, x0 + 1)))

    def one_image(img, off):  # off (2*kh*kw, Ho, Wo)
        cols = []
        for ki in range(kh):
            for kj in range(kw):
                t = ki * kw + kj
                y = oy + ki * dh + off[2 * t]
                x = ox + kj * dw + off[2 * t + 1]
                cols.append(bilinear(img, y, x))   # (C, Ho, Wo)
        return jnp.stack(cols, axis=1)             # (C, K, Ho, Wo)

    cols = jax.vmap(one_image)(data, offset)       # (N, C, K, Ho, Wo)
    wmat = weight.reshape(num_filter, -1)          # (O, C*K)
    out = jnp.einsum("ock,nckhw->nohw",
                     wmat.reshape(num_filter, c, kh * kw), cols)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1, 1, 1))
    return out
