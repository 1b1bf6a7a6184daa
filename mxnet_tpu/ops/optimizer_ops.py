"""Optimizer update ops.

TPU-native counterpart of src/operator/optimizer_op.cc (sgd_update,
sgd_mom_update, adam_update, rmsprop_update, ftrl_update, signsgd, nag,
multi-precision variants).  The reference mutates weight/state in place on
the device; here each op is a pure function returning the new weight (and
new state tensors) and the Python Optimizer rebinds the NDArray buffers —
inside a jitted train step XLA turns this into true in-place update via
buffer donation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op


def _rescale_clip(grad, rescale_grad, clip_gradient, wd, weight):
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    return g + wd * weight


@register_op("sgd_update", num_outputs=1, mutate_inputs=(0,))
def _sgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
                clip_gradient=-1.0, lazy_update=True):
    """Vanilla SGD step: w -= lr * (rescaled, clipped grad
    + wd * w)."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient, wd, weight)
    return weight - lr * g


@register_op("sgd_mom_update", num_outputs=2, mutate_inputs=(0, 2))
def _sgd_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                    rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True):
    """SGD with momentum: mom = momentum*mom - lr*g;
    w += mom.  Returns (new_weight, new_mom)."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient, wd, weight)
    new_mom = momentum * mom - lr * g
    return weight + new_mom, new_mom


@register_op("nag_mom_update", num_outputs=2, mutate_inputs=(0, 2))
def _nag_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                    rescale_grad=1.0, clip_gradient=-1.0):
    """Nesterov accelerated gradient: momentum update with the
    gradient looked ahead one step.  Returns (new_weight, new_mom)."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient, wd, weight)
    new_mom = momentum * mom + g
    return weight - lr * (g + momentum * new_mom), new_mom


@register_op("adam_update", num_outputs=3, mutate_inputs=(0, 2, 3))
def _adam_update(weight, grad, mean, var, lr=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                 lazy_update=True):
    """Adam step (no bias correction, reference convention):
    first/second-moment EMAs drive w -= lr * m / (sqrt(v) + eps).
    Returns (new_weight, new_mean, new_var)."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient, wd, weight)
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = beta2 * var + (1 - beta2) * jnp.square(g)
    # the ratio first: with bfloat16 moments and a traced float32 lr
    # (parallel.SPMDTrainer) the divide then stays a bfloat16 divide; as
    # (lr * mean) / (...) it is a float32 one, which cost the v5e 0.5% of
    # BERT's wgrad + Adam fusions (PERF.md, PR 29)
    return (weight - lr * (new_mean / (jnp.sqrt(new_var) + epsilon)),
            new_mean, new_var)


@register_op("rmsprop_update", num_outputs=2, mutate_inputs=(0, 2))
def _rmsprop_update(weight, grad, n, lr=0.001, gamma1=0.9, epsilon=1e-8,
                    wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                    clip_weights=-1.0):
    """RMSProp: EMA of squared gradients normalizes the step;
    optional clip_weights bounds the result.  Returns (new_weight,
    new_n)."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient, wd, weight)
    new_n = (1 - gamma1) * jnp.square(g) + gamma1 * n
    w = weight - lr * g / jnp.sqrt(new_n + epsilon)
    if clip_weights is not None and clip_weights > 0:
        w = jnp.clip(w, -clip_weights, clip_weights)
    return w, new_n


@register_op("rmspropalex_update", num_outputs=4, mutate_inputs=(0, 2, 3, 4))
def _rmspropalex_update(weight, grad, n, g_state, delta, lr=0.001, gamma1=0.95,
                        gamma2=0.9, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                        clip_gradient=-1.0, clip_weights=-1.0):
    """RMSProp (Graves variant): centered second moment plus a
    momentum-like delta accumulator.  Returns (new_weight, new_n,
    new_g, new_delta)."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient, wd, weight)
    new_n = (1 - gamma1) * jnp.square(g) + gamma1 * n
    new_g = (1 - gamma1) * g + gamma1 * g_state
    new_delta = gamma2 * delta - lr * g / jnp.sqrt(new_n - jnp.square(new_g) + epsilon)
    w = weight + new_delta
    if clip_weights is not None and clip_weights > 0:
        w = jnp.clip(w, -clip_weights, clip_weights)
    return w, new_n, new_g, new_delta


@register_op("ftrl_update", num_outputs=3, mutate_inputs=(0, 2, 3))
def _ftrl_update(weight, grad, z, n, lr=0.1, lamda1=0.01, beta=1.0, wd=0.0,
                 rescale_grad=1.0, clip_gradient=-1.0):
    """FTRL-proximal: z/n accumulators with L1 soft-thresholding
    (lamda1) and per-coordinate lr.  Returns (new_weight, new_z,
    new_n)."""
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    new_n = n + jnp.square(g)
    sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
    new_z = z + g - sigma * weight
    w = jnp.where(
        jnp.abs(new_z) <= lamda1, jnp.zeros_like(weight),
        -(new_z - jnp.sign(new_z) * lamda1) /
        ((beta + jnp.sqrt(new_n)) / lr + wd))
    return w, new_z, new_n


@register_op("signsgd_update", num_outputs=1, mutate_inputs=(0,))
def _signsgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
                    clip_gradient=-1.0):
    """SignSGD: steps by the SIGN of the rescaled gradient only;
    wd decays the weight directly."""
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    return weight - lr * (jnp.sign(g) + wd * weight)


@register_op("signum_update", num_outputs=2, mutate_inputs=(0, 2))
def _signum_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, wd_lh=0.0):
    """Signum: momentum EMA of the gradient, step by its sign
    (SignSGD with momentum).  Returns (new_weight, new_mom)."""
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    new_mom = momentum * mom - (1 - momentum) * g
    w = (1 - lr * wd_lh) * weight + lr * jnp.sign(new_mom) - lr * wd * weight
    return w, new_mom


@register_op("adagrad_update", num_outputs=2, mutate_inputs=(0, 2),
             aliases=("_sparse_adagrad_update",))
def _adagrad_update(weight, grad, history, lr=0.01, epsilon=1e-7, wd=0.0,
                    rescale_grad=1.0, clip_gradient=-1.0):
    """AdaGrad: accumulated squared gradients give per-coordinate
    lr decay.  Returns (new_weight, new_history)."""
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    new_hist = history + jnp.square(g)
    return weight - lr * (g / jnp.sqrt(new_hist + epsilon) + wd * weight), new_hist


@register_op("adadelta_update", num_outputs=3, mutate_inputs=(0, 2, 3))
def _adadelta_update(weight, grad, acc_g, acc_delta, lr=1.0, rho=0.9,
                     epsilon=1e-5, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    """AdaDelta: RMS-ratio of accumulated delta to accumulated
    gradient replaces the global lr.  Returns (new_weight, new_acc_g,
    new_acc_delta)."""
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    g = g + wd * weight
    new_acc_g = rho * acc_g + (1 - rho) * jnp.square(g)
    delta = jnp.sqrt(acc_delta + epsilon) / jnp.sqrt(new_acc_g + epsilon) * g
    new_acc_delta = rho * acc_delta + (1 - rho) * jnp.square(delta)
    return weight - lr * delta, new_acc_g, new_acc_delta


@register_op("adamax_update", num_outputs=3, mutate_inputs=(0, 2, 3))
def _adamax_update(weight, grad, mean, var, lr=0.002, beta1=0.9, beta2=0.999,
                   epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                   t=1):
    """AdaMax: Adam with the infinity norm as the second moment
    (running max of |g|).  Returns (new_weight, new_mean, new_var)."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient, wd, weight)
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = jnp.maximum(beta2 * var, jnp.abs(g))
    lr_t = lr / (1 - beta1 ** t)
    return weight - lr_t * new_mean / (new_var + epsilon), new_mean, new_var


@register_op("nadam_update", num_outputs=3, mutate_inputs=(0, 2, 3))
def _nadam_update(weight, grad, mean, var, lr=0.001, beta1=0.9, beta2=0.999,
                  epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                  t=1, schedule_decay=0.004):
    """Nadam: Adam with Nesterov momentum via the schedule-decay
    momentum correction.  Returns (new_weight, new_mean, new_var)."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient, wd, weight)
    m_t = beta1 * (1 - 0.5 * 0.96 ** (t * schedule_decay))
    m_t1 = beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * schedule_decay))
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = beta2 * var + (1 - beta2) * jnp.square(g)
    g_hat = g / (1 - m_t)
    m_hat = new_mean / (1 - m_t1)
    m_bar = (1 - m_t) * g_hat + m_t1 * m_hat
    v_hat = new_var / (1 - beta2 ** t)
    return weight - lr * m_bar / (jnp.sqrt(v_hat) + epsilon), new_mean, new_var


# multi-precision (fp16/bf16 weights with fp32 master copy;
# ref: mp_sgd_update / mp_sgd_mom_update / mp_adam-like kernels)

@register_op("mp_sgd_update", num_outputs=2, mutate_inputs=(0, 2))
def _mp_sgd_update(weight, grad, weight32, lr=0.01, wd=0.0, rescale_grad=1.0,
                   clip_gradient=-1.0, lazy_update=True):
    """Multi-precision SGD: updates the fp32 master copy and
    casts back to the low-precision weight dtype.  Returns
    (new_weight, new_weight32)."""
    g = _rescale_clip(grad.astype(jnp.float32), rescale_grad, clip_gradient,
                      wd, weight32)
    new_w32 = weight32 - lr * g
    return new_w32.astype(weight.dtype), new_w32


@register_op("mp_sgd_mom_update", num_outputs=3, mutate_inputs=(0, 2, 3))
def _mp_sgd_mom_update(weight, grad, mom, weight32, lr=0.01, momentum=0.0,
                       wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                       lazy_update=True):
    """Multi-precision SGD with momentum: fp32 master-copy math,
    low-precision weight output.  Returns (new_weight, new_mom,
    new_weight32)."""
    g = _rescale_clip(grad.astype(jnp.float32), rescale_grad, clip_gradient,
                      wd, weight32)
    new_mom = momentum * mom - lr * g
    new_w32 = weight32 + new_mom
    return new_w32.astype(weight.dtype), new_mom, new_w32


@register_op("mp_adam_update", num_outputs=4, mutate_inputs=(0, 2, 3, 4))
def _mp_adam_update(weight, grad, mean, var, weight32, lr=0.001, beta1=0.9,
                    beta2=0.999, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                    clip_gradient=-1.0):
    """Multi-precision Adam: fp32 master-copy moments and update,
    cast back to the weight dtype.  Returns (new_weight, new_mean,
    new_var, new_weight32)."""
    g = _rescale_clip(grad.astype(jnp.float32), rescale_grad, clip_gradient,
                      wd, weight32)
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = beta2 * var + (1 - beta2) * jnp.square(g)
    new_w32 = weight32 - lr * new_mean / (jnp.sqrt(new_var) + epsilon)
    return new_w32.astype(weight.dtype), new_mean, new_var, new_w32


# ---------------------------------------------------------------------------
# multi-tensor fused updates (ref: optimizer_op.cc multi_sgd_update,
# multi_sgd_mom_update, multi_mp_sgd_*, preloaded_multi_*, multi_sum_sq,
# multi_lars — the Trainer's one-launch-many-weights path) and LAMB
# (ref: lamb.cc lamb_update_phase1/2).
#
# Attrs `lrs`/`wds` are per-weight lists; the preloaded_* variants take
# them as trailing tensor inputs instead (device-resident schedules).
# ---------------------------------------------------------------------------

def _chunk(arrays, n, per):
    """Split the flat variadic input into n per-weight tuples using the
    reference's INTERLEAVED convention (optimizer_op.cc /
    _flatten_list(zip(weights, grads, ...))):
    [w0, g0, (m0, ...), w1, g1, ...] -> [(w0, g0, ...), (w1, g1, ...)]."""
    return [tuple(arrays[i * per:(i + 1) * per]) for i in range(n)]


@register_op("multi_sum_sq", differentiable=False,
             num_outputs=lambda attrs: int(attrs.get("num_arrays", 1)))
def _multi_sum_sq(*arrays, num_arrays=1):
    """Per-array sum of squares in fp32 (the LARS norm inputs);
    one (1,)-shaped output per input array."""
    return tuple(jnp.sum(jnp.square(a.astype(jnp.float32))).reshape((1,))
                 for a in arrays)


@register_op("multi_sgd_update",
             num_outputs=lambda attrs: int(attrs.get("num_weights", 1)))
def _multi_sgd_update(*arrays, lrs=(), wds=(), rescale_grad=1.0,
                      clip_gradient=-1.0, num_weights=1):
    """Fused SGD over many weights in one launch: interleaved
    [w0, g0, w1, g1, ...] inputs, per-weight lrs/wds attrs."""
    outs = []
    for i, (w, g) in enumerate(_chunk(arrays, num_weights, 2)):
        gg = _rescale_clip(g, rescale_grad, clip_gradient, wds[i], w)
        outs.append(w - lrs[i] * gg)
    return tuple(outs)


@register_op("multi_sgd_mom_update",
             num_outputs=lambda attrs: int(attrs.get("num_weights", 1)))
def _multi_sgd_mom_update(*arrays, lrs=(), wds=(), momentum=0.0,
                          rescale_grad=1.0, clip_gradient=-1.0,
                          num_weights=1):
    """Fused momentum-SGD over many weights in one launch:
    interleaved [w, g, mom] triples, per-weight lrs/wds attrs."""
    outs = []
    for i, (w, g, m) in enumerate(_chunk(arrays, num_weights, 3)):
        gg = _rescale_clip(g, rescale_grad, clip_gradient, wds[i], w)
        nm = momentum * m - lrs[i] * gg
        outs.append(w + nm)
    return tuple(outs)


@register_op("multi_mp_sgd_update",
             num_outputs=lambda attrs: int(attrs.get("num_weights", 1)))
def _multi_mp_sgd_update(*arrays, lrs=(), wds=(), rescale_grad=1.0,
                         clip_gradient=-1.0, num_weights=1):
    """Fused multi-precision SGD: interleaved [w, g, w32]
    triples, fp32 master-copy math, per-weight lrs/wds attrs."""
    outs = []
    for i, (w, g, w32) in enumerate(_chunk(arrays, num_weights, 3)):
        gg = _rescale_clip(g.astype(jnp.float32), rescale_grad,
                           clip_gradient, wds[i], w32)
        outs.append((w32 - lrs[i] * gg).astype(w.dtype))
    return tuple(outs)


@register_op("multi_mp_sgd_mom_update",
             num_outputs=lambda attrs: int(attrs.get("num_weights", 1)))
def _multi_mp_sgd_mom_update(*arrays, lrs=(), wds=(), momentum=0.0,
                             rescale_grad=1.0, clip_gradient=-1.0,
                             num_weights=1):
    """Fused multi-precision momentum-SGD: interleaved
    [w, g, mom, w32] quads, fp32 master-copy math, per-weight
    lrs/wds attrs."""
    outs = []
    for i, (w, g, m, w32) in enumerate(_chunk(arrays, num_weights, 4)):
        gg = _rescale_clip(g.astype(jnp.float32), rescale_grad,
                           clip_gradient, wds[i], w32)
        nm = momentum * m - lrs[i] * gg
        outs.append((w32 + nm).astype(w.dtype))
    return tuple(outs)


@register_op("preloaded_multi_sgd_update",
             num_outputs=lambda attrs: int(attrs.get("num_weights", 1)))
def _preloaded_multi_sgd_update(*arrays, rescale_grad=1.0,
                                clip_gradient=-1.0, num_weights=1):
    """Like multi_sgd_update, but lrs/wds arrive as the two trailing
    TENSOR inputs (device-resident schedules, no retrace per lr)."""
    lrs, wds = arrays[-2], arrays[-1]
    outs = []
    for i, (w, g) in enumerate(_chunk(arrays[:-2], num_weights, 2)):
        gg = _rescale_clip(g, rescale_grad, clip_gradient, wds[i], w)
        outs.append(w - lrs[i] * gg)
    return tuple(outs)


@register_op("multi_lars", differentiable=False)
def _multi_lars(lrs, weights_sum_sq, grads_sum_sq, wds, eta=0.001,
                eps=1e-8, rescale_grad=1.0):
    """LARS local-lr schedule (ref: multi_lars.cc): per-layer lr scaled
    by ||w|| / (||g|| + wd*||w|| + eps)."""
    wn = jnp.sqrt(weights_sum_sq)
    gn = jnp.sqrt(grads_sum_sq) * rescale_grad
    ratio = eta * wn / (gn + wds * wn + eps)
    return jnp.where(wn > 0, lrs * ratio, lrs)


@register_op("lamb_update_phase1", num_outputs=3)
def _lamb_update_phase1(weight, grad, mean, var, beta1=0.9, beta2=0.999,
                        epsilon=1e-6, t=1, bias_correction=True, wd=0.0,
                        rescale_grad=1.0, clip_gradient=-1.0):
    """LAMB phase 1 (ref: lamb.cc): adam-style direction g' =
    m̂/(sqrt(v̂)+eps) + wd*w.  Returns (g', new_mean, new_var)."""
    g = grad.astype(jnp.float32) * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    nm = beta1 * mean + (1 - beta1) * g
    nv = beta2 * var + (1 - beta2) * jnp.square(g)
    if bias_correction:
        mh = nm / (1 - beta1 ** t)
        vh = nv / (1 - beta2 ** t)
    else:
        mh, vh = nm, nv
    direction = mh / (jnp.sqrt(vh) + epsilon) + wd * weight
    return direction, nm, nv


@register_op("lamb_update_phase2")
def _lamb_update_phase2(weight, g, r1, r2, lr=0.001,
                        lower_bound=-1.0, upper_bound=-1.0):
    """LAMB phase 2 (ref: lamb.cc): apply with trust ratio r1/r2 where
    r1=||w||, r2=||g'|| (computed by the caller, usually via norm)."""
    r1v = r1.reshape(())
    r2v = r2.reshape(())
    if lower_bound is not None and lower_bound > 0:
        r1v = jnp.maximum(r1v, lower_bound)
    if upper_bound is not None and upper_bound > 0:
        r1v = jnp.minimum(r1v, upper_bound)
    trust = jnp.where((r1v > 0) & (r2v > 0), r1v / r2v, 1.0)
    return weight - lr * trust * g
