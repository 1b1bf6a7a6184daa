"""Optimizers (ref: python/mxnet/optimizer/optimizer.py): registry,
Optimizer base (lr/wd mults, multi-precision fp32 master weights, state
creation), SGD/NAG/Adam/AdaGrad/AdaDelta/Adamax/Nadam/RMSProp/Ftrl/Signum,
and the serializable Updater used by KVStore servers.

Update math runs through the registered optimizer update ops
(ops/optimizer_ops.py) — one cached XLA executable per parameter shape.
"""
from __future__ import annotations

import pickle
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..base import MXNetError, Registry
from ..ndarray.ndarray import NDArray, zeros
from ..ops.registry import apply_pure, invoke

__all__ = ["Optimizer", "Updater", "create", "register", "get_updater"]

_REG: Registry = Registry("optimizer")
register = _REG.register


def create(name, **kwargs) -> "Optimizer":
    if isinstance(name, Optimizer):
        return name
    return _REG.get(name)(**kwargs)


class Optimizer:
    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count: Dict[int, int] = {}
        self.multi_precision = multi_precision
        self.idx2name = param_idx2name or {}
        self.param_dict = param_dict or {}
        self.lr_mult: Dict[str, float] = {}
        self.wd_mult: Dict[str, float] = {}

    # ---- state -----------------------------------------------------------
    def create_state(self, index, weight) -> Any:
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and str(weight.data.dtype) in ("float16", "bfloat16"):
            w32 = weight.astype("float32")
            return (self.create_state(index, w32), w32)
        return self.create_state(index, weight)

    # ---- bookkeeping (ref: Optimizer._update_count / _get_lr / _get_wd) --
    def _update_count(self, index):
        self._index_update_count.setdefault(index, self.begin_num_update)
        self._index_update_count[index] += 1
        self.num_update = max(self.num_update, self._index_update_count[index])

    def _get_lr(self, index) -> float:
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index) -> float:
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    def set_learning_rate(self, lr):
        self.lr = lr

    @property
    def learning_rate(self):
        return self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = dict(args_wd_mult)

    def _common(self, index) -> Dict[str, float]:
        return dict(lr=self._get_lr(index), wd=self._get_wd(index),
                    rescale_grad=self.rescale_grad,
                    clip_gradient=self.clip_gradient
                    if self.clip_gradient is not None else -1.0)

    # ---- fused step (pure-function view of the update math) -------------
    #
    # The eager path above dispatches one registered update op per
    # parameter.  The fused path (optimizer/fused.py) applies the SAME
    # registered pure functions over the whole parameter pytree in one
    # jitted program.  The split of responsibilities:
    #
    #   _FUSED_STATIC : names of the attrs the math reads at trace time
    #       (momentum, betas, clip_gradient, ...).  They key the
    #       executable cache; changing one retraces, which is correct.
    #       None (the base default) marks an optimizer as not fusible.
    #   fused_hyper   : per-step host-side scalars (lr with mults and
    #       bias correction folded in, wd, rescale_grad, the step count
    #       t where the kernel needs it).  These enter the program as
    #       TRACED arguments, so set_learning_rate / a new
    #       rescale_grad = scale/batch_size never retrigger a compile.
    #   fused_fold_t  : the per-class half of fused_hyper, how the
    #       update count t enters lr / wd / rescale_grad.  Plain
    #       arithmetic, so parallel.SPMDTrainer calls it INSIDE its step
    #       program with a traced t and a traced lr.
    #   fused_apply   : the pure math, (weight, grad, state, hyper) ->
    #       (new_weight, new_state) on raw jax values.

    _FUSED_STATIC: Optional[Tuple[str, ...]] = None
    # True when fused_hyper carries the raw step count "t" (bias
    # correction computed INSIDE the kernel).  t participates in the
    # per-parameter dtype cast, and half floats cannot represent
    # integers past 256 (bf16) / 2048 (f16) — so these optimizers take
    # the eager loop for half-precision weights without a multi-
    # precision master copy (see FusedUpdater.update_all).
    _FUSED_T_HYPER = False
    # True when fused_apply is purely ELEMENTWISE (every output element
    # depends only on the matching input elements + scalars).  The SPMD
    # step (optimizer/spmd.py) may then concatenate many parameters
    # into one flat ZeRO bucket — one reduce-scatter/update/all-gather
    # per bucket instead of per parameter.  Norm-based updates (LAMB's
    # per-tensor trust ratio) must keep per-parameter tensors and set
    # this False.
    _FUSED_ELEMENTWISE = True

    def fused_static_key(self) -> Optional[Tuple]:
        """Hashable fingerprint of the trace-time attrs, or None when
        this optimizer has no fused path (fall back to the eager loop)."""
        if self._FUSED_STATIC is None:
            return None
        return tuple((a, getattr(self, a)) for a in self._FUSED_STATIC)

    def fused_hyper(self, index, t) -> Dict[str, float]:
        """Per-step scalars for parameter `index` at update count `t`,
        computed on the host and passed as traced jit arguments."""
        return self.fused_fold_t({"lr": float(self._get_lr(index)),
                                  "wd": float(self._get_wd(index)),
                                  "rescale_grad": float(self.rescale_grad)},
                                 t)

    def fused_fold_t(self, hyper, t):
        """Fold the update count into `hyper` (lr, wd, rescale_grad of
        one parameter) and return it: bias correction into lr (Adam),
        or `t` itself where the kernel takes it (_FUSED_T_HYPER).  `t`
        and the values of `hyper` are Python numbers or traced float32
        scalars alike."""
        if self._FUSED_T_HYPER:
            hyper["t"] = t
        return hyper

    def _fused_clip(self) -> float:
        return self.clip_gradient if self.clip_gradient is not None else -1.0

    def _fused_common(self, hyper) -> Dict[str, Any]:
        return dict(lr=hyper["lr"], wd=hyper["wd"],
                    rescale_grad=hyper["rescale_grad"],
                    clip_gradient=self._fused_clip())

    def fused_apply(self, weight, grad, state, hyper):
        """Pure update math on jax values: returns (new_weight, new_state)
        with new_state mirroring the structure of `state`."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the fused step")

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def _mp_active(self, weight, state) -> bool:
        """Whether `state` carries an fp32 master copy for a half-
        precision weight — THE multi-precision predicate, shared by the
        eager dispatch below and the fused path (optimizer/fused.py)."""
        return (self.multi_precision and isinstance(state, tuple)
                and isinstance(state[-1], NDArray)
                and str(state[-1].data.dtype) == "float32"
                and str(weight.data.dtype) in ("float16", "bfloat16"))

    def update_multi_precision(self, index, weight, grad, state):
        if self._mp_active(weight, state):
            self._update_mp(index, weight, grad, state)
        else:
            self.update(index, weight, grad, state)

    def _update_mp(self, index, weight, grad, state):
        inner_state, w32 = state
        self.update(index, w32, grad.astype("float32"), inner_state)
        weight._data = w32.data.astype(weight.data.dtype)


def _rebind(targets, results):
    """Write update-op results back into the mutated NDArrays."""
    if isinstance(results, NDArray):
        results = [results]
    for t, r in zip(targets, results):
        t._data = r.data


@register("sgd")
class SGD(Optimizer):
    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.ctx, dtype=str(weight.data.dtype))

    def _sparse_update(self, index, weight, grad, state, kw):
        """Lazy update: only the rows present in the row_sparse gradient
        are touched (ref: sgd_update FComputeEx on kRowSparseStorage +
        SGDUpdateDnsRspImpl lazy_update path)."""
        import jax.numpy as jnp

        rows = grad._aux["indices"]
        g = jnp.take(grad._data, rows, axis=0).astype(weight.data.dtype)
        g = g * kw["rescale_grad"]
        if kw["clip_gradient"] > 0:
            g = jnp.clip(g, -kw["clip_gradient"], kw["clip_gradient"])
        w_rows = jnp.take(weight.data, rows, axis=0)
        g = g + kw["wd"] * w_rows
        if state is None:
            weight._data = weight.data.at[rows].add(-kw["lr"] * g)
        else:
            m_rows = jnp.take(state.data, rows, axis=0)
            m_rows = self.momentum * m_rows - kw["lr"] * g
            state._data = state.data.at[rows].set(m_rows)
            weight._data = weight.data.at[rows].add(m_rows)

    def update(self, index, weight, grad, state):
        from ..ndarray.sparse import RowSparseNDArray

        self._update_count(index)
        kw = self._common(index)
        if isinstance(grad, RowSparseNDArray):
            if self.lazy_update:
                return self._sparse_update(index, weight, grad, state, kw)
            grad = NDArray(grad._data, ctx=grad.ctx)  # std_update: densify
        if state is None:
            _rebind([weight], invoke("sgd_update", weight, grad, **kw))
        else:
            _rebind([weight, state],
                    invoke("sgd_mom_update", weight, grad, state,
                           momentum=self.momentum, **kw))

    def _update_mp(self, index, weight, grad, state):
        inner, w32 = state
        self._update_count(index)
        kw = self._common(index)
        if inner is None:
            _rebind([weight, w32], invoke("mp_sgd_update", weight, grad, w32, **kw))
        else:
            _rebind([weight, inner, w32],
                    invoke("mp_sgd_mom_update", weight, grad, inner, w32,
                           momentum=self.momentum, **kw))

    _FUSED_STATIC = ("momentum", "clip_gradient")

    def fused_apply(self, weight, grad, state, hyper):
        kw = self._fused_common(hyper)
        if state is None:
            return apply_pure("sgd_update", weight, grad, **kw), None
        return apply_pure("sgd_mom_update", weight, grad, state,
                          momentum=self.momentum, **kw)


@register("nag")
class NAG(SGD):
    def update(self, index, weight, grad, state):
        from ..ndarray.sparse import RowSparseNDArray

        if isinstance(grad, RowSparseNDArray):
            # NAG has no lazy sparse kernel (ref: nag_mom_update is
            # dense-only); densify = std_update semantics
            grad = NDArray(grad._data, ctx=grad.ctx)
        self._update_count(index)
        kw = self._common(index)
        if state is None:
            _rebind([weight], invoke("sgd_update", weight, grad, **kw))
        else:
            _rebind([weight, state],
                    invoke("nag_mom_update", weight, grad, state,
                           momentum=self.momentum, **kw))

    def fused_apply(self, weight, grad, state, hyper):
        kw = self._fused_common(hyper)
        if state is None:
            return apply_pure("sgd_update", weight, grad, **kw), None
        return apply_pure("nag_mom_update", weight, grad, state,
                          momentum=self.momentum, **kw)


@register("adam")
class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        dt = str(weight.data.dtype)
        return (zeros(weight.shape, ctx=weight.ctx, dtype=dt),
                zeros(weight.shape, ctx=weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        kw = self._common(index)
        # bias correction folded into lr (ref: Adam.update)
        kw["lr"] *= (1.0 - self.beta2 ** t) ** 0.5 / (1.0 - self.beta1 ** t)
        mean, var = state
        _rebind([weight, mean, var],
                invoke("adam_update", weight, grad, mean, var,
                       beta1=self.beta1, beta2=self.beta2,
                       epsilon=self.epsilon, **kw))

    _FUSED_STATIC = ("beta1", "beta2", "epsilon", "clip_gradient")

    def fused_fold_t(self, hyper, t):
        # the same bias-correction fold as the eager path — a new t
        # only changes a traced scalar, never the program
        hyper["lr"] *= (1.0 - self.beta2 ** t) ** 0.5 \
            / (1.0 - self.beta1 ** t)
        return hyper

    def fused_apply(self, weight, grad, state, hyper):
        mean, var = state
        nw, nm, nv = apply_pure("adam_update", weight, grad, mean, var,
                                beta1=self.beta1, beta2=self.beta2,
                                epsilon=self.epsilon,
                                **self._fused_common(hyper))
        return nw, (nm, nv)


@register("adagrad")
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, ctx=weight.ctx, dtype=str(weight.data.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        _rebind([weight, state],
                invoke("adagrad_update", weight, grad, state,
                       epsilon=self.float_stable_eps, **kw))

    _FUSED_STATIC = ("float_stable_eps", "clip_gradient")

    def fused_apply(self, weight, grad, state, hyper):
        return apply_pure("adagrad_update", weight, grad, state,
                          epsilon=self.float_stable_eps,
                          **self._fused_common(hyper))


@register("adadelta")
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        dt = str(weight.data.dtype)
        return (zeros(weight.shape, ctx=weight.ctx, dtype=dt),
                zeros(weight.shape, ctx=weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        kw.pop("lr")
        acc_g, acc_d = state
        _rebind([weight, acc_g, acc_d],
                invoke("adadelta_update", weight, grad, acc_g, acc_d,
                       rho=self.rho, epsilon=self.epsilon, lr=1.0, **kw))

    _FUSED_STATIC = ("rho", "epsilon", "clip_gradient")

    def fused_apply(self, weight, grad, state, hyper):
        acc_g, acc_d = state
        nw, ng, ndel = apply_pure(
            "adadelta_update", weight, grad, acc_g, acc_d, rho=self.rho,
            epsilon=self.epsilon, lr=1.0, wd=hyper["wd"],
            rescale_grad=hyper["rescale_grad"],
            clip_gradient=self._fused_clip())
        return nw, (ng, ndel)


@register("adamax")
class Adamax(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def create_state(self, index, weight):
        dt = str(weight.data.dtype)
        return (zeros(weight.shape, ctx=weight.ctx, dtype=dt),
                zeros(weight.shape, ctx=weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        kw = self._common(index)
        mean, var = state
        _rebind([weight, mean, var],
                invoke("adamax_update", weight, grad, mean, var,
                       beta1=self.beta1, beta2=self.beta2, t=t, **kw))

    _FUSED_STATIC = ("beta1", "beta2", "clip_gradient")
    _FUSED_T_HYPER = True

    def fused_apply(self, weight, grad, state, hyper):
        mean, var = state
        nw, nm, nv = apply_pure("adamax_update", weight, grad, mean, var,
                                beta1=self.beta1, beta2=self.beta2,
                                t=hyper["t"], **self._fused_common(hyper))
        return nw, (nm, nv)


@register("nadam")
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay

    def create_state(self, index, weight):
        dt = str(weight.data.dtype)
        return (zeros(weight.shape, ctx=weight.ctx, dtype=dt),
                zeros(weight.shape, ctx=weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        kw = self._common(index)
        mean, var = state
        _rebind([weight, mean, var],
                invoke("nadam_update", weight, grad, mean, var,
                       beta1=self.beta1, beta2=self.beta2,
                       epsilon=self.epsilon, t=t,
                       schedule_decay=self.schedule_decay, **kw))

    _FUSED_STATIC = ("beta1", "beta2", "epsilon", "schedule_decay",
                     "clip_gradient")
    _FUSED_T_HYPER = True

    def fused_apply(self, weight, grad, state, hyper):
        mean, var = state
        nw, nm, nv = apply_pure("nadam_update", weight, grad, mean, var,
                                beta1=self.beta1, beta2=self.beta2,
                                epsilon=self.epsilon, t=hyper["t"],
                                schedule_decay=self.schedule_decay,
                                **self._fused_common(hyper))
        return nw, (nm, nv)


@register("rmsprop")
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2 = gamma1, gamma2
        self.epsilon = epsilon
        self.centered = centered
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        dt = str(weight.data.dtype)
        if self.centered:
            return (zeros(weight.shape, ctx=weight.ctx, dtype=dt),
                    zeros(weight.shape, ctx=weight.ctx, dtype=dt),
                    zeros(weight.shape, ctx=weight.ctx, dtype=dt))
        return zeros(weight.shape, ctx=weight.ctx, dtype=dt)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        kw["clip_weights"] = self.clip_weights if self.clip_weights else -1.0
        if self.centered:
            n, g, delta = state
            _rebind([weight, n, g, delta],
                    invoke("rmspropalex_update", weight, grad, n, g, delta,
                           gamma1=self.gamma1, gamma2=self.gamma2,
                           epsilon=self.epsilon, **kw))
        else:
            _rebind([weight, state],
                    invoke("rmsprop_update", weight, grad, state,
                           gamma1=self.gamma1, epsilon=self.epsilon, **kw))

    _FUSED_STATIC = ("gamma1", "gamma2", "epsilon", "centered",
                     "clip_weights", "clip_gradient")

    def fused_apply(self, weight, grad, state, hyper):
        kw = self._fused_common(hyper)
        kw["clip_weights"] = self.clip_weights if self.clip_weights else -1.0
        if self.centered:
            n, g, delta = state
            nw, nn, ng, nd = apply_pure(
                "rmspropalex_update", weight, grad, n, g, delta,
                gamma1=self.gamma1, gamma2=self.gamma2,
                epsilon=self.epsilon, **kw)
            return nw, (nn, ng, nd)
        return apply_pure("rmsprop_update", weight, grad, state,
                          gamma1=self.gamma1, epsilon=self.epsilon, **kw)


@register("ftrl")
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        dt = str(weight.data.dtype)
        return (zeros(weight.shape, ctx=weight.ctx, dtype=dt),
                zeros(weight.shape, ctx=weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        z, n = state
        _rebind([weight, z, n],
                invoke("ftrl_update", weight, grad, z, n, lamda1=self.lamda1,
                       beta=self.beta, **kw))

    _FUSED_STATIC = ("lamda1", "beta", "clip_gradient")

    def fused_apply(self, weight, grad, state, hyper):
        z, n = state
        nw, nz, nn = apply_pure("ftrl_update", weight, grad, z, n,
                                lamda1=self.lamda1, beta=self.beta,
                                **self._fused_common(hyper))
        return nw, (nz, nn)


@register("signum")
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, ctx=weight.ctx, dtype=str(weight.data.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        if state is None:
            _rebind([weight], invoke("signsgd_update", weight, grad, **kw))
        else:
            _rebind([weight, state],
                    invoke("signum_update", weight, grad, state,
                           momentum=self.momentum, wd_lh=self.wd_lh, **kw))

    _FUSED_STATIC = ("momentum", "wd_lh", "clip_gradient")

    def fused_apply(self, weight, grad, state, hyper):
        kw = self._fused_common(hyper)
        if state is None:
            return apply_pure("signsgd_update", weight, grad, **kw), None
        return apply_pure("signum_update", weight, grad, state,
                          momentum=self.momentum, wd_lh=self.wd_lh, **kw)


@register("signsgd")
class SignSGD(Signum):
    def __init__(self, **kwargs):
        kwargs.setdefault("momentum", 0.0)
        super().__init__(**kwargs)


@register("lamb")
class LAMB(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2
        self.epsilon = epsilon
        self.lower_bound, self.upper_bound = lower_bound, upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        dt = str(weight.data.dtype)
        return (zeros(weight.shape, ctx=weight.ctx, dtype=dt),
                zeros(weight.shape, ctx=weight.ctx, dtype=dt))

    def update(self, index, weight, grad, state):
        import jax.numpy as jnp

        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        mean, var = state
        g, new_mean, new_var = invoke(
            "lamb_update_phase1", weight, grad, mean, var,
            beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon,
            t=t, bias_correction=self.bias_correction, wd=wd,
            rescale_grad=self.rescale_grad,
            clip_gradient=self.clip_gradient or -1.0)
        mean._data = new_mean.data
        var._data = new_var.data
        r1 = weight.norm()
        r2 = g.norm()
        new_w = invoke("lamb_update_phase2", weight, g, r1, r2, lr=lr,
                       lower_bound=self.lower_bound or -1.0,
                       upper_bound=self.upper_bound or -1.0)
        weight._data = new_w.data

    _FUSED_STATIC = ("beta1", "beta2", "epsilon", "lower_bound",
                     "upper_bound", "bias_correction", "clip_gradient")
    _FUSED_T_HYPER = True
    # the phase-2 trust ratio is per-TENSOR (norm(w)/norm(update)):
    # concatenating params would corrupt the norms
    _FUSED_ELEMENTWISE = False

    def fused_apply(self, weight, grad, state, hyper):
        mean, var = state
        direction, nm, nv = apply_pure(
            "lamb_update_phase1", weight, grad, mean, var,
            beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon,
            t=hyper["t"], bias_correction=self.bias_correction,
            wd=hyper["wd"], rescale_grad=hyper["rescale_grad"],
            clip_gradient=self._fused_clip())
        r1 = apply_pure("norm", weight)
        r2 = apply_pure("norm", direction)
        nw = apply_pure("lamb_update_phase2", weight, direction, r1, r2,
                        lr=hyper["lr"],
                        lower_bound=self.lower_bound or -1.0,
                        upper_bound=self.upper_bound or -1.0)
        return nw, (nm, nv)


@register("test")
class Test(Optimizer):
    def create_state(self, index, weight):
        return zeros(weight.shape, ctx=weight.ctx)

    def update(self, index, weight, grad, state):
        weight._data = (weight + grad * self.rescale_grad).data

    _FUSED_STATIC = ()

    def fused_apply(self, weight, grad, state, hyper):
        return weight + grad * hyper["rescale_grad"], state


class Updater:
    """Serializable updater (ref: optimizer.py::Updater, get_updater) —
    the object a KVStore server runs to apply gradients."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict[int, Any] = {}
        self.states_synced: Dict[int, bool] = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def get_states(self, dump_optimizer=False):
        def to_np(s):
            if isinstance(s, NDArray):
                return s.asnumpy()
            if isinstance(s, (tuple, list)):
                return tuple(to_np(x) for x in s)
            return s

        payload = {k: to_np(v) for k, v in self.states.items()}
        if dump_optimizer:
            return pickle.dumps((payload, self.optimizer.__class__.__name__,
                                 self.optimizer.__dict__.copy()))
        return pickle.dumps(payload)

    def set_states(self, states, ctx=None):
        """Restore a payload; `ctx` places the buffers on a specific
        device — a replica updater's state must live WITH its replica,
        not on the default device."""
        data = pickle.loads(states)
        if isinstance(data, tuple) and len(data) == 3:
            payload, _cls, _odict = data
        else:
            payload = data
        self._pending = payload
        for k, v in payload.items():
            self.states[k] = self._restore(v, ctx)

    def _restore(self, v, ctx=None):
        if isinstance(v, np.ndarray):
            from ..ndarray.ndarray import array

            return array(v, ctx=ctx) if ctx is not None else array(v)
        if isinstance(v, tuple):
            return tuple(self._restore(x, ctx) for x in v)
        return v


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)
