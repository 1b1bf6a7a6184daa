"""Generated op namespace for mxnet_tpu.nd.

Counterpart of the reference's import-time wrapper generation
(ref: python/mxnet/ndarray/register.py::_make_ndarray_function, which lists
registered ops through the C API and synthesizes Python functions).  Here
wrappers are synthesized lazily from the op registry via module __getattr__.

Special frontends (RNG injection, train-mode injection, in-place aux-state
rebinds) are defined explicitly below, matching the reference ops whose
kernels consult OpContext state.
"""
from __future__ import annotations

from typing import Callable, Dict

from .. import autograd
from .. import random as _random
from ..ops.registry import OP_REGISTRY, invoke
from .ndarray import NDArray


def _make_wrapper(name: str) -> Callable:
    def fn(*args, out=None, name=name, **kwargs):
        res = invoke(name, *args, **kwargs)
        if out is not None:
            src = res[0] if isinstance(res, list) else res
            out._data = src._data
            return out
        return res

    op = OP_REGISTRY.get(name)
    fn.__name__ = name
    fn.__qualname__ = name
    fn.__doc__ = (f"Imperative wrapper for registered op '{name}'.\n\n"
                  f"{op.param_doc}")
    return fn


# ---- special frontends ----------------------------------------------------

def Dropout(data, p=0.5, mode="training", axes=(), **kw):
    """ref: nd.Dropout — consults global train mode; key auto-threaded."""
    return invoke("Dropout", data, _random.next_key(), p=p, mode=mode,
                  axes=tuple(axes), _train=autograd.is_training())


def Custom(*inputs, op_type=None, **kwargs):
    """Eager frontend for user CustomOps (ref: nd.Custom over custom.cc).

    Runs the user's forward/backward DIRECTLY on host numpy — works on
    any device, including PJRT plugins without host-callback support.
    Traced programs
    (hybridize / Symbol / SPMDTrainer) instead hit the registry 'Custom'
    op, which stages the same host code via jax.pure_callback."""
    import jax.numpy as jnp
    import numpy as np

    from .. import operator as _operator
    from .ndarray import NDArray

    prop = _operator.get_prop(op_type)(**kwargs)
    np_ins = [x.asnumpy() for x in inputs]
    structs = _operator.out_structs_for(
        prop, [a.shape for a in np_ins], [a.dtype for a in np_ins])
    # ONE operator instance shared forward->backward (user code may stash
    # forward state on self for backward, reference lifetime semantics)
    op_inst = _operator.make_operator(prop, np_ins)
    np_outs = _operator.run_forward_host(op_inst, np_ins, structs,
                                         is_train=autograd.is_training())
    ctx = inputs[0].ctx if inputs else None
    outs = tuple(NDArray(jnp.asarray(o), ctx=ctx) for o in np_outs)
    if autograd.is_recording():
        parents = [(autograd._node_of(x), x) for x in inputs]

        def custom_backward(node_cts, _np_ins=np_ins, _np_outs=np_outs,
                            _op=op_inst):
            import jax

            np_cts = [np.asarray(jax.device_get(c)) if c is not None
                      else np.zeros(o.shape, o.dtype)
                      for c, o in zip(node_cts, _np_outs)]
            grads = _operator.run_backward_host(_op, _np_ins, _np_outs,
                                                np_cts)
            return [jnp.asarray(g) for g in grads]

        node = autograd.TapeNode(None, None, [x.data for x in inputs],
                                 parents, len(outs),
                                 custom_backward=custom_backward)
        for i, o in enumerate(outs):
            o._ag_node = (node, i)
    return outs[0] if len(outs) == 1 else list(outs)


def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
              momentum=0.9, fix_gamma=False, use_global_stats=False,
              output_mean_var=False, axis=1, **kw):
    """ref: nd.BatchNorm — updates moving stats in place in train mode."""
    train = autograd.is_training() and not use_global_stats
    res = invoke("BatchNorm", data, gamma, beta, moving_mean, moving_var,
                 eps=eps, momentum=momentum, fix_gamma=fix_gamma,
                 use_global_stats=use_global_stats, axis=axis, _train=train,
                 **kw)
    if train:
        out, new_mean, new_var = res
        moving_mean._data = new_mean._data
        moving_var._data = new_var._data
        return out
    return res


def dot_product_attention(query, key, value, valid_mask=None, num_heads=1,
                          scale=None, dropout=0.0, causal=False,
                          num_kv_heads=0, **kw):
    """Fused attention frontend — threads the PRNG key + train flag for
    attention-probability dropout (ref: BERT dropout-on-softmax)."""
    if valid_mask is None:
        import numpy as _np

        from .ndarray import array as _array

        sk = key.shape[1] if key.ndim == 3 else key.shape[2]
        valid_mask = _array(_np.ones((key.shape[0], sk), _np.float32),
                            ctx=key.ctx)
    return invoke("dot_product_attention", query, key, value, valid_mask,
                  _random.next_key(), num_heads=num_heads, scale=scale,
                  dropout=dropout, causal=causal, num_kv_heads=num_kv_heads,
                  _train=autograd.is_training())


def _make_random_wrapper(op_name: str):
    def fn(*args, ctx=None, **kwargs):
        out = invoke(op_name, _random.next_key(), *args, **kwargs)
        if ctx is not None:
            out = out.as_in_context(ctx)
        return out

    fn.__name__ = op_name
    return fn


_SPECIAL: Dict[str, Callable] = {
    "Dropout": Dropout,
    "dropout": Dropout,
    "BatchNorm": BatchNorm,
    "batch_norm": BatchNorm,
    "dot_product_attention": dot_product_attention,
    "FusedAttention": dot_product_attention,
    "Custom": Custom,
}
for _rn in ("_random_uniform", "_random_normal", "_random_randint",
            "_random_gamma", "_random_exponential", "_random_poisson",
            "_random_bernoulli", "_sample_multinomial", "_shuffle",
            "_random_gumbel", "_random_laplace", "_random_negative_binomial",
            "_sample_uniform", "_sample_normal", "_sample_gamma",
            "_sample_exponential", "_sample_poisson",
            "_sample_negative_binomial",
            "_sample_generalized_negative_binomial"):
    _SPECIAL[_rn] = _make_random_wrapper(_rn)
    _SPECIAL[_rn.lstrip("_")] = _SPECIAL[_rn]  # e.g. nd.sample_gamma
# legacy bare aliases (ref: nd.uniform/nd.normal over random_uniform)
_SPECIAL["uniform"] = _SPECIAL["_random_uniform"]
_SPECIAL["normal"] = _SPECIAL["_random_normal"]


def lookup(name: str):
    if name in _SPECIAL:
        return _SPECIAL[name]
    if name in OP_REGISTRY:
        return _make_wrapper(name)
    raise AttributeError(f"no registered op '{name}'")
