"""Gluon Parameter / ParameterDict.

TPU-native counterpart of python/mxnet/gluon/parameter.py: deferred shape
init, grad_req, per-context replicas, list_ctx/data/grad, and trainer
hookup.  A Parameter owns one NDArray per context (data-parallel replicas);
under a sharded mesh (kvstore 'xla' / parallel module) the single replica
is a sharded jax array instead.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray.ndarray import NDArray, array as nd_array
from ..telemetry import tracing as _tracing
from .. import initializer as init_mod

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError"]


class DeferredInitializationError(MXNetError):
    """Raised when data() is called before shape is known (ref: same name)."""


class Parameter:
    def __init__(self, name: str, grad_req: str = "write", shape=None,
                 dtype="float32", lr_mult: float = 1.0, wd_mult: float = 1.0,
                 init=None, allow_deferred_init: bool = False,
                 differentiable: bool = True, stype="default",
                 grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._data: Optional[Dict[Context, NDArray]] = None
        self._grad: Optional[Dict[Context, NDArray]] = None
        self._deferred_init = None  # (initializer, ctx_list, default_init)
        self._trainer = None

    # ---- shape -----------------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown_ok = all(s1 in (0, None) or s1 == s2
                         for s1, s2 in zip(self._shape, new_shape)) \
            and len(self._shape) == len(new_shape)
        if not unknown_ok:
            raise MXNetError(
                f"cannot change shape of Parameter {self.name} from "
                f"{self._shape} to {tuple(new_shape)}")
        self._shape = tuple(new_shape)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise MXNetError(f"invalid grad_req {req!r}")
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._grad = None
            elif self._grad is None:
                self._init_grad()
        for ctxnd in (self._data or {}).values():
            ctxnd._ag_grad_req = req

    def _shape_is_known(self) -> bool:
        return self._shape is not None and all(
            s is not None and s > 0 for s in self._shape)

    # ---- init ------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit: bool = False):
        default_init = default_init or init_mod.Uniform(0.07)
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if not self._shape_is_known():
            if self.allow_deferred_init:
                self._deferred_init = (init, list(ctx), default_init)
                return
            raise MXNetError(
                f"cannot initialize Parameter {self.name}: unknown shape "
                f"{self._shape} and allow_deferred_init=False")
        self._finish_init(init, list(ctx), default_init)

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            return
        if not self._shape_is_known():
            raise DeferredInitializationError(
                f"Parameter {self.name} has unknown shape {self._shape}")
        init, ctx, default_init = self._deferred_init
        self._deferred_init = None
        self._finish_init(init, ctx, default_init)

    def _finish_init(self, init, ctx_list: List[Context], default_init):
        # one set-up record for a run of parameters (telemetry.tracing.phase):
        # the initializer's draw on the host and the write to each context
        with _tracing.phase("mx.setup.init", merge=True, parameters=1,
                            elements=int(np.prod(self._shape))):
            buf = np.zeros(self._shape, dtype=np.float32)
            initializer = init_mod.create(init) if init is not None else \
                (init_mod.create(self.init) if self.init is not None
                 else default_init)
            if init is not None or self.init is not None:
                initializer.init_array(self.name, buf)
            else:
                initializer(init_mod.InitDesc(self.name), buf)
            self._data = {}
            for c in ctx_list:
                self._data[c] = nd_array(buf, ctx=c, dtype=self.dtype)
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        self._grad = {}
        for c, d in self._data.items():
            d.attach_grad(self._grad_req)
            self._grad[c] = d.grad

    # ---- access ----------------------------------------------------------
    def _check_initialized(self, ctx=None):
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    f"Parameter {self.name} has not finished deferred init")
            raise MXNetError(
                f"Parameter {self.name} has not been initialized. "
                "Call .initialize() first")
        if ctx is not None and ctx not in self._data:
            raise MXNetError(
                f"Parameter {self.name} was not initialized on context {ctx}; "
                f"it lives on {list(self._data)}")

    def data(self, ctx: Optional[Context] = None) -> NDArray:
        self._check_initialized(ctx)
        if ctx is None:
            ctx = next(iter(self._data))
        return self._data[ctx]

    def list_data(self) -> List[NDArray]:
        self._check_initialized()
        return list(self._data.values())

    def grad(self, ctx: Optional[Context] = None) -> NDArray:
        self._check_initialized(ctx)
        if self._grad is None:
            raise MXNetError(f"Parameter {self.name} has grad_req='null'")
        if ctx is None:
            ctx = next(iter(self._data))
        return self._data[ctx].grad

    def list_grad(self) -> List[NDArray]:
        self._check_initialized()
        return [d.grad for d in self._data.values()]

    def list_ctx(self) -> List[Context]:
        self._check_initialized()
        return list(self._data)

    def zero_grad(self):
        if self._data is None:
            return
        for d in self._data.values():
            d.zero_grad()

    def set_data(self, data):
        """Set value on all contexts (ref: Parameter.set_data)."""
        self.shape = data.shape
        if self._data is None:
            if self._deferred_init is not None:
                self._finish_deferred_init()
            else:
                raise MXNetError(
                    f"Parameter {self.name} has not been initialized")
        for c in list(self._data):
            src = data if isinstance(data, NDArray) else nd_array(data)
            newd = src.as_in_context(c)
            self._data[c]._data = newd.data.astype(self._data[c].data.dtype)

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._check_initialized()
        cur = self.data()
        self._data = {c: cur.as_in_context(c).copy() if c != cur.ctx else cur
                      for c in ctx}
        if self._grad_req != "null":
            self._init_grad()

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is None:
            return
        for c in list(self._data):
            self._data[c] = self._data[c].astype(dtype)
        if self._grad_req != "null":
            self._init_grad()

    def var(self):
        from ..symbol.symbol import var

        return var(self.name, shape=self._shape, dtype=self.dtype)

    def __repr__(self):
        return f"Parameter {self.name} (shape={self._shape}, dtype={self.dtype})"


class Constant(Parameter):
    """Non-learnable parameter (ref: gluon/parameter.py::Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, np.ndarray):
            value = np.asarray(value, dtype="float32")
        self.value = value
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype if value.dtype != np.float64 else "float32",
                         init=init_mod.Constant(0))
        self._value_arr = value

    def _finish_init(self, init, ctx_list, default_init):
        self._data = {c: nd_array(self._value_arr, ctx=c, dtype=self.dtype)
                      for c in ctx_list}


class ParameterDict:
    """Ordered name->Parameter mapping with a shared prefix
    (ref: gluon/parameter.py::ParameterDict)."""

    def __init__(self, prefix: str = "", shared: Optional["ParameterDict"] = None):
        self._prefix = prefix
        self._params: Dict[str, Parameter] = {}
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def get(self, name: str, **kwargs) -> Parameter:
        """Create-or-retrieve (shared lookup first)."""
        full = self._prefix + name
        param = self._get_impl(full)
        if param is None:
            param = Parameter(full, **kwargs)
            self._params[full] = param
        else:
            for k, v in kwargs.items():
                if k == "shape" and v is not None:
                    param.shape = v if not isinstance(v, int) else (v,)
                elif k == "init" and v is not None and param.init is None:
                    param.init = v
        return param

    def get_constant(self, name: str, value=None) -> Constant:
        full = self._prefix + name
        param = self._get_impl(full)
        if param is None:
            if value is None:
                raise MXNetError(f"no constant named {full} and no value given")
            param = Constant(full, value)
            self._params[full] = param
        return param

    def _get_impl(self, full_name):
        if full_name in self._params:
            return self._params[full_name]
        if self._shared is not None:
            p = self._shared._get_impl(full_name)
            if p is not None:
                self._params[full_name] = p
            return p
        return None

    def update(self, other: "ParameterDict"):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise MXNetError(f"duplicate parameter name {k}")
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit: bool = False):
        default = init_mod.create(init) if init is not None else init_mod.Uniform(0.07)
        for p in self._params.values():
            p.initialize(None, ctx, default_init=default,
                         force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self._params.values():
            p.reset_ctx(ctx)

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def save(self, fname: str, strip_prefix: str = ""):
        from ..serialization import save_ndarrays

        out = {}
        for name, p in self._params.items():
            key = name[len(strip_prefix):] if name.startswith(strip_prefix) else name
            out[key] = p.data().as_in_context(cpu())
        save_ndarrays(fname, out)

    def load(self, fname: str, ctx=None, allow_missing: bool = False,
             ignore_extra: bool = False, restore_prefix: str = ""):
        from ..serialization import load_ndarrays

        loaded = load_ndarrays(fname)
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        if not allow_missing:
            for name in self._params:
                if name not in loaded:
                    raise MXNetError(f"Parameter {name} missing in file {fname}")
        for name, value in loaded.items():
            if name not in self._params:
                if ignore_extra:
                    continue
                raise MXNetError(f"Parameter {name} in file is not in this dict")
            p = self._params[name]
            if p._data is None:
                p.shape = value.shape
                p.initialize(ctx=ctx or [current_context()],
                             default_init=init_mod.Zero())
            p.set_data(value)

    # mapping protocol
    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def __iter__(self):
        return iter(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __len__(self):
        return len(self._params)

    def __repr__(self):
        lines = "\n".join(f"  {p}" for p in self._params.values())
        return f"ParameterDict '{self._prefix}' (\n{lines}\n)"
