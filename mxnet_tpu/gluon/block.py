"""Gluon Block / HybridBlock / CachedOp.

TPU-native counterpart of python/mxnet/gluon/block.py and
src/imperative/cached_op.cc:

  * ``Block``: imperative container with auto-registered children and
    parameters, name scopes, collect_params, save/load.
  * ``HybridBlock.hybrid_forward(F, x, **params)``: dual dispatch — eagerly
    F is the NDArray namespace; when hybridized the SAME code is traced
    with jax tracers through a pure-function namespace.
  * ``hybridize()`` → ``CachedOp``: the whole forward becomes ONE cached
    XLA executable per (train-mode, input signature), with an equally
    cached vjp executable for backward.  This is the reference's
    CachedOp bulked-execution design taken to its limit: on TPU the
    graph path is not an optimization but the performance model.

Functional-state contract: layers with mutable aux state (BatchNorm
moving stats) register updates on the active TraceScope during tracing;
CachedOp returns them as extra outputs and rebinds the aux NDArrays after
each call — the XLA-safe equivalent of the reference's in-place aux-state
writes.
"""
from __future__ import annotations

import contextlib
import json
import re
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .. import autograd as ag
from .. import ndarray as nd_mod
from .. import random as rnd
from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray.ndarray import NDArray
from ..telemetry import tracing as _tracing
from ..ops import residuals
from .parameter import (Constant, DeferredInitializationError, Parameter,
                        ParameterDict)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedOp", "TraceScope",
           "current_trace"]


# ---------------------------------------------------------------------------
# naming (ref: block.py::_BlockScope)
# ---------------------------------------------------------------------------

class _BlockScope(threading.local):
    def __init__(self):
        self.current = None
        self.counters = {}


_SCOPE = _BlockScope()


class _NameManager:
    def __init__(self, block, prefix):
        self._block = block
        self._prefix = prefix
        self._counters: Dict[str, int] = {}
        self._old = None

    @staticmethod
    def create(prefix: Optional[str], params, hint: str):
        cur = _SCOPE.current
        if cur is None:
            if prefix is None:
                cnt = _SCOPE.counters
                i = cnt.get(hint, 0)
                cnt[hint] = i + 1
                prefix = f"{hint}{i}_"
            pdict = ParameterDict(prefix) if params is None else \
                ParameterDict(params.prefix, shared=params)
            return prefix, pdict
        if prefix is None:
            i = cur._counters.get(hint, 0)
            cur._counters[hint] = i + 1
            prefix = f"{hint}{i}_"
        full = cur._prefix + prefix
        pdict = ParameterDict(full) if params is None else \
            ParameterDict(params.prefix, shared=params)
        return full, pdict

    def __enter__(self):
        self._old = _SCOPE.current
        _SCOPE.current = self
        return self

    def __exit__(self, *exc):
        _SCOPE.current = self._old
        return False


# ---------------------------------------------------------------------------
# trace scope — active while a CachedOp traces the block with jax tracers
# ---------------------------------------------------------------------------

class TraceScope(threading.local):
    pass


class _TraceState(threading.local):
    def __init__(self):
        self.scope: Optional["ActiveTrace"] = None
        self.block_prefix = ""    # prefix of the enclosing traced block


_TRACE = _TraceState()


@contextlib.contextmanager
def _block_scope(block: "Block"):
    """``jax.named_scope`` of one traced block, so the compiled program's
    ``op_name`` metadata (and the profile viewer) carries the block path
    ``bertmodel0/encoder/layers/layer3/attn/...``.  Gluon names repeat
    the parents' prefixes, so a component is the block's name less the
    enclosing traced block's prefix where it starts with it, the whole
    name otherwise.  Trace time only: nothing here runs per step."""
    outer = _TRACE.block_prefix
    name = block._name
    if outer and name.startswith(outer):
        name = name[len(outer):]
    _TRACE.block_prefix = block._prefix
    try:
        with jax.named_scope(name or type(block).__name__):
            yield
    finally:
        _TRACE.block_prefix = outer


class ActiveTrace:
    def __init__(self, param_values: Dict[int, Any], train: bool):
        self.param_values = param_values     # id(Parameter) -> traced value
        self.train = train
        # id(Parameter) -> reads of its value in this program: a block
        # called four times reads each of its parameters four times, a
        # segment's inner trace (`nested`) counts into this table, and a
        # read inside a body that is traced once and run `trips` times
        # (`repeated`) counts once a trip
        self.uses: Dict[int, int] = {}
        self.trips = 1
        self.aux_params: List[Parameter] = []
        self.aux_values: List[Any] = []
        self._extra_params: List[Parameter] = []

    def value_of(self, param: Parameter):
        v = self.param_values.get(id(param))
        if v is None:
            raise MXNetError(
                f"Parameter {param.name} used in hybrid forward but not "
                "captured by the CachedOp trace")
        self.uses[id(param)] = self.uses.get(id(param), 0) + self.trips
        return v

    def nested(self) -> "ActiveTrace":
        """The trace of a recomputed segment inside this one: the same
        values and mode, aux updates of its own (they leave through the
        segment's boundary), reads counted into this trace's table."""
        inner = ActiveTrace(self.param_values, self.train)
        inner.uses, inner.trips = self.uses, self.trips
        return inner

    @contextlib.contextmanager
    def repeated(self, trips: int):
        """Around the trace of a loop body (`lax.scan`) that runs `trips`
        times: what it reads, it reads once a trip."""
        outer, self.trips = self.trips, self.trips * trips
        try:
            yield
        finally:
            self.trips = outer

    def use_counts(self) -> Dict[int, int]:
        """{reads: how many Parameters were read that often} so far."""
        counts: Dict[int, int] = {}
        for n in self.uses.values():
            counts[n] = counts.get(n, 0) + 1
        return dict(sorted(counts.items()))

    def add_aux_update(self, param: Parameter, new_value):
        self.aux_params.append(param)
        self.aux_values.append(new_value)

    def __enter__(self):
        self._old = _TRACE.scope
        _TRACE.scope = self
        return self

    def __exit__(self, *exc):
        _TRACE.scope = self._old
        return False


def current_trace() -> Optional[ActiveTrace]:
    return _TRACE.scope


def in_trace() -> bool:
    return _TRACE.scope is not None


# ---------------------------------------------------------------------------
# the pure-function op namespace used as F during tracing
# (counterpart of python/mxnet/symbol as the F of hybrid_forward)
# ---------------------------------------------------------------------------

class _PureNamespace:
    """F for traced execution: ops apply directly to jax values."""

    def __getattr__(self, name):
        from ..ops.registry import apply_pure, get_op

        op = get_op(name)  # raises MXNetError for unknown ops

        def fn(*args, **kwargs):
            out = apply_pure(name, *args, **kwargs)
            return list(out) if isinstance(out, tuple) else out

        fn.__name__ = name
        return fn

    # special stateful frontends
    def Dropout(self, data, p=0.5, mode="training", axes=(), **kw):
        from ..ops.registry import apply_pure

        ts = current_trace()
        train = ts.train if ts is not None else ag.is_training()
        return apply_pure("Dropout", data, rnd.next_key(), p=p, mode=mode,
                          axes=tuple(axes), _train=train)

    def BatchNorm(self, data, gamma, beta, running_mean, running_var,
                  eps=1e-5, momentum=0.9, fix_gamma=False,
                  use_global_stats=False, axis=1, _aux_params=None, **kw):
        from ..ops.registry import apply_pure

        ts = current_trace()
        train = (ts.train if ts is not None else ag.is_training()) \
            and not use_global_stats
        res = apply_pure("BatchNorm", data, gamma, beta, running_mean,
                         running_var, eps=eps, momentum=momentum,
                         fix_gamma=fix_gamma,
                         use_global_stats=use_global_stats, axis=axis,
                         _train=train, **kw)
        if train:
            out, new_mean, new_var = res
            if ts is not None and _aux_params is not None:
                ts.add_aux_update(_aux_params[0], new_mean)
                ts.add_aux_update(_aux_params[1], new_var)
            return out
        return res

    def dot_product_attention(self, query, key, value, valid_mask=None,
                              num_heads=1, scale=None, dropout=0.0,
                              causal=False, num_kv_heads=0, **kw):
        """Fused attention — key + train flag threaded from the trace.
        No `valid_mask` reaches the op as None: it is what lets a causal
        call take the op's blocked route."""
        from ..ops.registry import apply_pure

        ts = current_trace()
        train = ts.train if ts is not None else ag.is_training()
        return apply_pure("dot_product_attention", query, key, value,
                          valid_mask, rnd.next_key(), num_heads=num_heads,
                          scale=scale, dropout=dropout, causal=causal,
                          num_kv_heads=num_kv_heads, _train=train)

    FusedAttention = dot_product_attention


F_PURE = _PureNamespace()


class _NDNamespaceWrapper:
    """F for eager execution — mxnet_tpu.ndarray with BatchNorm routed
    through the layer-aware signature (accepts/ignores _aux_params)."""

    def __getattr__(self, name):
        return getattr(nd_mod, name)

    def BatchNorm(self, data, gamma, beta, running_mean, running_var,
                  _aux_params=None, **kw):
        return nd_mod.BatchNorm(data, gamma, beta, running_mean, running_var,
                                **kw)


F_ND = _NDNamespaceWrapper()


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------

class Block:
    """Base container (ref: gluon/block.py::Block)."""

    def __init__(self, prefix: Optional[str] = None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _NameManager.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _NameManager(self, self._prefix)
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: Dict[str, Parameter] = {}
        self._forward_hooks: List[Callable] = []
        self._forward_pre_hooks: List[Callable] = []

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self) -> ParameterDict:
        return self._params

    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        pat = re.compile(select) if select is not None else None
        for name, p in self.params.items():
            if pat is None or pat.match(name):
                ret._params[name] = p
        for child in self._children.values():
            for name, p in child.collect_params(select).items():
                if name not in ret._params:
                    ret._params[name] = p
        return ret

    # attribute magic: auto-register children and parameters
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block: "Block", name: Optional[str] = None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def apply(self, fn):
        for c in self._children.values():
            c.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit: bool = False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active: bool = True, **kwargs):
        for c in self._children.values():
            c.hybridize(active, **kwargs)

    def cast(self, dtype):
        # one set-up record for the whole tree: the children's `cast`,
        # entered inside this phase, are this phase (tracing.phase)
        with _tracing.phase("mx.setup.cast"):
            for c in self._children.values():
                c.cast(dtype)
            for p in self._reg_params.values():
                p.cast(dtype)

    def zero_grad(self):
        self.collect_params().zero_grad()

    def _collect_params_with_prefix(self, prefix: str = ""):
        """Structural names ('0.weight', 'body.1.bias', …) independent of
        name-scope counters (ref: block.py::_collect_params_with_prefix) —
        what save_parameters/load_parameters key on, so weights load into
        any same-structure network."""
        if prefix:
            prefix += "."
        ret = {prefix + key: p for key, p in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename: str, deduplicate: bool = False):
        from ..context import cpu
        from ..serialization import save_ndarrays

        params = self._collect_params_with_prefix()
        save_ndarrays(filename,
                      {k: p.data().as_in_context(cpu())
                       for k, p in params.items()})

    def load_parameters(self, filename: str, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        from ..context import current_context
        from ..serialization import load_ndarrays
        from .. import initializer as init_mod

        loaded = load_ndarrays(filename)
        params = self._collect_params_with_prefix()
        if not any("." in k for k in loaded) and any("." in k for k in params):
            # fall back: file was saved with full name-scope names
            byname = {p.name: p for p in params.values()}
            params = byname
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise MXNetError(
                        f"Parameter {name} missing in file {filename}")
        for name, value in loaded.items():
            if name not in params:
                if ignore_extra:
                    continue
                raise MXNetError(
                    f"Parameter {name} in file {filename} does not exist in "
                    "this block")
            p = params[name]
            if p._data is None:
                p.shape = value.shape
                p.initialize(ctx=ctx or [current_context()],
                             default_init=init_mod.Zero())
            p.set_data(value)

    # legacy aliases (ref: save_params/load_params deprecated names)
    save_params = save_parameters

    def load_params(self, *a, **kw):
        return self.load_parameters(*a, **kw)

    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-block summary (ref: block.py::summary)."""
        rows = []

        def walk(b, indent):
            nparams = sum(int(np.prod(p.shape)) for p in b._reg_params.values()
                          if p.shape and all(s > 0 for s in p.shape))
            rows.append(f"{'  ' * indent}{type(b).__name__}({b.name}): "
                        f"{nparams} params")
            for c in b._children.values():
                walk(c, indent + 1)

        walk(self, 0)
        print("\n".join(rows))

    def __repr__(self):
        lines = [f"{type(self).__name__}("]
        for key, child in self._children.items():
            lines.append(f"  ({key}): {type(child).__name__}")
        lines.append(")")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# CachedOp (ref: src/imperative/cached_op.cc — here: trace → jitted XLA
# executable + cached vjp executable)
# ---------------------------------------------------------------------------

class CachedOp:
    def __init__(self, block: "HybridBlock", static_alloc=False,
                 static_shape=False, mirror=None):
        self.block = block
        # static_alloc/static_shape are accepted for API parity; XLA's
        # compiled programs are statically planned by construction.
        # mirror: gradient mirroring (ref: MXNET_BACKWARD_DO_MIRROR /
        # GraphExecutor recompute-to-save-memory) — on TPU this is
        # jax.checkpoint: the backward recomputes activations instead of
        # keeping them in HBM, trading MXU FLOPs for memory; all but the
        # values a kernel named for its backward (ops/residuals.py)
        from ..util import env

        self.mirror = (env.get_bool("MXNET_BACKWARD_DO_MIRROR")
                       if mirror is None else bool(mirror))
        self._pure: Dict[bool, Callable] = {}
        self._fwd: Dict[bool, Callable] = {}
        self._vjp: Dict[bool, Callable] = {}
        self._pstruct: Optional[List[Tuple[str, Parameter]]] = None
        self._aux_order: Dict[bool, List[Parameter]] = {}
        self._out_treedef: Dict[bool, Any] = {}

    def _param_list(self) -> List[Tuple[str, Parameter]]:
        if self._pstruct is None:
            self._pstruct = sorted(self.block.collect_params().items())
        return self._pstruct

    def _make_pure(self, train: bool) -> Callable:
        plist = self._param_list()
        block = self.block

        def fn(pvals: Tuple, ivals: Tuple, key):
            trace = ActiveTrace(
                {id(p): v for (_, p), v in zip(plist, pvals)}, train)
            trace.mirror = self.mirror  # per-sub-block remat segments
            with trace, rnd.key_provider(rnd.KeyProvider(key)):
                outs = block.forward(*ivals)
            flat, treedef = jax.tree_util.tree_flatten(outs)
            self._aux_order[train] = list(trace.aux_params)
            self._out_treedef[train] = treedef
            return tuple(flat), tuple(trace.aux_values)

        return fn

    def _get_fwd(self, train: bool) -> Callable:
        if train not in self._fwd:
            pure = self._make_pure(train)
            self._pure[train] = pure
            self._fwd[train] = jax.jit(pure)
        return self._fwd[train]

    def _get_vjp(self, train: bool) -> Callable:
        if train not in self._vjp:
            pure = self._pure[train]

            def vjp_fn(pvals, ivals, key, cts):
                def f(pv, iv):
                    flat, _aux = pure(pv, iv, key)
                    return flat

                _, vjp = jax.vjp(f, tuple(pvals), tuple(ivals))
                pg, ig = vjp(tuple(cts))
                return tuple(pg), tuple(ig)

            self._vjp[train] = jax.jit(vjp_fn)
        return self._vjp[train]

    def __call__(self, *inputs: NDArray):
        ctx = None
        ivals = []
        for x in inputs:
            if isinstance(x, NDArray):
                ctx = ctx or x.ctx
                ivals.append(x.data)
            else:
                ivals.append(x)
        ctx = ctx or current_context()
        train = ag.is_training()
        try:
            plist = self._param_list()
            param_nds = [p.data(ctx) for _, p in plist]
        except DeferredInitializationError:
            # resolve deferred shapes with one eager pass, then retry
            self.block._active = False
            try:
                with ag.pause():
                    self.block(*inputs)
            finally:
                self.block._active = True
            self._pstruct = None
            plist = self._param_list()
            param_nds = [p.data(ctx) for _, p in plist]
        pvals = tuple(pn.data for pn in param_nds)
        key = rnd.next_key()
        fwd = self._get_fwd(train)
        flat, aux_vals = fwd(pvals, tuple(ivals), key)
        # rebind aux state (BatchNorm moving stats) — functional update
        for p, v in zip(self._aux_order[train], aux_vals):
            p.data(ctx)._data = v
        out_nds = [NDArray(o, ctx=ctx) for o in flat]

        if ag.is_recording():
            diff_params = [(pn, p) for pn, (_, p) in zip(param_nds, plist)]
            parents = [(getattr(pn, "_ag_node", None), pn) for pn in param_nds]
            parents += [(getattr(x, "_ag_node", None), x)
                        if isinstance(x, NDArray) else (None, None)
                        for x in inputs]
            cop = self

            def custom_backward(node_cts, _flat=flat):
                cts = tuple(
                    c if c is not None else jax.numpy.zeros(f.shape, f.dtype)
                    for c, f in zip(node_cts, _flat))
                pg, ig = cop._get_vjp(train)(pvals, tuple(ivals), key, cts)
                return list(pg) + list(ig)

            node = ag.TapeNode(None, None, list(pvals) + list(ivals), parents,
                               len(flat), custom_backward=custom_backward)
            for i, o in enumerate(out_nds):
                o._ag_node = (node, i)

        outs = jax.tree_util.tree_unflatten(self._out_treedef[train], out_nds)
        return outs


# ---------------------------------------------------------------------------
# HybridBlock
# ---------------------------------------------------------------------------

class HybridBlock(Block):
    """ref: gluon/block.py::HybridBlock — same dual-dispatch contract."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cached_op: Optional[CachedOp] = None
        self._flags: Dict[str, Any] = {}

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        self._cached_op = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def _clear_cached_op(self):
        self._cached_op = None
        for c in self._children.values():
            if isinstance(c, HybridBlock):
                c._clear_cached_op()

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def _infer_param_shapes(self, *args):
        """Overridden by builtin layers that support deferred shapes;
        called with the forward inputs when a param's shape is unknown."""
        raise MXNetError(
            f"{type(self).__name__} cannot infer parameter shapes; pass "
            "explicit input dims (in_units/in_channels) or initialize with "
            "known shapes")

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes from example inputs
        (ref: HybridBlock.infer_shape)."""
        self._infer_param_shapes(*args)
        for p in self._reg_params.values():
            p._finish_deferred_init()

    def forward(self, x, *args):
        if not isinstance(x, NDArray):
            # traced path: raw jax values; params come from the trace scope
            ts = current_trace()
            params = {}
            for name, p in self._reg_params.items():
                if ts is not None:
                    params[name] = ts.value_of(p)
                else:
                    params[name] = p.data().data
            with _block_scope(self):
                if (ts is not None and getattr(ts, "mirror", False)
                        and self._reg_params
                        and all(hasattr(a, "dtype") for a in args)):
                    # gradient mirroring: each PARAM-BEARING sub-block is a
                    # remat SEGMENT — the backward recomputes its activations
                    # from its inputs instead of keeping them live across the
                    # whole program.  It keeps, beside those inputs, only the
                    # values NAMED inside it (ops/residuals.py: what an
                    # attention kernel wrote for its backward kernels, which
                    # nothing but a second run of the kernel could make
                    # again); a segment that names nothing is recomputed
                    # whole.  Param-less containers are NOT wrapped
                    # (an outer whole-function checkpoint would only add a
                    # redundant full recompute), and blocks with non-array
                    # extra args are left unwrapped.  Aux updates (BatchNorm
                    # stats) made inside the segment are returned THROUGH the
                    # checkpoint boundary and replayed onto the outer trace —
                    # letting the inner tracers escape via the side channel
                    # would be an UnexpectedTracerError.
                    outer = ts
                    aux_params_cell = [()]

                    def seg(xx, pp, *targs):
                        inner = outer.nested()
                        inner.mirror = True
                        with inner:
                            out = self.hybrid_forward(F_PURE, xx, *targs,
                                                      **pp)
                        aux_params_cell[0] = tuple(inner.aux_params)
                        return out, tuple(inner.aux_values)

                    with residuals.segment():
                        out, aux_vals = jax.checkpoint(
                            seg, policy=residuals.KEEP_NAMED)(
                                x, params, *args)
                    for p, v in zip(aux_params_cell[0], aux_vals):
                        ts.add_aux_update(p, v)
                    return out
                return self.hybrid_forward(F_PURE, x, *args, **params)

        if self._active:
            if self._cached_op is None:
                self._cached_op = CachedOp(self, **{
                    k: v for k, v in self._flags.items()
                    if k in ("static_alloc", "static_shape", "mirror")})
            return self._cached_op(x, *args)

        ctx = x.ctx
        try:
            params = {name: p.data(ctx) for name, p in self._reg_params.items()}
        except DeferredInitializationError:
            self.infer_shape(x, *args)
            params = {name: p.data(ctx) for name, p in self._reg_params.items()}
        return self.hybrid_forward(F_ND, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **params):
        raise NotImplementedError

    def export(self, path: str, epoch: int = 0):
        """ref: HybridBlock.export — writes `path-symbol.json` (graph
        metadata: jaxpr text of the traced program) + `path-%04d.params`."""
        if self._cached_op is None:
            raise MXNetError("run at least one forward after hybridize() "
                             "before export()")
        plist = self._cached_op._param_list()
        meta = {
            "framework": "mxnet_tpu",
            "block": type(self).__name__,
            "params": {n: list(p.shape) for n, p in plist},
        }
        with open(f"{path}-symbol.json", "w") as f:
            json.dump(meta, f, indent=2)
        from ..serialization import save_ndarrays
        from ..context import cpu

        save_ndarrays(f"{path}-{epoch:04d}.params",
                      {n: p.data().as_in_context(cpu()) for n, p in plist})
        return f"{path}-symbol.json", f"{path}-{epoch:04d}.params"


def _eval_symbol_eager(outputs, feed):
    """Evaluate a Symbol DAG node-by-node on eager NDArrays through the
    generated frontends — so autograd tapes it, Dropout gets its key, and
    BatchNorm updates its aux stats in place, exactly like hand-written
    imperative code (ref role: CachedOp over an imported graph)."""
    from .. import autograd as _ag
    from .. import random as _rnd
    from ..ndarray.register import _SPECIAL, lookup
    from ..symbol.symbol import KEYED_OPS, TRAIN_AWARE_OPS

    env = {}
    for node in outputs._topo():
        if node.op is None:
            if node.name not in feed:
                raise MXNetError(
                    f"SymbolBlock: free variable {node.name!r} is neither "
                    f"an input nor a loaded parameter")
            env[(id(node), 0)] = feed[node.name]
            continue
        ins = [env[(id(i), ix)] for (i, ix) in node.inputs]
        attrs = {k: v for k, v in node.attrs.items()
                 if not k.startswith("__") and k != "name"}
        if node.op not in _SPECIAL:
            # ops without a dedicated frontend (e.g. RNN) still need
            # their train flag / PRNG key threaded, like the executor
            if node.op in TRAIN_AWARE_OPS:
                attrs["_train"] = _ag.is_training()
            if node.op in KEYED_OPS:
                # as an NDArray so invoke routes it to the key INPUT
                # slot (a raw jax array would be frozen as an attr)
                from ..ndarray import NDArray as _ND

                attrs["key"] = _ND(_rnd.next_key())
        out = lookup(node.op)(*ins, **attrs)
        outs = out if isinstance(out, (list, tuple)) else [out]
        for i, o in enumerate(outs):
            env[(id(node), i)] = o
    res = [env[(id(n), i)] for (n, i) in outputs._heads]
    return res[0] if len(res) == 1 else res


class SymbolBlock(HybridBlock):
    """Construct a Block from a symbol graph (ref: block.py::SymbolBlock):
    the arg/aux vars that are not inputs become gluon Parameters, and
    forward evaluates the graph imperatively through the op frontends
    (taped under autograd; aux stats update in place)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        if isinstance(outputs, (list, tuple)):
            from ..symbol import Group

            outputs = Group(list(outputs))
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        self._sb_outputs = outputs
        self._sb_inputs = [i if isinstance(i, str) else i.name
                           for i in inputs]
        in_set = set(self._sb_inputs)
        self._sb_args = [n for n in outputs.list_arguments()
                         if n not in in_set]
        self._sb_aux = list(outputs.list_auxiliary_states())
        with self.name_scope():
            for n in self._sb_args:
                self._reg_params[n] = self.params.get(
                    n, allow_deferred_init=True)
            for n in self._sb_aux:
                self._reg_params[n] = self.params.get(
                    n, grad_req="null", allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Load `prefix-symbol.json` (+ `.params`) into a ready Block
        (ref: SymbolBlock.imports)."""
        from .. import symbol as sym_mod
        from ..serialization import load_ndarrays

        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        block = SymbolBlock(sym, list(input_names))
        if param_file:
            raw = load_ndarrays(param_file)
            if not isinstance(raw, dict):
                raise MXNetError("SymbolBlock.imports: params file must "
                                 "hold a named dict")
            # accept both checkpoint-style arg:/aux: tags and plain names
            loaded = {(k.split(":", 1)[1] if ":" in k else k): v
                      for k, v in raw.items()}
            for name, p in block._collect_params_with_prefix().items():
                if name not in loaded:
                    raise MXNetError(
                        f"SymbolBlock.imports: parameter {name!r} not "
                        f"found in {param_file}")
                v = loaded[name]
                p.shape = tuple(v.shape)
                p.initialize(ctx=ctx)
                p.set_data(v if ctx is None else v.as_in_context(ctx))
        return block

    def _infer_param_shapes(self, *args):
        # deferred init: resolve every parameter shape from the graph
        shape_kwargs = {n: tuple(a.shape)
                        for n, a in zip(self._sb_inputs, args)}
        arg_shapes, _, aux_shapes = \
            self._sb_outputs.infer_shape_partial(**shape_kwargs)
        by_name = dict(zip(self._sb_outputs.list_arguments(), arg_shapes))
        by_name.update(zip(self._sb_outputs.list_auxiliary_states(),
                           aux_shapes))
        for name, p in self._collect_params_with_prefix().items():
            shp = by_name.get(name)
            if p.shape in (None, ()) or any(s == 0 for s in (p.shape or ())):
                if shp is None or any(s in (None, 0) for s in shp):
                    raise MXNetError(
                        f"SymbolBlock: cannot infer shape of parameter "
                        f"{name!r} from input shapes {shape_kwargs}")
                p.shape = tuple(shp)

    def hybridize(self, active=True, **kwargs):
        # no-op: the graph is already compiled; stays silent so a parent
        # network's cascaded hybridize() (reference workflow: imported
        # feature extractor inside a HybridSequential) keeps working
        if active:
            import warnings

            warnings.warn("SymbolBlock is already a graph; hybridize() "
                          "has no effect", stacklevel=2)

    def forward(self, *args):
        self._ensure_init(*args)
        feed = dict(zip(self._sb_inputs, args))
        for name, p in self._collect_params_with_prefix().items():
            feed[name] = p.data(ctx=args[0].ctx if args else None)
        return _eval_symbol_eager(self._sb_outputs, feed)

    def _ensure_init(self, *args):
        params = self._collect_params_with_prefix()
        if any(p._data is None for p in params.values()):
            self._infer_param_shapes(*args)
            for p in params.values():
                if p._data is None:
                    p._finish_deferred_init()
