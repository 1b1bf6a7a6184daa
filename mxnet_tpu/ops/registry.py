"""Operator registry + imperative invoke path.

TPU-native counterpart of the reference's op machinery:
  - nnvm op registry with FCompute kernels (ref: src/operator/**,
    NNVM_REGISTER_OP, FCompute<xpu>)
  - Imperative::Invoke dispatch (ref: src/imperative/imperative.cc)
  - the dependency engine's async execution (ref: src/engine/threaded_engine.cc)

Design (idiomatic TPU, not a port):
  * Every op is a PURE jax function ``fn(*arrays, **attrs)``.  Shape/dtype
    inference is obtained from ``jax.eval_shape`` instead of hand-written
    FInferShape/FInferType.
  * The eager path compiles and caches one XLA executable per
    (op, attrs, input shapes/dtypes) via ``jax.jit`` — the counterpart of
    the reference's per-op CUDA kernel + engine push.  Dispatch is async
    (PjRt returns futures), so the Python thread does not block — the same
    contract the reference's ThreadedEngine provides.
  * Gradients come from ``jax.vjp`` on the same pure function, compiled and
    cached per signature at backward time.  XLA dead-code-eliminates the
    forward recomputation inside the vjp when it isn't needed, so this is
    cheap — and the true perf path is hybridize (one fused program).
"""
from __future__ import annotations

import functools
import itertools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..analysis import sanitizer as _mxsan
from ..base import MXNetError, Registry
from ..util import env
from .. import profiler as _profiler
from ..telemetry import instruments as _tinstruments
from ..telemetry import metrics as _tmetrics
from ..telemetry import tracing as _tracing

__all__ = ["Operator", "register_op", "get_op", "list_ops", "invoke",
           "apply_pure", "dispatch"]


class Operator:
    """A registered op: pure jax fn + metadata.

    Parameters
    ----------
    name : canonical CamelCase or snake_case op name (reference-compatible).
    fn : pure function of positional jax arrays and keyword attrs.
    num_outputs : static output count, or a callable(attrs)->int.
    differentiable : if False, never recorded on the autograd tape.
    mutate_inputs : indices of inputs that the *frontend* treats as mutated
        (optimizer update ops); purely informational — the pure fn returns
        the new value and the frontend rebinds the NDArray buffer.
    """

    def __init__(self, name: str, fn: Callable, *, num_outputs=1,
                 differentiable: bool = True, mutate_inputs: Sequence[int] = (),
                 aliases: Sequence[str] = (), no_jit: bool = False):
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs
        self.differentiable = differentiable
        self.mutate_inputs = tuple(mutate_inputs)
        self.aliases = tuple(aliases)
        # eager-only op: output shape depends on input VALUES (boolean_mask)
        # — cannot be traced/jitted; invoke calls fn on concrete arrays
        self.no_jit = no_jit
        self._build_descriptor()

    # ---- typed attribute descriptor (the dmlc::Parameter role:
    # DMLC_DECLARE_PARAMETER declares name/type/default per op attr and
    # rejects unknown kwargs; here the descriptor is derived from the pure
    # fn's signature — parameters with defaults are attrs, the rest are
    # array inputs) -------------------------------------------------------
    def _build_descriptor(self):
        import inspect

        self.attr_defaults: Dict[str, Any] = {}
        self.input_names: List[str] = []
        self.allow_any_attr = False
        try:
            sig = inspect.signature(self.fn)
        except (TypeError, ValueError):
            self.allow_any_attr = True
            return
        self.param_order: List[str] = []
        self.param_default: Dict[str, Any] = {}
        for p in sig.parameters.values():
            if p.kind == inspect.Parameter.VAR_KEYWORD:
                self.allow_any_attr = True
            elif p.kind == inspect.Parameter.VAR_POSITIONAL:
                self.input_names.append("*" + p.name)
            elif p.default is inspect.Parameter.empty:
                self.input_names.append(p.name)
                self.param_order.append(p.name)
            else:
                self.attr_defaults[p.name] = p.default
                self.param_order.append(p.name)
                self.param_default[p.name] = p.default

    def validate_attrs(self, attrs: dict) -> dict:
        """Reject unknown attributes loudly and coerce reference-style
        string values ("(3, 3)", "64", "True") to the declared type.
        Returns the (possibly coerced) attrs dict."""
        if self.allow_any_attr:
            return attrs
        out = None
        for k, v in attrs.items():
            if k not in self.attr_defaults:
                if k.startswith("__"):  # scope attrs (__lr_mult__ etc)
                    continue
                raise MXNetError(
                    f"operator {self.name!r} has no attribute {k!r}; "
                    f"valid attributes: {sorted(self.attr_defaults)} "
                    f"(array inputs: {self.input_names})")
            d = self.attr_defaults[k]
            if isinstance(v, str) and d is not None \
                    and not isinstance(d, str):
                import ast

                try:
                    cv = ast.literal_eval(v)
                except (ValueError, SyntaxError):
                    raise MXNetError(
                        f"operator {self.name!r} attribute {k!r}: cannot "
                        f"parse {v!r} as {type(d).__name__}")
                if out is None:
                    out = dict(attrs)
                out[k] = cv
        return attrs if out is None else out

    @property
    def param_doc(self) -> str:
        """Generated parameter section (ref: dmlc Parameter __DOC__)."""
        lines = []
        if self.input_names:
            lines.append("Array inputs: " + ", ".join(self.input_names))
        if self.attr_defaults:
            lines.append("Attributes:")
            for k, d in self.attr_defaults.items():
                tname = type(d).__name__ if d is not None else "optional"
                lines.append(f"    {k} : {tname}, default {d!r}")
        if self.allow_any_attr:
            lines.append("(accepts free-form keyword attributes)")
        return "\n".join(lines)

    def nout(self, attrs: dict) -> int:
        if callable(self.num_outputs):
            return self.num_outputs(attrs)
        return self.num_outputs

    def __repr__(self):
        return f"Op({self.name})"


OP_REGISTRY: Registry[Operator] = Registry("operator", lowercase=False)


def register_op(name: str, *, num_outputs=1, differentiable: bool = True,
                mutate_inputs: Sequence[int] = (), aliases: Sequence[str] = (),
                no_jit: bool = False):
    """Decorator: register a pure jax function as a framework op."""

    def _wrap(fn: Callable) -> Callable:
        op = Operator(name, fn, num_outputs=num_outputs,
                      differentiable=differentiable,
                      mutate_inputs=mutate_inputs, aliases=aliases,
                      no_jit=no_jit)
        OP_REGISTRY.register(name)(op)
        for a in aliases:
            OP_REGISTRY.register(a)(op)
        return fn

    return _wrap


def get_op(name: str) -> Operator:
    return OP_REGISTRY.get(name)


def list_ops() -> List[str]:
    return OP_REGISTRY.list()


# --------------------------------------------------------------------------
# attrs normalisation — attrs must be hashable to key the executable cache
# (counterpart of dmlc::Parameter's typed, canonicalised op kwargs).
# --------------------------------------------------------------------------

def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, np.ndarray):
        return ("__nparr__", v.shape, str(v.dtype), v.tobytes())
    if isinstance(v, np.generic):
        return v.item()
    return v


def freeze_attrs(attrs: dict) -> Tuple:
    return tuple(sorted((k, _freeze(v)) for k, v in attrs.items()))


def thaw_attrs(key: Tuple) -> dict:
    return {k: v for k, v in key}


# --------------------------------------------------------------------------
# Executable caches (counterpart: CachedOp-per-op + cuDNN autotune cache).
# jax.jit itself caches per input shape/dtype; we cache the jitted callable
# per (op, attrs) so attrs are baked in as static values.
# --------------------------------------------------------------------------

_jit_lock = threading.Lock()
# mxsan annotations: reads are the optimistic half of the
# double-checked idiom (deliberately lock-free); writes must stay
# under _jit_lock — the sanitizer verifies exactly that at runtime.
# Values are _CacheEntry cells (callable + LRU tick); both caches are
# BOUNDED by MXNET_OP_CACHE_MAX so attr-churning workloads (dynamic
# shapes through reshape/slice attrs) cannot grow them without bound.
_jit_cache: Dict[Tuple, "_CacheEntry"] = _mxsan.track(
    {}, "ops.registry._jit_cache", reads="unlocked-ok")
_grad_cache: Dict[Tuple, "_CacheEntry"] = _mxsan.track(
    {}, "ops.registry._grad_cache", reads="unlocked-ok")
_cache_ticks = itertools.count(1)
# all three counters mutate under _jit_lock (the _AotDispatch per-sig
# evictions re-acquire it after their instance lock just to count)
_cache_evictions = {"ops_jit": 0, "ops_grad": 0, "ops_aot": 0}

# MXNET_ENGINE_TYPE=NaiveEngine → fully synchronous execution for debugging
# (ref: src/engine/naive_engine.cc). Any other value = async (default).
_NAIVE = env.get_str("MXNET_ENGINE_TYPE") == "NaiveEngine"

# MXNET_COMPILE_CACHE_OPS=1 routes per-op executables through the
# persistent compile cache (AOT per input signature).  Read once, like
# _NAIVE; tests toggle via _refresh_ops_aot().
_OPS_AOT = env.get_bool("MXNET_COMPILE_CACHE_OPS")


def _refresh_ops_aot() -> bool:
    """Re-read the knob and drop cached callables built under the old
    mode (test hook; production reads the knob once at import)."""
    global _OPS_AOT
    _OPS_AOT = env.get_bool("MXNET_COMPILE_CACHE_OPS")
    with _jit_lock:
        _jit_cache.clear()
        _grad_cache.clear()
    return _OPS_AOT


class _CacheEntry:
    """Cached jit/grad callable.  ``tick`` is LRU recency, refreshed by
    a plain attribute write on the lock-free hit path; the eviction
    scan under _jit_lock reads it."""

    __slots__ = ("fn", "tick")

    def __init__(self, fn):
        self.fn = fn
        self.tick = next(_cache_ticks)


def _cache_hit(cache: Dict[Tuple, "_CacheEntry"], key: Tuple):
    e = cache.get(key)
    if e is None:
        return None
    e.tick = next(_cache_ticks)
    return e.fn


def _cache_insert_locked(cache: Dict[Tuple, "_CacheEntry"], key: Tuple,
                         fn: Callable, store: str) -> None:
    """Insert + bounded-LRU eviction.  Caller holds _jit_lock (both
    caches share it, matching the existing locking discipline)."""
    cache[key] = _CacheEntry(fn)
    cap = env.get_int("MXNET_OP_CACHE_MAX")
    evicted = 0
    while cap and len(cache) > cap:
        oldest = min(cache.items(), key=lambda kv: kv[1].tick)[0]
        if oldest == key:
            break  # never evict what we just inserted
        del cache[oldest]
        _cache_evictions[store] += 1  # mxlint: disable=MX004 — caller holds _jit_lock
        evicted += 1
    if evicted:
        _tinstruments.compile_cache_evict_total(store).inc(evicted)


def _first_party_fn(fn: Callable) -> bool:
    """Whether a registered op's implementation lives in this package
    (gates alias-key eligibility — see _AotDispatch and
    compile_cache.first_party, the one policy implementation)."""
    from ..compile_cache import first_party

    return first_party(getattr(fn, "__module__", ""))


def cache_info() -> Dict[str, int]:
    """Sizes + eviction counts of the in-process op executable caches
    (the bounded-cache tests assert on this).  ``aot_evictions``
    aggregates per-signature drops across every _AotDispatch wrapper
    (MXNET_COMPILE_CACHE_OPS=1)."""
    with _jit_lock:
        return {"jit_entries": len(_jit_cache),
                "grad_entries": len(_grad_cache),
                "jit_evictions": _cache_evictions["ops_jit"],
                "grad_evictions": _cache_evictions["ops_grad"],
                "aot_evictions": _cache_evictions["ops_aot"]}


class _AotDispatch:
    """Opt-in wrapper (MXNET_COMPILE_CACHE_OPS=1): dispatches through
    AOT-compiled executables obtained from the persistent compile
    cache, one per concrete input signature.  Falls back to the lazy
    ``jax.jit`` callable whenever an argument is not a committed
    concrete ``jax.Array`` (python scalars, numpy, tracers) — AOT needs
    exact avals, and correctness beats persistence.

    ``use_alias=False`` (user-registered ops, i.e. ``op.fn`` outside
    the ``mxnet_tpu`` namespace) disables the cheap alias index: an
    alias key cannot see the op's implementation, and unlike
    first-party code a user edit does not bump the framework version
    that invalidates the store — the full program-text key (built
    after lower) stays the only disk key, so a changed implementation
    can never be served a stale executable."""

    __slots__ = ("_site", "_lazy", "_ckey", "_per_sig", "_lock",
                 "_use_alias")

    def __init__(self, site: str, lazy: Callable, ckey: Tuple,
                 use_alias: bool = True):
        self._site = site
        self._lazy = lazy
        self._ckey = ckey
        self._per_sig: Dict[Tuple, "_CacheEntry"] = {}
        self._lock = threading.Lock()
        self._use_alias = use_alias

    def _sig(self, args) -> Optional[Tuple]:
        leaves = jax.tree_util.tree_leaves(args)
        parts = []
        for a in leaves:
            if not isinstance(a, jax.Array) or \
                    isinstance(a, jax.core.Tracer):
                return None
            parts.append((tuple(a.shape), str(a.dtype),
                          bool(a.weak_type),
                          tuple(sorted(str(d) for d in a.devices()))))
        return (jax.tree_util.tree_structure(args), tuple(parts))

    def __call__(self, *args):
        sig = self._sig(args)
        if sig is None:
            return self._lazy(*args)
        ent = self._per_sig.get(sig)  # GIL-atomic instance-dict read
        if ent is not None:
            ent.tick = next(_cache_ticks)
            return ent.fn(*args)
        evicted = 0
        with self._lock:
            ent = self._per_sig.get(sig)
            if ent is None:
                from .. import compile_cache as _cc

                cell = {}

                def lowered():
                    low = cell.get("lowered")
                    if low is None:
                        low = cell["lowered"] = \
                            self._lazy.lower(*args)
                    return low

                # alias: op identity + attrs + avals — no tracing; a
                # warm process dispatches its first op without
                # lowering it (first-party ops only, see class doc)
                alias = _cc.cache_key(
                    "ops.alias", parts=(self._ckey, sig)) \
                    if self._use_alias else None
                fn, origin = _cc.get_or_compile(
                    self._site,
                    lambda: _cc.cache_key(
                        "ops", parts=(self._ckey, sig),
                        program_text=lowered().as_text(),
                        components={"op": self._ckey, "avals": sig}),
                    lambda: lowered().compile(), alias=alias)
                _mxsan.record_compile(
                    self._site, (self._ckey, sig),
                    provenance="build" if origin == "compiled"
                    else "cache")
                ent = self._per_sig[sig] = _CacheEntry(fn)
                # same bound as the (op, attrs) caches: per-signature
                # executables must not grow without limit under
                # dynamic-shape workloads
                cap = env.get_int("MXNET_OP_CACHE_MAX")
                while cap and len(self._per_sig) > cap:
                    oldest = min(self._per_sig.items(),
                                 key=lambda kv: kv[1].tick)[0]
                    if oldest == sig:
                        break
                    del self._per_sig[oldest]
                    evicted += 1
        if evicted:  # counting/telemetry outside the instance lock
            with _jit_lock:
                _cache_evictions["ops_aot"] += evicted
            _tinstruments.compile_cache_evict_total("ops_aot").inc(
                evicted)
        return ent.fn(*args)


def jitted(op: Operator, attrs_key: Tuple) -> Callable:
    key = (op.name, attrs_key)
    fn = _cache_hit(_jit_cache, key)
    if fn is None:
        with _jit_lock:
            fn = _cache_hit(_jit_cache, key)
            if fn is None:
                attrs = thaw_attrs(attrs_key)
                fn = jax.jit(functools.partial(op.fn, **attrs))
                if _OPS_AOT:
                    # compiles (and records) per concrete signature
                    # inside the wrapper instead of here
                    fn = _AotDispatch(
                        f"ops.jit:{op.name}", fn, (op.name, attrs_key),
                        use_alias=_first_party_fn(op.fn))
                else:
                    # per-op site: a storm means ONE op's sigs churn
                    _mxsan.record_compile(f"ops.jit:{op.name}",
                                          attrs_key)
                _cache_insert_locked(_jit_cache, key, fn, "ops_jit")
    return fn


def grad_fn(op: Operator, attrs_key: Tuple, argnums: Tuple[int, ...]) -> Callable:
    """Jitted vjp: (inputs, cotangents) -> grads for `argnums` inputs."""
    key = (op.name, attrs_key, argnums)
    fn = _cache_hit(_grad_cache, key)
    if fn is None:
        with _jit_lock:
            fn = _cache_hit(_grad_cache, key)
            if fn is None:
                attrs = thaw_attrs(attrs_key)
                f = functools.partial(op.fn, **attrs)

                def _vjp(inputs, cts, _f=f, _argnums=argnums):
                    def fwd(*diff_ins):
                        full = list(inputs)
                        for i, a in zip(_argnums, diff_ins):
                            full[i] = a
                        return _f(*full)

                    _, vjp = jax.vjp(fwd, *[inputs[i] for i in _argnums])
                    return vjp(cts)

                fn = jax.jit(_vjp)
                if _OPS_AOT:
                    fn = _AotDispatch(
                        f"ops.grad:{op.name}", fn,
                        (op.name, attrs_key, argnums),
                        use_alias=_first_party_fn(op.fn))
                else:
                    _mxsan.record_compile(f"ops.grad:{op.name}",
                                          (attrs_key, argnums))
                _cache_insert_locked(_grad_cache, key, fn, "ops_grad")
    return fn


def apply_pure(name: str, *arrays, **attrs):
    """Run op on raw jax values — the path used inside traced (hybridized)
    programs, where inputs are jax tracers and no wrapping happens.

    The op's work is traced under ``jax.named_scope`` of its registered
    name (``BatchNorm``, ``Convolution``, ``dot_product_attention``...):
    the vocabulary that ``parallel.spmd.step_programs()`` and a device
    profile key on.  It does not change when XLA renames a fusion or a
    user renames a block."""
    op = get_op(name)
    with jax.named_scope(op.name):
        return op.fn(*arrays, **attrs)


# --------------------------------------------------------------------------
# Imperative invoke (ref: MXImperativeInvokeEx → Imperative::Invoke)
# --------------------------------------------------------------------------

def _op_dispatch_child(op: Operator):
    """Counter child cached on the Operator, keyed by the registry
    generation — enabled dispatch pays an attribute read + int compare
    per call, not the instruments lock; a registry clear() invalidates
    the cache via the generation bump."""
    gen = _tmetrics.get_registry().generation
    cached = getattr(op, "_tel_dispatch", None)
    if cached is not None and cached[0] == gen:
        return cached[1]
    child = _tinstruments.op_dispatch_total(op.name)
    op._tel_dispatch = (gen, child)
    return child


def dispatch(op: Operator, attrs_key: Tuple, arrays, attrs: dict):
    """The dispatch hot section of `invoke`.

    When neither the profiler nor telemetry is active this is ONE
    predicate check ahead of the cached-executable call — no context
    manager, no event append, no counter touch (the overhead gate in
    tests/test_telemetry.py holds this to the seed dispatch cost).
    """
    if not (_profiler._running or _tracing._ENABLED):
        if op.no_jit:
            return op.fn(*arrays, **attrs)
        return jitted(op, attrs_key)(*arrays)
    with _profiler.profile_op(op.name):
        if op.no_jit:
            out = op.fn(*arrays, **attrs)
        else:
            out = jitted(op, attrs_key)(*arrays)
    if _tracing._ENABLED:
        _op_dispatch_child(op).inc()
    return out

def invoke(op_name: str, *inputs, **attrs):
    """Imperative op call on NDArrays → NDArray(s).

    Mirrors CS1 in SURVEY.md: infer/alloc outputs (jax does this), record
    on the autograd tape if recording, async-dispatch the compiled
    executable (PjRt), return immediately.
    """
    from ..ndarray.ndarray import NDArray, wrap_outputs
    from .. import autograd as ag

    op = get_op(op_name)
    # an OPTIONAL array input (state=None, bias=None) passed by keyword
    # must become a positional input, not an attr — otherwise the array
    # would be frozen into the jit cache key and crash inside the trace
    nd_kw = {k: v for k, v in attrs.items() if isinstance(v, NDArray)}
    if nd_kw and getattr(op, "param_order", None):
        order = op.param_order
        unknown = [k for k in nd_kw if k not in order]
        if unknown:
            if op.allow_any_attr:
                nd_kw = {k: v for k, v in nd_kw.items() if k in order}
            else:
                raise MXNetError(
                    f"operator {op.name!r} has no input or attribute "
                    f"{unknown[0]!r}; array inputs: {op.input_names}, "
                    f"attributes: {sorted(op.attr_defaults)}")
        if nd_kw:
            last = max(order.index(k) for k in nd_kw)
            extra = []
            for name in order[len(inputs):last + 1]:
                if name in nd_kw:
                    attrs.pop(name)
                    extra.append(nd_kw[name])
                else:  # gap: fill the declared default (e.g. state=None)
                    extra.append(attrs.pop(name,
                                           op.param_default.get(name)))
            inputs = tuple(inputs) + tuple(extra)
    arrays = []
    ctx = None
    for x in inputs:
        if isinstance(x, NDArray):
            # ._data: the dense jax payload — for sparse NDArrays .data is
            # the values block (reference naming); generic ops see the
            # densified view (ref: FCompute fallback densifies FComputeEx
            # storage types)
            arrays.append(x._data)
            ctx = ctx or x.ctx
        else:
            arrays.append(x)
    attrs = op.validate_attrs(attrs)  # loud unknown-attr errors + coercion
    attrs_key = freeze_attrs(attrs)
    out = dispatch(op, attrs_key, arrays, attrs)
    if _NAIVE:
        from .. import engine as _engine

        if _engine.in_bulk():
            # bulking scope defers the synchronous wait to scope exit
            _engine._track(out if isinstance(out, (tuple, list)) else [out])
        else:
            jax.block_until_ready(out)
    results = wrap_outputs(out, ctx)
    if op.differentiable and ag.is_recording():
        ag.record_op(op, attrs_key, inputs, arrays, results)
    return results
