"""NDArray: imperative tensor over a JAX/PjRt device buffer.

TPU-native counterpart of the reference NDArray
(ref: include/mxnet/ndarray.h, src/ndarray/ndarray.cc — chunk + engine var
+ shape/dtype/ctx; python/mxnet/ndarray/ndarray.py frontend).

Design notes (idiomatic TPU, not a port):
  * The payload is a ``jax.Array`` living in HBM (or host memory for cpu
    contexts).  JAX dispatch is asynchronous — calling an op returns a
    future-backed array immediately, which is exactly the contract the
    reference's dependency engine provides; ``asnumpy``/``wait_to_read``
    are the only sync points (ref: Engine::WaitForVar).
  * Mutation (in-place ops, sliced assignment) is emulated functionally:
    the op produces a fresh buffer and the NDArray rebinds to it.  XLA's
    buffer donation makes this allocation-free inside jitted programs;
    version-counter semantics (reads-before-write ordering) are inherited
    from JAX's effect ordering.
  * Autograd hooks (attach_grad / .grad / backward) live directly on the
    array, recorded by mxnet_tpu.autograd's tape.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError, integer_types, numeric_types
from ..context import Context, cpu, current_context

__all__ = ["NDArray", "wrap_outputs", "array", "zeros", "ones", "full",
           "empty", "arange", "from_jax", "concatenate", "stack"]

_DTYPE_ALIASES = {
    "float32": jnp.float32, "float64": jnp.float64, "float16": jnp.float16,
    "bfloat16": jnp.bfloat16, "uint8": jnp.uint8, "int8": jnp.int8,
    "int32": jnp.int32, "int64": jnp.int64, "bool": jnp.bool_,
    None: jnp.float32,
}


_INT32_MAX = 2 ** 31 - 1


def _normalize_basic_key(pval, key):
    """(starts, limits, strides, squeeze) tuples for a fully-basic key,
    or None when the key has advanced components / negative steps."""
    ks = key if isinstance(key, tuple) else (key,)
    if any(k is Ellipsis for k in ks):
        # expand a single Ellipsis to full slices (x[...], x[..., 0])
        pos = next(i for i, k in enumerate(ks) if k is Ellipsis)
        if any(k is Ellipsis for k in ks[pos + 1:]):
            return None
        fill = pval.ndim - (len(ks) - 1)
        ks = ks[:pos] + (slice(None),) * fill + ks[pos + 1:]
    if len(ks) > pval.ndim or not all(
            isinstance(k, (int, np.integer, slice)) for k in ks):
        return None
    starts, limits, strides, squeeze = [], [], [], []
    for i, k in enumerate(ks):
        n = pval.shape[i]
        if isinstance(k, slice):
            st, sp, stp = k.indices(n)
            if stp <= 0:
                return None
            sp = max(sp, st)  # x[10:5] is a valid EMPTY slice, not an error
            starts.append(st)
            limits.append(sp)
            strides.append(stp)
        else:
            k = int(k) + (n if int(k) < 0 else 0)
            starts.append(k)
            limits.append(k + 1)
            strides.append(1)
            squeeze.append(i)
    for i in range(len(ks), pval.ndim):
        starts.append(0)
        limits.append(pval.shape[i])
        strides.append(1)
    return tuple(starts), tuple(limits), tuple(strides), tuple(squeeze)


@functools.lru_cache(maxsize=256)
def _big_slice_fn(starts, limits, strides, squeeze):
    # one jitted fn per distinct slice spec: the lru_cache keeps the
    # function identity stable so jax's own jit cache hits on repeat
    return jax.jit(lambda x: jax.lax.squeeze(
        jax.lax.slice(x, starts, limits, strides), squeeze))


def _index_value(pval, key):
    """pval[key], with a large-offset escape hatch: eager jax lowers even
    static basic slices through dynamic_slice, whose runtime start
    indices are int32 — any offset past 2^31 overflows (nightly
    test_single_dim_beyond_2g_static_slice).  For overflow-risk BASIC
    keys the slice runs as a jitted lax.slice instead, where the bounds
    are static HLO attributes; the jitted fns are lru-cached per slice
    spec so repeated reads (view refreshes) compile once."""
    if max(pval.shape, default=0) <= _INT32_MAX:
        return pval[key]
    norm = _normalize_basic_key(pval, key)
    if norm is None:
        # advanced/negative-step reads would go through jnp's eager
        # int32 gather, whose clamp arithmetic overflows on a >2^31 dim
        # and returns WRONG DATA silently — refuse loudly instead (the
        # write path refuses symmetrically)
        raise MXNetError(
            "indexing an array with a dimension > 2^31-1 supports only "
            f"basic, positive-step keys (shape {pval.shape}, key "
            f"{key!r}); jax's int32 index path would silently return "
            "corrupt data — reshape to dims under 2^31 for advanced "
            "indexing")
    return _big_slice_fn(*norm)(pval)


_BIG_CHUNK = 2 ** 30


@functools.lru_cache(maxsize=256)
def _big_update_fn(shape, ax, norm):
    starts, limits, strides, squeeze = norm
    st, sp = starts[ax], limits[ax]
    inner_key = tuple(
        (0 if i in squeeze else slice(None)) if i == ax
        else (starts[i] if i in squeeze else slice(starts[i], limits[i]))
        for i in range(len(shape)))
    # indexed (target) shape of the assignment, for value broadcasting;
    # the big axis position among the value's (non-squeezed) dims
    idx_shape = tuple(limits[i] - starts[i] for i in range(len(shape))
                      if i not in squeeze)
    axpos = sum(1 for i in range(ax) if i not in squeeze)

    def fn(x, v):
        # static lax.slice bounds are int64-safe HLO attributes.  If the
        # targeted band itself still spans > 2^31 rows (the key did not
        # narrow the big axis, e.g. x[:, 2] = v), it is processed in
        # <= 2^30-row chunks so every scatter sees small dims only —
        # one band.at[].set past 2^31 would hit the exact int32 clamp
        # overflow this helper exists to avoid.
        pieces = [jax.lax.slice_in_dim(x, 0, st, axis=ax)]
        if sp - st <= _INT32_MAX:
            band = jax.lax.slice_in_dim(x, st, sp, axis=ax)
            pieces.append(band.at[inner_key].set(v))
        else:
            vb = jnp.broadcast_to(jnp.asarray(v), idx_shape)
            for cst in range(st, sp, _BIG_CHUNK):
                cen = min(cst + _BIG_CHUNK, sp)
                band = jax.lax.slice_in_dim(x, cst, cen, axis=ax)
                vchunk = jax.lax.slice_in_dim(vb, cst - st, cen - st,
                                              axis=axpos)
                pieces.append(band.at[inner_key].set(vchunk))
        pieces.append(jax.lax.slice_in_dim(x, sp, shape[ax], axis=ax))
        return jnp.concatenate(pieces, axis=ax)

    return jax.jit(fn)


def _update_value(pval, key, value):
    """Functional basic-key update (`pval.at[key].set(value)`) that stays
    CORRECT on arrays with a dimension past 2^31-1.

    jnp's eager scatter converts indices to int32 on the x32 default:
    past-2^31 offsets raise OverflowError, and — measurably worse — even
    SMALL-offset writes on a >2^31 dim are silently DROPPED (the clamp
    arithmetic overflows).  Here the huge axis is handled by static
    slicing the target band out, updating inside it (every dim small
    again), and concatenating back; non-basic keys on such arrays get a
    loud error instead of corruption."""
    if max(pval.shape, default=0) <= _INT32_MAX:
        return pval.at[key].set(value)
    norm = _normalize_basic_key(pval, key)
    big = [i for i, d in enumerate(pval.shape) if d > _INT32_MAX]
    if norm is None or len(big) != 1 \
            or any(s != 1 for s in norm[2]):
        raise MXNetError(
            "indexed assignment on an array with a dimension > 2^31-1 "
            "supports only basic, step-1 indexing with one oversized "
            f"dimension (shape {pval.shape}, key {key!r}); jax's int32 "
            "index path would silently corrupt this write — reshape to "
            "dims under 2^31 for advanced indexing")
    return _big_update_fn(pval.shape, big[0], norm)(pval, value)


def _resolve_dtype(dtype):
    if dtype in _DTYPE_ALIASES:
        return _DTYPE_ALIASES[dtype]
    return jnp.dtype(dtype)


def _ctx_of_jax(arr) -> Context:
    try:
        dev = list(arr.devices())[0]
    except Exception:
        return current_context()
    # Context.device_id is a LOCAL (per-process) position, matching
    # Context.jax_device's local_devices indexing — dev.id is a GLOBAL id
    # and the two differ on non-zero workers of a multi-process job.
    # Which devices count as accelerators is context.py's one answer.
    from .. import context as _context

    accel = _context._accelerator_devices()
    if dev in accel:
        return _context.tpu(accel.index(dev))
    if dev.platform != "cpu":   # another process's accelerator
        return _context.tpu(0)
    local = _context._cpu_devices()
    return cpu(next((i for i, d in enumerate(local) if d == dev), 0))


class NDArray:
    """An imperative, device-resident n-dimensional array.

    View semantics (ref: NDArray::Slice/Reshape/At aliasing,
    src/ndarray/ndarray.cc): basic `x[i]`/`x[a:b]`, `x.reshape(...)`,
    `x.slice(...)`, `x.slice_axis(...)` and `x.at(i)` return VIEWS in
    eager mode — writes through a view land in the base array and are
    visible to every overlapping view, like the reference.  Under the
    hood jax arrays are immutable, so a view carries (base, index-spec):
    reads re-derive lazily from the base's version counter, and writes
    rewrite the base functionally (`base.at[key].set`).  Under
    autograd.record these methods return recorded op outputs instead
    (no aliasing) so the tape stays sound."""

    __slots__ = ("_buf", "_ctx", "_ag_grad_req", "_ag_grad", "_ag_node",
                 "_deferred_init", "_base", "_vspec", "_version",
                 "_pversion", "__weakref__")

    # make NDArray win over numpy in mixed operators
    __array_priority__ = 1000.0

    def __init__(self, data, ctx: Optional[Context] = None, dtype=None):
        self._base = None
        self._vspec = None
        self._version = 0
        self._pversion = -1
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array):
            data = jnp.asarray(np.asarray(data), dtype=dtype)
        elif dtype is not None and data.dtype != jnp.dtype(dtype):
            data = data.astype(dtype)
        if ctx is not None and not isinstance(data, jax.core.Tracer):
            # (tracers have no placement — the enclosing trace decides)
            dev = ctx.jax_device
            if (isinstance(data, jax.Array)
                    and not data.is_fully_addressable):
                pass  # global SPMD value: keeps its mesh sharding; the
                #       single-device ctx is advisory only
            elif getattr(data, "devices", None) \
                    and list(data.devices()) != [dev]:
                data = jax.device_put(data, dev)
            elif not isinstance(data, jax.Array):
                data = jax.device_put(data, dev)
        self._buf = data
        self._ctx = ctx or _ctx_of_jax(data)
        self._ag_grad_req = "null"
        self._ag_grad = None
        self._ag_node = None

    # ---- view plumbing ---------------------------------------------------
    @property
    def _data(self):
        """The current jax value; views re-derive from their base when
        the base has changed since the last read."""
        if self._base is not None:
            self._refresh()
        return self._buf

    @_data.setter
    def _data(self, value):
        base = self._base
        if base is None:
            self._buf = value
            self._version += 1
            return
        kind, arg = self._vspec
        pval = base._data  # refreshes the parent chain first
        value = jnp.asarray(value)
        if kind == "index":
            base._data = _update_value(pval, arg,
                                        value.astype(pval.dtype))
        else:  # reshape
            base._data = value.astype(pval.dtype).reshape(pval.shape)
        self._pversion = -1  # force re-derive on next read
        self._refresh()

    def _refresh(self):
        parent = self._base
        pval = parent._data  # recursive: refreshes the whole chain
        if self._pversion == parent._version:
            return
        kind, arg = self._vspec
        self._buf = _index_value(pval, arg) if kind == "index" \
            else pval.reshape(arg)
        self._pversion = parent._version
        self._version += 1

    def _make_view(self, kind: str, arg) -> "NDArray":
        out = NDArray.__new__(NDArray)
        out._base = self
        out._vspec = (kind, arg)
        out._version = 0
        out._pversion = -1
        out._ctx = self._ctx
        out._ag_grad_req = "null"
        out._ag_grad = None
        out._ag_node = None
        pval = self._data
        out._buf = _index_value(pval, arg) if kind == "index" \
            else pval.reshape(arg)
        out._pversion = self._version
        return out

    @property
    def is_view(self) -> bool:
        return self._base is not None

    # ---- core properties -------------------------------------------------
    @property
    def data(self):
        """The underlying jax.Array."""
        return self._data

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return np.dtype(str(self._data.dtype)) if self._data.dtype != jnp.bfloat16 \
            else self._data.dtype

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ctx(self) -> Context:
        return self._ctx

    context = ctx

    @property
    def stype(self) -> str:
        return "default"

    def tostype(self, stype: str) -> "NDArray":
        """Convert storage type (ref: ndarray.py::tostype / cast_storage)."""
        if stype == "default":
            return self
        from .sparse import cast_storage

        return cast_storage(self, stype)

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        dims = "x".join(map(str, self.shape))
        return f"\n{np.asarray(self.asnumpy())}\n<NDArray {dims} @{self._ctx}>"

    def __bool__(self):
        if self.size != 1:
            raise MXNetError("The truth value of an NDArray with multiple "
                             "elements is ambiguous.")
        return bool(self.asnumpy().item())

    # ---- sync points (ref: Engine::WaitForVar / asnumpy) ----------------
    def asnumpy(self) -> np.ndarray:
        d = self._data
        if (isinstance(d, jax.Array) and not d.is_fully_addressable
                and d.sharding.is_fully_replicated):
            # multi-process mesh: a replicated global array cannot be
            # fetched whole, but any local shard IS the global value
            return np.asarray(d.addressable_shards[0].data)
        return np.asarray(jax.device_get(d))

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().item()

    def item(self):
        return self.asscalar()

    def wait_to_read(self):
        jax.block_until_ready(self._data)
        return self

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # ---- conversions / movement ----------------------------------------
    def astype(self, dtype, copy: bool = True) -> "NDArray":
        dt = _resolve_dtype(dtype)
        if not copy and self._data.dtype == dt:
            return self
        return self._op("cast", dtype=str(jnp.dtype(dt)))

    def copy(self) -> "NDArray":
        return NDArray(jnp.copy(self._data), ctx=self._ctx)

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        if isinstance(other, Context):
            return self.as_in_context(other)
        other._data = jax.device_put(self._data, other.ctx.jax_device)
        return other

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self._ctx:
            return self
        return NDArray(jax.device_put(self._data, ctx.jax_device), ctx=ctx)

    as_in_ctx = as_in_context

    def as_nd_ndarray(self):
        return self

    def tolist(self):
        return self.asnumpy().tolist()

    # ---- autograd hooks --------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype=None):
        """ref: ndarray.py::attach_grad — allocate grad & mark as leaf."""
        self._ag_grad_req = grad_req
        self._ag_grad = NDArray(jnp.zeros(self.shape, self._data.dtype),
                                ctx=self._ctx) if grad_req != "null" else None
        self._ag_node = None

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._ag_grad

    @property
    def grad_req(self) -> str:
        return self._ag_grad_req

    def zero_grad(self):
        if self._ag_grad is not None:
            self._ag_grad._data = jnp.zeros_like(self._ag_grad._data)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd

        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def detach(self) -> "NDArray":
        out = NDArray(self._data, ctx=self._ctx)
        return out

    # ---- op plumbing -----------------------------------------------------
    def _op(self, name, *others, **attrs):
        from ..ops.registry import invoke

        return invoke(name, self, *others, **attrs)

    def _rop(self, name, other, **attrs):
        from ..ops.registry import invoke

        return invoke(name, other, self, **attrs)

    @staticmethod
    def _pre(other):
        """Normalise the rhs of a binary op: scalars stay python scalars
        (baked into the jitted executable as weak-typed consts)."""
        if isinstance(other, NDArray):
            return other
        if isinstance(other, numeric_types):
            return other
        return NDArray(other)

    # arithmetic — true-scalar rhs routes to *_scalar ops so the executable
    # cache keys on the scalar value via attrs (matches reference
    # _plus_scalar etc.), keeping shapes static; array-likes are wrapped.
    def _binary(self, scalar_op, bcast_op, o):
        if isinstance(o, numeric_types):
            return self._op(scalar_op, scalar=o)
        return self._op(bcast_op, NDArray._pre(o))

    def __add__(self, o):
        return self._binary("_plus_scalar", "broadcast_add", o)

    def __radd__(self, o):
        return self.__add__(o)

    def __sub__(self, o):
        return self._binary("_minus_scalar", "broadcast_sub", o)

    def __rsub__(self, o):
        if isinstance(o, numeric_types):
            return self._op("_rminus_scalar", scalar=o)
        return NDArray._pre(o)._binary("_minus_scalar", "broadcast_sub", self)

    def __mul__(self, o):
        return self._binary("_mul_scalar", "broadcast_mul", o)

    def __rmul__(self, o):
        return self.__mul__(o)

    def __truediv__(self, o):
        return self._binary("_div_scalar", "broadcast_div", o)

    def __rtruediv__(self, o):
        if isinstance(o, numeric_types):
            return self._op("_rdiv_scalar", scalar=o)
        return NDArray._pre(o)._binary("_div_scalar", "broadcast_div", self)

    def __mod__(self, o):
        return self._binary("_mod_scalar", "broadcast_mod", o)

    def __pow__(self, o):
        return self._binary("_power_scalar", "broadcast_power", o)

    def __rpow__(self, o):
        if isinstance(o, numeric_types):
            return self._op("_rpower_scalar", scalar=o)
        return NDArray._pre(o)._binary("_power_scalar", "broadcast_power", self)

    def __neg__(self):
        return self._op("negative")

    def __abs__(self):
        return self._op("abs")

    def __matmul__(self, o):
        return self._op("matmul", NDArray._pre(o))

    def _inplace(self, r: "NDArray") -> "NDArray":
        # carry the tape node so gradients flow through in-place updates
        self._data = r._data
        self._ag_node = r._ag_node
        return self

    def __iadd__(self, o):
        return self._inplace(self + o)

    def __isub__(self, o):
        return self._inplace(self - o)

    def __imul__(self, o):
        return self._inplace(self * o)

    def __itruediv__(self, o):
        return self._inplace(self / o)

    # comparisons
    def __eq__(self, o):
        if o is None:
            return False
        return self._binary("_equal_scalar", "broadcast_equal", o)

    def __ne__(self, o):
        if o is None:
            return True
        return self._binary("_not_equal_scalar", "broadcast_not_equal", o)

    def __gt__(self, o):
        return self._binary("_greater_scalar", "broadcast_greater", o)

    def __ge__(self, o):
        return self._binary("_greater_equal_scalar", "broadcast_greater_equal", o)

    def __lt__(self, o):
        return self._binary("_lesser_scalar", "broadcast_lesser", o)

    def __le__(self, o):
        return self._binary("_lesser_equal_scalar", "broadcast_lesser_equal", o)

    __hash__ = object.__hash__

    @staticmethod
    def _eager_views() -> bool:
        """Views only outside autograd recording (the tape needs real op
        nodes for gradient flow; ref: autograd + view interaction)."""
        from ..autograd import is_recording

        return not is_recording()

    # ---- shape ops -------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        shape = tuple(shape)
        concrete = self._concrete_shape(shape)
        if concrete is not None and self._eager_views():
            return self._make_view("reshape", concrete)
        return self._op("reshape", shape=shape)

    def _concrete_shape(self, shape):
        """Resolve every reference reshape code — 0 (copy dim), -1
        (infer), -2 (copy rest), -3 (merge two), -4 (split) — against
        the current shape, so aliasing does not depend on how the shape
        is spelled.  None when unresolvable (falls to the op path)."""
        cur = list(self.shape)
        shape = list(shape)
        out = []
        si = k = 0
        try:
            while k < len(shape):
                s = shape[k]
                if not isinstance(s, (int, np.integer)):
                    return None
                s = int(s)
                if s == 0:
                    out.append(cur[si]); si += 1
                elif s == -2:
                    out.extend(cur[si:]); si = len(cur)
                elif s == -3:
                    out.append(cur[si] * cur[si + 1]); si += 2
                elif s == -4:
                    a, b = int(shape[k + 1]), int(shape[k + 2])
                    if a == -1:
                        a = cur[si] // b
                    if b == -1:
                        b = cur[si] // a
                    out.extend([a, b]); si += 1; k += 2
                elif s < -4:
                    return None
                else:
                    out.append(s)
                    if s != -1:
                        si += 1
                k += 1
        except (IndexError, ZeroDivisionError):
            return None
        total = 1
        for d in cur:
            total *= d
        if out.count(-1) == 1:
            known = 1
            for d in out:
                if d != -1:
                    known *= d
            if known == 0 or total % known:
                return None
            out[out.index(-1)] = total // known
        elif -1 in out:
            return None
        prod = 1
        for d in out:
            prod *= d
        return tuple(out) if prod == total else None

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return self._op("transpose", axes=tuple(axes) if axes else None)

    @property
    def T(self):
        return self.transpose()

    def flatten(self):
        return self._op("flatten")

    def expand_dims(self, axis):
        return self._op("expand_dims", axis=axis)

    def squeeze(self, axis=None):
        return self._op("squeeze", axis=axis)

    def broadcast_to(self, shape):
        return self._op("broadcast_to", shape=tuple(shape))

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def swapaxes(self, a1, a2):
        return self._op("swapaxes", dim1=a1, dim2=a2)

    def split(self, num_outputs, axis=0):
        from ..ops.registry import invoke

        return invoke("split", self, num_outputs=num_outputs, axis=axis)

    def tile(self, reps):
        return self._op("tile", reps=tuple(reps) if isinstance(reps, (list, tuple)) else (reps,))

    def repeat(self, repeats, axis=None):
        return self._op("repeat", repeats=repeats, axis=axis)

    def pad(self, mode="constant", pad_width=None, constant_value=0):
        return self._op("pad", mode=mode, pad_width=tuple(pad_width),
                        constant_value=constant_value)

    def slice(self, begin, end, step=None):
        if self._eager_views():
            key = tuple(slice(b, e, s) for b, e, s in
                        zip(begin, end, step or (None,) * len(begin)))
            return self._make_view("index", key)
        return self._op("slice", begin=tuple(begin), end=tuple(end),
                        step=tuple(step) if step else None)

    def slice_axis(self, axis, begin, end):
        if self._eager_views():
            ax = axis + self.ndim if axis < 0 else axis
            key = tuple(slice(None) for _ in range(ax)) + \
                (slice(begin, end),)
            return self._make_view("index", key)
        return self._op("slice_axis", axis=axis, begin=begin, end=end)

    def at(self, idx: int):
        """View of row `idx` sharing storage (ref: NDArray::At); a
        tape-backed copy under autograd.record, like the other views."""
        if self._eager_views():
            return self._make_view("index", int(idx))
        row = self._op("slice_axis", axis=0, begin=int(idx),
                       end=int(idx) + 1)
        return row.reshape(self.shape[1:])

    def take(self, indices, axis=0, mode="clip"):
        return self._op("take", NDArray._pre(indices), axis=axis, mode=mode)

    def pick(self, index, axis=-1, keepdims=False):
        return self._op("pick", NDArray._pre(index), axis=axis, keepdims=keepdims)

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        return self._op("one_hot", depth=depth, on_value=on_value,
                        off_value=off_value)

    # ---- reductions ------------------------------------------------------
    def sum(self, axis=None, keepdims=False):
        return self._op("sum", axis=_norm_axis(axis), keepdims=keepdims)

    def mean(self, axis=None, keepdims=False):
        return self._op("mean", axis=_norm_axis(axis), keepdims=keepdims)

    def max(self, axis=None, keepdims=False):
        return self._op("max", axis=_norm_axis(axis), keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        return self._op("min", axis=_norm_axis(axis), keepdims=keepdims)

    def prod(self, axis=None, keepdims=False):
        return self._op("prod", axis=_norm_axis(axis), keepdims=keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        return self._op("norm", ord=ord, axis=_norm_axis(axis), keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        return self._op("argmax", axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        return self._op("argmin", axis=axis, keepdims=keepdims)

    # elementwise conveniences
    def exp(self):
        return self._op("exp")

    def log(self):
        return self._op("log")

    def sqrt(self):
        return self._op("sqrt")

    def square(self):
        return self._op("square")

    def relu(self):
        return self._op("relu")

    def sigmoid(self):
        return self._op("sigmoid")

    def tanh(self):
        return self._op("tanh")

    def softmax(self, axis=-1):
        return self._op("softmax", axis=axis)

    def log_softmax(self, axis=-1):
        return self._op("log_softmax", axis=axis)

    def clip(self, a_min, a_max):
        return self._op("clip", a_min=a_min, a_max=a_max)

    def abs(self):
        return self._op("abs")

    def round(self):
        return self._op("round")

    def sign(self):
        return self._op("sign")

    def floor(self):
        return self._op("floor")

    def ceil(self):
        return self._op("ceil")

    def zeros_like(self):
        return self._op("zeros_like")

    def ones_like(self):
        return self._op("ones_like")

    def sort(self, axis=-1, is_ascend=True):
        return self._op("sort", axis=axis, is_ascend=is_ascend)

    def argsort(self, axis=-1, is_ascend=True, dtype="float32"):
        return self._op("argsort", axis=axis, is_ascend=is_ascend,
                        dtype=dtype)

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False,
             dtype="float32"):
        return self._op("topk", axis=axis, k=k, ret_typ=ret_typ,
                        is_ascend=is_ascend, dtype=dtype)

    def slice_like(self, shape_like, axes=()):
        return self._op("slice_like", NDArray._pre(shape_like),
                        axes=tuple(axes))

    def dot(self, other, transpose_a=False, transpose_b=False):
        return self._op("dot", NDArray._pre(other), transpose_a=transpose_a,
                        transpose_b=transpose_b)

    @staticmethod
    def _is_basic_key(key) -> bool:
        # np.integer counts: x[np.argmax(...)] must alias exactly like
        # x[int(...)] — the index dtype must not flip the contract
        if isinstance(key, (int, np.integer, slice)) or key is Ellipsis:
            return True
        if isinstance(key, tuple):
            return all(isinstance(k, (int, np.integer, slice))
                       or k is Ellipsis for k in key)
        return False

    # ---- indexing --------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key.data
        if self._is_basic_key(key) and self._eager_views():
            # basic indexing aliases the base (ref: NDArray::Slice/At)
            return self._make_view("index", key)
        out = _index_value(self._data, key)
        return NDArray(out, ctx=self._ctx)

    def __setitem__(self, key, value):
        """Sliced assignment — functional under the hood (x.at[key].set)."""
        if isinstance(key, NDArray):
            key = key.data
        if isinstance(value, NDArray):
            value = value.data
        if key is Ellipsis or (isinstance(key, slice) and key == slice(None)):
            v = jnp.broadcast_to(jnp.asarray(value, self._data.dtype), self.shape)
            self._data = jax.device_put(v, self._ctx.jax_device)
        else:
            self._data = _update_value(
                self._data, key, jnp.asarray(value, self._data.dtype))

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


def _norm_axis(axis):
    if axis is None:
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(axis)
    return int(axis)


def wrap_outputs(out, ctx: Optional[Context]):
    """Wrap a pure-fn result (array or tuple/list of arrays) into NDArray(s)."""
    if isinstance(out, (tuple, list)):
        return [NDArray(o, ctx=ctx) for o in out]
    return NDArray(out, ctx=ctx)


def from_jax(arr, ctx: Optional[Context] = None) -> NDArray:
    return NDArray(arr, ctx=ctx)


# ---- creation functions (ref: ndarray creation API) ----------------------

def _creation_ctx(ctx):
    return ctx if ctx is not None else current_context()


def array(source, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    if isinstance(source, NDArray):
        out = source.astype(dtype) if dtype else source.copy()
        return out.as_in_context(ctx) if ctx is not None else out
    src = np.asarray(source)
    if dtype is None:
        # TPU-native narrowing defaults: f64->f32, i64->i32 (no x64 mode)
        if src.dtype == np.float64:
            dtype = jnp.float32
        elif src.dtype == np.int64:
            dtype = jnp.int32
        else:
            dtype = src.dtype
    ctx = _creation_ctx(ctx)
    return NDArray(jax.device_put(jnp.asarray(src, dtype=dtype), ctx.jax_device), ctx=ctx)


def zeros(shape, ctx=None, dtype=None) -> NDArray:
    ctx = _creation_ctx(ctx)
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(jax.device_put(jnp.zeros(shape, _resolve_dtype(dtype)),
                                  ctx.jax_device), ctx=ctx)


def ones(shape, ctx=None, dtype=None) -> NDArray:
    ctx = _creation_ctx(ctx)
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(jax.device_put(jnp.ones(shape, _resolve_dtype(dtype)),
                                  ctx.jax_device), ctx=ctx)


def full(shape, val, ctx=None, dtype=None) -> NDArray:
    ctx = _creation_ctx(ctx)
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(jax.device_put(jnp.full(shape, val, _resolve_dtype(dtype)),
                                  ctx.jax_device), ctx=ctx)


def empty(shape, ctx=None, dtype=None) -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None) -> NDArray:
    ctx = _creation_ctx(ctx)
    out = jnp.arange(start, stop, step, _resolve_dtype(dtype))
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return NDArray(jax.device_put(out, ctx.jax_device), ctx=ctx)


def concatenate(arrays: Sequence[NDArray], axis: int = 0) -> NDArray:
    from ..ops.registry import invoke

    return invoke("concat", *arrays, dim=axis)


def stack(*arrays, axis: int = 0) -> NDArray:
    from ..ops.registry import invoke

    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return invoke("stack", *arrays, axis=axis)
