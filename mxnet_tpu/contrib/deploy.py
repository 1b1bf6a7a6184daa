"""Portable model export via StableHLO — the TPU-native deployment path.

Counterpart of the reference's deploy story (ref: save -symbol.json +
.params, reload in the C++ predictor / another language via the C API,
docs/faq/smart_device.md "deploy without Python").  On this stack the
compiler IR *is* the portable artifact: `export_model` traces the
block's eval-mode forward once and serializes it as versioned StableHLO
(jax.export), which any later jax release — or any StableHLO-speaking
runtime — can execute WITHOUT the model's Python class.  Weights ride
alongside in the standard reference `.params` byte format
(serialization.py), so they stay interchangeable with every other tool
in this framework.

The traced program is CachedOp's pure eval-mode function (the same
functionalization hybridize() compiles), with the PRNG key as a real
argument — stochastic eval-mode layers draw from the key you serve
with instead of replaying a baked-in constant.

Artifact layout (a directory):
    model.stablehlo   versioned StableHLO bytes (jax.export.serialize)
    model.params      the block's parameters, reference .params format
    meta.json         input shapes/dtypes + param order + output arity

    from mxnet_tpu.contrib import deploy
    deploy.export_model(net, "deploy_dir", [nd.zeros((1, 3, 224, 224))])
    ...
    served = deploy.import_model("deploy_dir")   # no model code needed
    y = served(x_nd)                             # NDArray in/out
"""
from __future__ import annotations

import json
import threading
import os
from typing import List, Sequence

from ..base import MXNetError
from ..context import current_context
from ..ndarray.ndarray import NDArray

__all__ = ["export_model", "import_model", "ServedModel"]


# mxsan: lock-free first read (double-checked); writes hold _NT_LOCK
from ..analysis import sanitizer as _mxsan

_NT_CACHE: dict = _mxsan.track({}, "contrib.deploy._NT_CACHE",
                               reads="unlocked-ok")
_NT_LOCK = threading.Lock()


def _namedtuple_cls(name: str, fields: tuple):
    """One reconstructed namedtuple class per (name, fields) — field
    access by name survives the artifact round-trip even though the
    original class is gone.  Locked: concurrent serving requests hit
    this on a cold model, and `isinstance`/identity checks downstream
    require ONE class per key (mxlint MX004)."""
    key = (name, fields)
    cls = _NT_CACHE.get(key)
    if cls is None:
        with _NT_LOCK:
            cls = _NT_CACHE.get(key)
            if cls is None:
                import collections

                cls = collections.namedtuple(name, fields)
                _NT_CACHE[key] = cls
    return cls


def _encode_tree(t):
    """Output-pytree template -> JSON (leaves are flat indices).
    Returns None for exotic pytree nodes — serving then falls back to
    the flat list."""
    if isinstance(t, dict):
        items = {k: _encode_tree(v) for k, v in t.items()}
        if any(v is None for v in items.values()):
            return None
        return {"kind": "dict", "items": items}
    if isinstance(t, tuple) and hasattr(t, "_fields"):
        # namedtuple: a plain-tuple encoding would silently break field
        # access by name on the serving side (ADVICE round 5)
        items = [_encode_tree(v) for v in t]
        if any(v is None for v in items):
            return None
        return {"kind": "namedtuple", "name": type(t).__name__,
                "fields": list(t._fields), "items": items}
    if isinstance(t, (tuple, list)):
        items = [_encode_tree(v) for v in t]
        if any(v is None for v in items):
            return None
        return {"kind": "tuple" if isinstance(t, tuple) else "list",
                "items": items}
    if isinstance(t, int):
        return {"kind": "leaf", "index": t}
    return None


def _decode_tree(t, leaves):
    if t["kind"] == "leaf":
        return leaves[t["index"]]
    if t["kind"] == "dict":
        return {k: _decode_tree(v, leaves) for k, v in t["items"].items()}
    items = [_decode_tree(v, leaves) for v in t["items"]]
    if t["kind"] == "namedtuple":
        cls = _namedtuple_cls(t.get("name", "ServedOutputs"),
                              tuple(t["fields"]))
        return cls(*items)
    return tuple(items) if t["kind"] == "tuple" else items


def export_model(block, path: str, example_inputs: Sequence,
                 dynamic_batch: bool = False,
                 platforms: Sequence[str] = ("cpu", "tpu")) -> str:
    """Trace `block` (initialized; deferred shapes are resolved with
    one eager pass on `example_inputs` if needed) and write the
    portable artifact directory.  Returns `path`.

    dynamic_batch=True exports dim 0 of every input as ONE shared
    symbolic size (jax.export shape polymorphism): the served model
    then accepts any batch, the serving analogue of BucketingModule
    without the buckets.  Models whose forward needs a concrete batch
    (reshape to literal sizes, batch-dependent control flow) must keep
    the default fixed-shape export — the tracer raises loudly."""
    import jax
    import jax.numpy as jnp

    from jax import export as jexport

    from .. import autograd
    from ..gluon.block import CachedOp
    from ..gluon.parameter import DeferredInitializationError

    xs = [x.data if isinstance(x, NDArray) else jnp.asarray(x)
          for x in example_inputs]
    op = CachedOp(block)
    plist = op._param_list()
    if not plist:
        raise MXNetError("export_model: block has no parameters; "
                         "initialize it first")
    try:
        pvals = tuple(p.data().data for _, p in plist)
    except DeferredInitializationError:
        # we hold exactly the inputs needed to resolve deferred shapes
        # (the CachedOp.__call__ resolve-and-retry pattern, including
        # its _active guard — without it a hybridized block would
        # jit-compile a throwaway program just to resolve shapes)
        was_active = getattr(block, "_active", False)
        block._active = False
        try:
            with autograd.pause():
                block(*[NDArray(x) for x in xs])
        finally:
            block._active = was_active
        op._pstruct = None
        plist = op._param_list()
        pvals = tuple(p.data().data for _, p in plist)

    pure = op._make_pure(train=False)

    def serve_fn(params, key, *inputs):
        flat, _aux = pure(params, inputs, key)
        return flat

    # default: lowered for BOTH backends, so an artifact exported on a
    # CPU dev box serves on the TPU host (and vice versa) — jax.export
    # pins the lowering platform otherwise.  Pass platforms=("tpu",)
    # to skip the dual lowering when exporting and serving on one
    # backend.
    platforms = list(platforms)
    known = {"cpu", "tpu", "cuda", "rocm"}
    bad = [p for p in platforms if p not in known]
    if bad:
        # jax.export accepts arbitrary platform strings silently (the
        # runtime just never selects them) — a typo would produce an
        # artifact that can never serve anywhere it claims to
        raise MXNetError(f"unknown platform(s) {bad}; known: "
                         f"{sorted(known)}")
    structs = tuple(jax.ShapeDtypeStruct(v.shape, v.dtype) for v in pvals)
    key_struct = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if dynamic_batch:
        # 0-d side-inputs (scalars) have no batch dimension to free —
        # they stay concrete rather than being fabricated into (b,)
        # vectors (which would surface as a misleading broadcast error)
        (b,) = jexport.symbolic_shape("b")
        in_structs = tuple(
            jax.ShapeDtypeStruct((b,) + tuple(x.shape[1:]), x.dtype)
            if x.ndim >= 1 else jax.ShapeDtypeStruct((), x.dtype)
            for x in xs)
    else:
        in_structs = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                           for x in xs)
    try:
        exp = jexport.export(jax.jit(serve_fn), platforms=platforms)(
            structs, key_struct, *in_structs)
    except Exception as e:
        # only a platform-SPECIFIC-KERNEL lowering failure (Pallas /
        # Mosaic) warrants the single-backend retry, and only onto a
        # backend the caller actually requested; everything else
        # re-raises untouched — a generic "platform" substring match
        # would swallow argument errors (a typo'd platform name) and
        # misattribute unrelated failures while doubling time-to-error
        msg = str(e).lower()
        backend = jax.default_backend()
        if len(platforms) <= 1 or backend not in platforms \
                or not any(s in msg for s in ("pallas", "mosaic")):
            raise
        import warnings

        platforms = [backend]
        warnings.warn(
            f"export_model: multi-platform lowering failed on a "
            f"platform-specific kernel ({type(e).__name__}); the "
            f"artifact is pinned to {backend!r} and will NOT serve on "
            f"other backends. "
            f"Cause: {str(e).splitlines()[0][:150]}", UserWarning,
            stacklevel=2)
        exp = jexport.export(jax.jit(serve_fn))(structs, key_struct,
                                                *in_structs)
    blob = exp.serialize()

    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "model.stablehlo"), "wb") as f:
        f.write(blob)
    from ..serialization import save_ndarrays as nd_save

    nd_save(os.path.join(path, "model.params"),
            {name: p.data() for name, p in plist})
    meta = {
        "format": "mxnet_tpu.deploy/1",
        # the serializer's era: jax.export guarantees a bounded
        # backward-compat window, so a failed deserialize years later
        # must be distinguishable from a corrupted artifact
        "jax_version": jax.__version__,
        "param_order": [name for name, _ in plist],
        "param_shapes": {name: list(p.data().shape) for name, p in plist},
        "param_dtypes": {name: str(p.data().dtype) for name, p in plist},
        "inputs": [{"shape": ([None] + list(x.shape[1:]))
                    if dynamic_batch and x.ndim >= 1
                    else list(x.shape), "dtype": str(x.dtype)}
                   for x in xs],
        "dynamic_batch": bool(dynamic_batch),
        "platforms": list(platforms),
        "n_outputs": len(exp.out_avals),
        # output avals, so serving can decide coalescability (is every
        # output batch-major?) WITHOUT deserializing the StableHLO —
        # symbolic dims serialize as their expression string ("b");
        # older artifacts lack this key and fall back to the exported
        # program's out_avals
        "outputs": [{"shape": [d if isinstance(d, int) else str(d)
                               for d in aval.shape],
                     "dtype": str(aval.dtype)}
                    for aval in exp.out_avals],
        # the model's output pytree (dict/tuple nesting), JSON-encoded,
        # so serving returns the same structure the block documents —
        # not a flat list in tree-flatten order
        "out_tree": _encode_tree(
            jax.tree_util.tree_unflatten(
                op._out_treedef[False],
                list(range(op._out_treedef[False].num_leaves)))),
    }
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1)
    return path


class ServedModel:
    """A reloaded artifact: callable NDArray-in/NDArray-out.

    `params` may be swapped wholesale (same names/shapes/dtypes) with
    `set_params`, e.g. after further training — the compiled program is
    weight-agnostic because parameters are arguments, not constants.
    Stochastic eval-mode layers draw from the per-call `seed`."""

    def __init__(self, exported, params: dict, meta: dict):
        # `exported` may be the deserialized jax.export.Exported OR a
        # zero-arg loader returning one.  import_model passes a loader:
        # deserializing StableHLO is the dominant import cost, and a
        # warm serving process (persistent compile cache hit) never
        # needs the program at all — only its params and meta.
        if callable(exported) and not hasattr(exported, "call"):
            self._exported = None
            self._exported_loader = exported
        else:
            self._exported = exported
            self._exported_loader = None
        self._exported_lock = threading.Lock()
        self._meta = meta
        self._order: List[str] = meta["param_order"]
        self.set_params(params)

    @property
    def meta(self) -> dict:
        """The artifact's meta.json (read-only view for serving)."""
        return dict(self._meta)

    @property
    def exported(self):
        """The deserialized jax.export.Exported program — the serving
        layer AOT-compiles per-bucket executables from it instead of
        paying a re-trace on every `exported.call`.  Deserialized on
        first touch when the artifact was imported lazily."""
        if self._exported is None:
            with self._exported_lock:
                if self._exported is None:
                    self._exported = self._exported_loader()
        return self._exported

    @property
    def program_loaded(self) -> bool:
        """Whether the StableHLO program has been deserialized (False
        on a warm process that served everything from the compile
        cache — the laziness the warm-start bench measures)."""
        return self._exported is not None

    @property
    def param_values(self) -> tuple:
        """Current parameter leaves in export order (device arrays)."""
        return self._pvals

    def decode_outputs(self, leaves):
        """Rebuild the block's documented output structure from flat
        leaves (tree-flatten order) — shared with mxnet_tpu.serving."""
        tree = self._meta.get("out_tree")
        if tree is not None:
            return _decode_tree(tree, leaves)
        return leaves[0] if len(leaves) == 1 else leaves

    def set_params(self, params: dict) -> None:
        """Validated atomically: a bad set leaves the old weights.

        The weights are placed on the current context's device, and the
        model serves there: a weights file loads onto cpu() (nd.load's
        contract), while on a TPU host the default context, the inputs
        and the serving executables are on tpu(0)."""
        import jax

        missing = [n for n in self._order if n not in params]
        if missing:
            raise MXNetError(f"artifact params missing {missing[:5]}")
        ctx = current_context()
        dev = ctx.jax_device
        new = []
        for n in self._order:
            v = params[n].data if isinstance(params[n], NDArray) \
                else params[n]
            want_s = self._meta.get("param_shapes", {}).get(n)
            want_d = self._meta.get("param_dtypes", {}).get(n)
            if want_s is not None and list(v.shape) != want_s:
                raise MXNetError(
                    f"param {n}: shape {list(v.shape)} != exported "
                    f"{want_s}")
            if want_d is not None and str(v.dtype) != want_d:
                raise MXNetError(
                    f"param {n}: dtype {v.dtype} != exported {want_d}")
            new.append(jax.device_put(v, dev))
        self._pvals = tuple(new)
        self._ctx = ctx

    def __call__(self, *inputs, seed: int = 0):
        import jax
        import jax.numpy as jnp

        want = self._meta["inputs"]
        if len(inputs) != len(want):
            raise MXNetError(
                f"artifact takes {len(want)} inputs, got {len(inputs)}")
        ctx = self._ctx
        dev = ctx.jax_device
        xs = []
        for x, w in zip(inputs, want):
            v = x.data if isinstance(x, NDArray) else jnp.asarray(x)
            got_s, want_s = list(v.shape), w["shape"]
            fixed_ok = (len(got_s) == len(want_s)
                        and all(ws is None or gs == ws
                                for gs, ws in zip(got_s, want_s)))
            if not fixed_ok:
                raise MXNetError(
                    f"input shape {got_s} != exported {want_s} "
                    "(None = free batch dim; other dims are fixed-shape "
                    "in a StableHLO artifact)")
            if str(v.dtype) != w["dtype"]:
                raise MXNetError(
                    f"input dtype {v.dtype} != exported {w['dtype']}")
            xs.append(jax.device_put(v, dev))
        if self._meta.get("dynamic_batch"):
            sizes = {x.shape[0] for x in xs if x.ndim >= 1}
            if len(sizes) > 1:
                raise MXNetError(
                    f"dynamic-batch artifact: all inputs must share one "
                    f"batch size, got {sorted(sizes)}")
        key = jax.device_put(jax.random.PRNGKey(seed), dev)
        outs = self.exported.call(self._pvals, key, *xs)
        nds = [NDArray(o, ctx=ctx) for o in outs]
        # the structure the block's forward documents (dict/tuple/
        # namedtuple nesting), not a flat list in tree-flatten order
        return self.decode_outputs(nds)


def import_model(path: str) -> ServedModel:
    """Reload an artifact directory — no model code, no block class.

    The StableHLO program deserializes LAZILY (on first `.exported`
    touch): meta + params are enough to answer requests on a process
    whose executables come out of the persistent compile cache, and
    deserialization is the dominant import cost.  Import still verifies
    the program file exists and is non-empty (a missing/zero-byte
    artifact fails HERE); a deeper corruption (truncated serialization)
    surfaces on the first `.exported` touch — the same failure point a
    bad weights file has always had."""
    from ..serialization import load_ndarrays as nd_load

    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    if meta.get("format") != "mxnet_tpu.deploy/1":
        raise MXNetError(f"not a deploy artifact: {path}")
    program = os.path.join(path, "model.stablehlo")
    try:
        if os.path.getsize(program) == 0:
            raise MXNetError(
                f"artifact {path}: model.stablehlo is empty (torn "
                f"write?)")
    except OSError:
        raise MXNetError(f"artifact {path} has no model.stablehlo")

    def _load():
        from jax import export as jexport

        with open(program, "rb") as f:
            return jexport.deserialize(f.read())

    params = nd_load(os.path.join(path, "model.params"))
    return ServedModel(_load, params, meta)
