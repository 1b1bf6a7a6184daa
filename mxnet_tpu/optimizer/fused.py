"""Fused optimizer step: the whole parameter pytree in ONE dispatch.

The eager Trainer loop issues one registered update op per parameter per
replica — ~N kernel launches per step while the device idles between
them.  ``FusedUpdater`` applies the SAME pure update math
(``Optimizer.fused_apply``, backed by the registered optimizer_ops) over
every parameter in a single ``jax.jit`` program, AOT-compiled once per
(optimizer class, static hyperparams, tree structure, shapes/dtypes,
device) and cached process-wide.  This is the weight-update fusion of
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arXiv:2004.13336) adapted to the eager frontend.

Two properties carry the perf claim:

  * **Donation** — weights and states are donated to the executable
    (``donate_argnums``) on accelerator backends, so the update is a
    true in-place buffer reuse: zero copies, zero transient HBM.
    (Skipped on CPU, where PjRt does not implement donation and would
    warn on every compile.)
  * **No retrace on schedule changes** — lr / wd / rescale_grad / the
    bias-correction step count enter as TRACED scalar arguments
    (``Optimizer.fused_hyper``), so ``set_learning_rate`` and the
    per-step ``rescale_grad = scale/batch_size`` reuse the cached
    executable.  AOT compilation makes this a hard guarantee: a
    signature change cannot silently retrace — it builds (and counts) a
    new executable.

``FusedUpdater`` extends the serializable ``Updater``: states live in
the same ``{index: NDArray-tree}`` dict, ``get_states``/``set_states``
produce the identical payload, and the inherited per-parameter
``__call__`` remains the transparent fallback for steps the fused path
cannot take (e.g. a sparse gradient showing up mid-run).
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import itertools

from ..analysis import sanitizer as _mxsan
from ..ndarray.ndarray import NDArray
from ..telemetry import instruments as _ins
from ..telemetry import mxhealth as _mxhealth
from ..telemetry import tracing as _tracing
from ..telemetry.mxprof import costs as _costs
from ..util import env as _env
from .. import compile_cache as _cc
from ..compile_cache import audit as _ir_audit
from ..compile_cache import jax_cache as _jax_cache
from .optimizer import Optimizer, Updater

__all__ = ["FusedUpdater", "FusedUnsupported", "ExecutableCache",
           "apply_master", "apply_param", "compile_stats"]


class FusedUnsupported(Exception):
    """This parameter set cannot take the fused path exactly (raised
    BEFORE any state mutation) — the caller runs the eager loop."""


_TICKS = itertools.count(1)


class _Entry:
    """One cached executable.  ``tick`` is LRU recency — refreshed by
    an attribute write on the hot path (no lock, no dict mutation; the
    eviction scan under the cache lock reads it).  ``cost`` is the
    executable's static cost analysis (mxprof MFU accounting), captured
    once at insert time for fresh builds AND persistent-cache loads
    alike — a warm restart keeps its cost metadata.  ``fingerprint``
    is the HLO-module identity riding beside it (mxtriage regression
    attribution: "did the compiled program change").  ``origin`` is
    "compiled" where XLA built it in this process and "cache" where it
    was loaded (the ``.mxcc`` store or JAX's persistent cache).
    ``program`` is the executable's scope table, built on the first
    call of ``parallel.spmd.step_programs()`` and never before;
    ``param_uses`` what the trace that built it counted (the SPMD step's
    ``{reads: Parameters read that often}``), None where nothing was
    traced."""

    __slots__ = ("fn", "tick", "cost", "fingerprint", "origin", "program",
                 "param_uses")

    def __init__(self, fn, cost=None, fingerprint=None, origin="compiled"):
        self.fn = fn
        self.tick = next(_TICKS)
        self.cost = cost
        self.fingerprint = fingerprint
        self.origin = origin
        self.program = None
        self.param_uses = None


class ProgramBuild:
    """One program's road from its jitted function to an executable, each
    stage run once and under its set-up phase (`telemetry.tracing.phase`):

        mx.build.trace    `build_traced()`: `jitted.trace(*args)`, the
                          Python of the whole model, Pallas kernels too
        mx.build.lower    `.lower()`: the jaxpr to StableHLO
        mx.build.backend  `.compile()`: XLA's compile, or JAX's load from
                          its persistent cache (`origin`: "compiled" |
                          "cache", by a rise in `jax_cache.counts()`)
        mx.build.audit    the mxir audit, only where MXNET_IR_AUDIT is on

    every record with `site` and `program` (the traced function's name)
    in its stats.  `records` are the one stopwatch of a build:
    `seconds` is their sum and `start` the first one's start, which is
    what `ExecutableCache.stats()`, the compile histogram and the
    `*-compile` chrome span are fed from."""

    def __init__(self, build_traced, site: str):
        self._build_traced = build_traced
        self.site = site
        self.program = None
        self.records: List[dict] = []
        self.loaded = False     # JAX served the executable from its cache
        self._lowered = None
        self.rendered_text = None   # the module's text, once asked for

    def lowered(self):
        if self._lowered is None:
            with _tracing.phase("mx.build.trace", site=self.site) as rec:
                traced = self._build_traced()
                self.program = rec["stats"]["program"] = traced.fun_name
            self.records.append(rec)
            with _tracing.phase("mx.build.lower", site=self.site,
                                program=self.program) as rec:
                self._lowered = traced.lower()
            self.records.append(rec)
        return self._lowered

    def text(self) -> str:
        """The lowered module's text, rendered once (the `.mxcc` key and
        the audit read it; no build phase of its own)."""
        if self.rendered_text is None:
            self.rendered_text = self.lowered().as_text()
        return self.rendered_text

    def compile(self):
        lowered = self.lowered()
        hits = _jax_cache.counts()["hits"]
        with _tracing.phase("mx.build.backend", site=self.site,
                            program=self.program) as rec:
            compiled = lowered.compile()
            self.loaded = _jax_cache.counts()["hits"] > hits
            rec["stats"]["origin"] = "cache" if self.loaded else "compiled"
        self.records.append(rec)
        return compiled

    def audit(self, donate: bool) -> None:
        """mxir program audit (MXNET_IR_AUDIT=1): one boolean check when
        off; when on, reuses the memoized `text()` render."""
        if not _ir_audit.enabled():
            return
        with _tracing.phase("mx.build.audit", site=self.site,
                            program=self.program) as rec:
            _ir_audit.maybe_audit(self.site, self.text,
                                  expect_donation=donate)
        self.records.append(rec)

    @property
    def seconds(self) -> float:
        return sum(r["seconds"] for r in self.records)

    @property
    def start(self) -> float:
        """`perf_counter` at the first stage's start."""
        return _tracing._T0 + self.records[0]["start"]

    def stage_seconds(self, stage: str) -> float:
        return sum(r["seconds"] for r in self.records
                   if r["name"] == "mx.build." + stage)


class ExecutableCache:
    """Bounded in-process executable cache + compile accounting for one
    optimizer-step site, shared by the per-replica fused path (site
    ``optimizer.fused_step``) and the mesh-wide SPMD path
    (``optimizer.spmd_step``, optimizer/spmd.py).

    mxsan: lock-free reads are the design (callers probe before
    compiling); writes stay under ``lock`` — the sanitizer checks the
    write half at runtime.  Values are _Entry cells (executable + LRU
    tick); the cache is BOUNDED by MXNET_FUSED_CACHE_MAX — a long-lived
    trainer process cycling through tree structures (eval loops,
    growing models) must not hold every executable it ever built.

    The persistent tier (PR 7) is consulted when enabled: the ALIAS key
    is the cheap in-process ``sig`` (no tracing) for first-party
    optimizers only — the framework version in the key fingerprint pins
    THEIR math, but a user's Optimizer subclass can change without it,
    so those always key by the lowered program text."""

    def __init__(self, site: str, track_name: str, evict_store: str,
                 span_name: str, metric):
        self.site = site
        self.data: Dict[Tuple, _Entry] = _mxsan.track(
            {}, track_name, reads="unlocked-ok")
        self.lock = threading.Lock()
        self._evict_store = evict_store
        self._span_name = span_name
        self._metric = metric  # () -> histogram child, lazily resolved
        self.compiles = 0
        # seconds of the builds counted in `compiles`, by stage
        # (`audit` only where MXNET_IR_AUDIT is on); their sum is
        # stats()["seconds_total"]
        self.stage_seconds = dict.fromkeys(
            ("trace", "lower", "backend", "audit"), 0.0)
        self.cache_loads = 0
        self.evictions = 0

    def lookup(self, sig):
        """Lock-free hit path; refreshes LRU recency."""
        ent = self.data.get(sig)
        if ent is None:
            return None
        ent.tick = next(_TICKS)
        return ent.fn

    def cost(self, sig):
        """The cached executable's static cost (mxprof), or None —
        lock-free like lookup (cost is written once at insert)."""
        ent = self.data.get(sig)
        return ent.cost if ent is not None else None

    def fingerprint(self, sig):
        """The cached executable's HLO-module fingerprint, or None —
        lock-free like cost (written once at insert)."""
        ent = self.data.get(sig)
        return ent.fingerprint if ent is not None else None

    def stats(self) -> Dict[str, float]:
        with self.lock:
            return {"count": self.compiles,
                    "seconds_total": sum(self.stage_seconds.values()),
                    **{f"{stage}_seconds": v
                       for stage, v in self.stage_seconds.items()},
                    "cache_loads": self.cache_loads,
                    "evictions": self.evictions, "size": len(self.data)}

    def compile(self, sig, build_traced, optimizer, alias_ok=True,
                components=None, donate=False):
        """Build (or load from the persistent store) the executable for
        ``sig``; insert, LRU-evict past MXNET_FUSED_CACHE_MAX, count.
        ``build_traced`` makes the program's traced stage
        (``jax.jit(f, ...).trace(*args)``); :class:`ProgramBuild` takes
        it from there, every stage under its set-up phase.
        ``alias_ok=False`` forces the program-text key even for
        first-party optimizers — required when the program embeds USER
        code (e.g. the SPMD trainer's model forward), which the
        framework version cannot pin.  ``components`` is the NAMED view
        of ``sig`` for compile provenance — with the persistent cache
        off (the default), the provenance diff is recorded here, since
        reaching this method already means the site cache missed.
        ``donate`` is the call site's donation decision, forwarded to
        the mxir program auditor so MX014 can verify the lowered
        module actually aliases something."""
        build = ProgramBuild(build_traced, self.site)
        if _cc.enabled():
            alias = _cc.cache_key(
                f"{self.site}.alias", parts=(sig,)) \
                if alias_ok and _cc.first_party(
                    type(optimizer).__module__) else None

            def full_key():
                return _cc.cache_key(
                    self.site, parts=(sig,), program_text=build.text(),
                    components=components)

            compiled, origin = _cc.get_or_compile(
                self.site, full_key, build.compile, alias=alias)
        else:
            from ..telemetry.mxtriage import provenance as _prov

            # record_miss never raises — diagnostics can't break a build
            _prov.record_miss(self.site, _cc.cache_key(
                self.site, parts=(sig,), components=components))
            compiled, origin = build.compile(), "compiled"
        # runs for cache loads too — a disk-loaded executable is still
        # this process's step program and its invariants still hold or
        # not
        build.audit(donate)
        dt = build.seconds
        # static cost analysis for MFU accounting — computed on the
        # executable object, so a persistent-cache load (origin
        # "memory"/"disk") carries the same metadata as a fresh build;
        # the HLO fingerprint rides beside it (rendered text is reused
        # when the key path already produced it)
        cost = _costs.executable_cost(compiled)
        fp = _costs.hlo_fingerprint(compiled, program_text=build.rendered_text)
        _costs.note(self.site, repr(hash(sig)), cost, fingerprint=fp)
        with self.lock:
            # a concurrent compile of the same signature may have won;
            # keep the first so the compile count matches the cache
            prior = self.data.get(sig)
            if prior is not None:
                return prior.fn
            loaded = origin != "compiled" or build.loaded
            self.data[sig] = _Entry(compiled, cost, fp,
                                    "cache" if loaded else "compiled")
            if origin == "compiled":
                self.compiles += 1
                for stage in self.stage_seconds:
                    self.stage_seconds[stage] += build.stage_seconds(stage)
            else:
                self.cache_loads += 1
            cap = _env.get_int("MXNET_FUSED_CACHE_MAX")
            evicted = 0
            while cap and len(self.data) > cap:
                oldest = min(self.data.items(),
                             key=lambda kv: kv[1].tick)[0]
                if oldest == sig:
                    break  # never evict what we just inserted
                del self.data[oldest]
                self.evictions += 1
                evicted += 1
        if evicted:  # telemetry outside the cache lock
            _ins.compile_cache_evict_total(self._evict_store).inc(evicted)
        if origin == "compiled":
            # always counted, never gated (serving-compile precedent):
            # a recompile on the training hot path is the thing to watch
            self._metric().observe(dt)
            _tracing.record_complete(self._span_name, "training",
                                     build.start, dt)
        _mxsan.record_compile(self.site, sig, dt,
                              provenance="build" if origin == "compiled"
                              else "cache")
        return compiled


_FUSED_CACHE = ExecutableCache(
    "optimizer.fused_step", "optimizer.fused._CACHE", "fused",
    "fused-compile", lambda: _ins.fused_compile_seconds())
# module-level aliases: process-wide executable cache — replicas (and
# trainers) with identical signatures share one compiled program
_CACHE = _FUSED_CACHE.data
_CACHE_LOCK = _FUSED_CACHE.lock


def compile_stats() -> Dict[str, float]:
    """How many fused-step executables were built in this process (and
    the wall seconds spent building them).  The no-recompile guarantee
    is asserted against this counter — and against the
    ``mx_fused_compile_seconds`` histogram, which mirrors it.
    ``cache_loads`` counts executables served by the persistent compile
    cache instead of XLA; ``evictions`` counts LRU drops past
    MXNET_FUSED_CACHE_MAX."""
    return _FUSED_CACHE.stats()


def _state_data(s):
    """NDArray state tree -> raw jax value tree (same structure)."""
    if s is None:
        return None
    if isinstance(s, NDArray):
        return s.data
    return tuple(_state_data(x) for x in s)


def _rebind_state(old, new):
    """Write the new jax values back into the existing NDArray state
    objects — identity is preserved so checkpoints and the eager
    fallback see the updated buffers."""
    if old is None:
        return
    if isinstance(old, NDArray):
        old._data = new
        return
    for o, n in zip(old, new):
        _rebind_state(o, n)


def _leaf_aval(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (tuple(x.shape), str(x.dtype))
    return type(x).__name__


def apply_master(opt: Optimizer, w, g, s, h):
    """One multi-precision update on raw jax values (mp_* semantics):
    ``s`` is ``(inner state, fp32 master weight)``, the math runs on the
    master and the weight is its cast.  THE master-weight rule of every
    traced step: ``apply_param`` below and ``parallel.SPMDTrainer``."""
    inner, w32 = s
    nw32, ninner = opt.fused_apply(w32, g.astype(jnp.float32), inner, h)
    return nw32.astype(w.dtype), (ninner, nw32)


def apply_param(opt: Optimizer, w, g, s, mp: bool, h: Dict[str, Any]):
    """One parameter's optimizer update on raw jax values, multi-
    precision aware — the traced inner math shared by the per-replica
    fused step below and the mesh-wide SPMD step (optimizer/spmd.py).

    ``h`` maps hyper keys to 0-d float32 scalars.  Without a master
    they are cast to the weight dtype, matching the eager path's
    weak-scalar promotion (a python-float attr never upcasts an f16
    kernel)."""
    if mp:
        return apply_master(opt, w, g, s, h)
    h = {k: v.astype(w.dtype) for k, v in h.items()}
    return opt.fused_apply(w, g, s, h)


def _tree_select(ok, new, old):
    """Elementwise step/no-step selection over matching state trees —
    the in-graph half of the skip_step policy (traced; `ok` is a
    scalar bool)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o), new, old)


def _sq_norms(tensors):
    """(n,) float32 vector of per-tensor sum-of-squares (traced)."""
    f32 = jnp.float32
    return jnp.stack([jnp.sum(jnp.square(t.astype(f32)))
                      for t in tensors]) if tensors \
        else jnp.zeros((0,), f32)


def _nonfinite_count(tensors):
    """Scalar float32 count of nonfinite values across tensors
    (traced) — mxhealth's global nonfinite counter."""
    total = jnp.float32(0)
    for t in tensors:
        total = total + jnp.sum((~jnp.isfinite(t)).astype(jnp.float32))
    return total


def _build_step(opt: Optimizer, mp_flags: Tuple[bool, ...],
                health_mode=None):
    """The traced program: apply the optimizer's pure math to every
    parameter.  Static hyperparams are read off `opt` at trace time and
    are part of the cache key (Optimizer.fused_static_key).

    Per-step scalars arrive PACKED: one (n_params,) float32 vector per
    hyper key instead of n_params scalar buffers — three host->device
    transfers per step, not 3N (scalar transfer cost would otherwise
    swamp the single-dispatch win).

    ``health_mode`` (part of the executable signature) grows the
    program by mxhealth's numerics outputs — per-param grad/update/
    param norm-squares and a global nonfinite count — as tiny extra
    results of the SAME dispatch; ``"guard"`` additionally selects the
    pre-step weights/states when any gradient value is nonfinite, so a
    skipped step is bit-identical to not having stepped."""

    def step(weights, grads, states, hyper_vecs):
        new_w, new_s = [], []
        for i, (w, g, s, mp) in enumerate(zip(weights, grads, states,
                                              mp_flags)):
            h = {k: v[i] for k, v in hyper_vecs.items()}
            nw, ns = apply_param(opt, w, g, s, mp, h)
            new_w.append(nw)
            new_s.append(ns)
        new_w, new_s = tuple(new_w), tuple(new_s)
        if health_mode is None:
            return new_w, new_s
        f32 = jnp.float32
        gn2 = _sq_norms(grads)
        pn2 = _sq_norms(weights)
        un2 = jnp.stack([
            jnp.sum(jnp.square(nw.astype(f32) - w.astype(f32)))
            for nw, w in zip(new_w, weights)]) if weights \
            else jnp.zeros((0,), f32)
        nonfinite = _nonfinite_count(grads)
        if health_mode == "guard":
            ok = nonfinite == 0
            new_w = _tree_select(ok, new_w, weights)
            new_s = _tree_select(ok, new_s, states)
        return new_w, new_s, (gn2, un2, pn2, nonfinite)

    return step


class FusedUpdater(Updater):
    """Updater whose batch entry point (`update_all`) runs the whole
    parameter list as one compiled program."""

    def __init__(self, optimizer: Optimizer):
        super().__init__(optimizer)

    def supports(self, indices: List[int],
                 weights: List[NDArray]) -> bool:
        """Static-compatibility probe, mutation-free apart from state
        creation (which the eager path would perform identically):
        False when this parameter set must take the eager loop.  The
        caller can latch the answer — the conditions are fixed for a
        run (optimizer class, weight dtypes, multi-precision layout)."""
        opt = self.optimizer
        if not opt._FUSED_T_HYPER:
            return True
        for i, w in zip(indices, weights):
            if i not in self.states:
                self.states[i] = opt.create_state_multi_precision(i, w)
            if (str(w.data.dtype) in ("float16", "bfloat16")
                    and not opt._mp_active(w, self.states[i])):
                return False
        return True

    def update_all(self, indices: List[int], grads: List[NDArray],
                   weights: List[NDArray]) -> None:
        """Apply one optimizer step to every (index, grad, weight)
        triple in a single dispatch.  All arrays must live on one
        device (one replica's view); the Trainer guarantees this."""
        opt = self.optimizer
        for i, w in zip(indices, weights):
            if i not in self.states:
                self.states[i] = opt.create_state_multi_precision(i, w)

        mp_flags, states = [], []
        for i, w in zip(indices, weights):
            s = self.states[i]
            mp_flags.append(opt._mp_active(w, s))
            states.append(s)

        if opt._FUSED_T_HYPER and any(
                not mp and str(w.data.dtype) in ("float16", "bfloat16")
                for w, mp in zip(weights, mp_flags)):
            # the traced step count would be cast to the half weight
            # dtype, which cannot represent t past 256 (bf16) — the
            # eager loop folds t host-side in full precision instead.
            # Raised before any count/state mutation so the fallback
            # replays the step exactly.
            raise FusedUnsupported(
                f"{type(opt).__name__}: half-precision weights without "
                "multi_precision need the eager loop (in-kernel bias "
                "correction cannot trace t in half precision)")

        hypers = []
        for i in indices:
            opt._update_count(i)
            hypers.append(opt.fused_hyper(i, opt._index_update_count[i]))

        w_tup = tuple(w.data for w in weights)
        g_tup = tuple(g.data for g in grads)
        s_tup = tuple(_state_data(s) for s in states)
        # pack per-parameter scalars: one (n,) vector per hyper key
        # packs HOST python floats (lr/wd/t), not device arrays — this
        # is the 3-transfers-per-step design, not a device sync
        h_vecs = {k: np.asarray([h[k] for h in hypers],  # mxlint: disable=MX002
                                np.float32)
                  for k in hypers[0]}

        hm = _mxhealth.mode() if _mxhealth._ACTIVE else None
        dev = weights[0].ctx.jax_device
        # the raise policy disables donation: it promises params at
        # their PRE-step values after the raise, which a donated input
        # buffer cannot honor (the dispatch consumed it)
        donate = dev.platform not in ("cpu",) and hm != "raise"
        args = (w_tup, g_tup, s_tup, h_vecs)
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = (type(opt), opt.fused_static_key(), tuple(mp_flags),
               donate, str(dev), hm, treedef,
               tuple(_leaf_aval(x) for x in leaves))

        fn = _FUSED_CACHE.lookup(sig)
        if fn is None:
            fn = self._compile(sig, args, mp_flags, donate, hm)
        out = fn(*args)
        if hm is not None:
            new_w, new_s, health = out
            if getattr(self, "mxprof_report_cost", True):
                # replica-0-reports, like the FLOPs accounting below:
                # replicas run the same program on the same reduced
                # grads, so one replica's numerics speak for the step.
                # Under policy "raise" this raises NonFiniteGradient
                # BEFORE the writeback — params keep their pre-step
                # buffers (donation is off on this path).
                _mxhealth.monitor().on_step(_FUSED_CACHE.site, {
                    "gn2": health[0], "un2": health[1],
                    "pn2": health[2], "nonfinite": health[3],
                    "guarded": hm == "guard"})
        else:
            new_w, new_s = out

        snk = _tracing._SINK
        if snk is not None and getattr(self, "mxprof_report_cost",
                                       True):
            # mxprof: this step ran these FLOPs.  The Trainer clears
            # the flag on replicas > 0 — they run the SAME program, and
            # counting it nrep times against one device's peak would
            # inflate MFU by the replica count.
            c = _FUSED_CACHE.cost(sig)
            if c is not None:
                snk.on_flops(_FUSED_CACHE.site, c)

        for w, nw in zip(weights, new_w):
            w._data = nw
        for s, ns in zip(states, new_s):
            _rebind_state(s, ns)

    def _compile(self, sig, args, mp_flags, donate, health_mode=None):
        def build_traced():
            step = _build_step(self.optimizer, tuple(mp_flags),
                               health_mode)
            return jax.jit(
                step, donate_argnums=(0, 2) if donate else ()).trace(*args)

        # the NAMED sig view compile provenance diffs a miss against
        # (sig layout: see the tuple built in update_multi).  The live
        # collective wire encoding rides along as plan metadata: the
        # per-replica program itself never encodes, but the kvstore
        # reduce feeding it does, so a provenance diff can say "the
        # executable rebuilt while the wire encoding flipped"
        from . import comm as _comm

        components = {"optimizer": sig[0], "statics": sig[1],
                      "mp": sig[2], "donation": sig[3],
                      "device": sig[4], "health_mode": sig[5],
                      "treedef": sig[6], "avals": sig[7],
                      "wire_encoding": _comm.config().mode}
        return _FUSED_CACHE.compile(sig, build_traced, self.optimizer,
                                    components=components, donate=donate)
