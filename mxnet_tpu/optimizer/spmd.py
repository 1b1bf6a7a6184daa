"""Unified SPMD optimizer step: ONE program over the replica mesh.

The per-replica fused path (optimizer/fused.py) still dispatches pmap
style: N replicas mean N AOT dispatches per step, plus separate bucket
collectives, with every replica holding a full copy of the optimizer
states.  ``SpmdUpdater`` collapses the whole step-chain tail — gradient
reduce + optimizer apply — into a SINGLE donated ``jax.jit`` program
compiled under a named 1-D ``dp`` mesh over the replica devices
(``parallel.mesh.replica_mesh``), with ``NamedSharding`` annotations on
grads and optimizer states so XLA inserts the collectives.

Inside the program the parameters are grouped by a static **bucket
plan** (the "bucketed reduce + fused apply" layout):

  * **ZeRO buckets** — parameters ≥ ``MXNET_ZERO_MIN_SIZE`` elements
    whose optimizer is elementwise concatenate (flat, padded to the
    shard count) into dtype/mp-homogeneous buckets capped at
    ``MXNET_SPMD_BUCKET_BYTES``.  Per bucket: one **reduce-scatter**
    (replica sum constrained to the ``dp`` layout), one shard-local
    **update** on 1/N of the elements with per-element hyper vectors,
    one **all-gather** of the fresh weights.  Optimizer states live
    flat-sharded — each device holds 1/N of every state tensor
    (ZeRO-1 / cross-replica weight-update sharding, arXiv:2004.13336).
  * **small group** — everything below the threshold reduces in one
    concatenated **all-reduce**, then updates per-parameter on
    replicated (original-shape) tensors: sharding a 64-element bias
    would pay collective latency for nothing.
  * **singles** — norm-based optimizers (LAMB) keep per-parameter
    tensors (the trust ratio is per tensor) but still shard their
    states and update across ``dp`` when big enough.

Data-parallel local replicas, multi-process (DCN) layouts, and the
single-device degenerate case are the same code path: only the mesh
differs.  ``MXNET_ZERO_STATES=0`` keeps every state replicated (the
collectives are then plain all-reduces, still one program).

Hyper scalars stay TRACED (packed vectors, like the fused path), so lr
schedules never recompile; the executable is AOT-compiled once per
(optimizer class, statics, mesh layout, plan, tree/avals) and routed
through the persistent compile cache (PR 7) so a fresh process
warm-starts the mesh-wide program from disk.

Per-replica t-skew note: the per-replica paths bump the shared update
count once per replica, so replica r applies bias correction at
``t = step*N - N + r + 1``.  One program produces one result; it uses
the replica-0 trajectory (first bump) and keeps bumping N times per
step so schedules stay aligned when paths mix mid-run.  For t-free
optimizers the two paths are fp-tolerant identical; for t-optimizers
the SPMD result equals the per-replica path's replica 0 (and keeps
replicas exactly in sync, which the skewed path does not).
"""
from __future__ import annotations

import pickle
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis import sanitizer as _mxsan
from ..ndarray.ndarray import NDArray
from ..resilience import chaos as _chaos
from ..telemetry import instruments as _ins
from ..telemetry import mxhealth as _mxhealth
from ..telemetry import tracing as _tracing
from ..util import env as _env
from . import comm as _comm
from .fused import (ExecutableCache, FusedUnsupported, _leaf_aval,
                    _nonfinite_count, _sq_norms, _tree_select,
                    apply_param)
from .optimizer import Optimizer, Updater

__all__ = ["SpmdUpdater", "compile_stats"]

AXIS = "dp"

_SPMD_CACHE = ExecutableCache(
    "optimizer.spmd_step", "optimizer.spmd._CACHE", "spmd",
    "spmd-compile", lambda: _ins.spmd_compile_seconds())


def compile_stats() -> Dict[str, float]:
    """SPMD-step executable builds in this process — the
    one-executable-per-(mesh, layout) guarantee is asserted against
    ``count`` (phased tracing variants are separate jit programs built
    only while tracing is active and are not counted here)."""
    return _SPMD_CACHE.stats()


class _Meta(NamedTuple):
    shape: Tuple[int, ...]
    dtype: str
    size: int     # prod(shape)
    padded: int   # size rounded up to a multiple of the shard count


class _Bucket(NamedTuple):
    """One ZeRO bucket: concatenated flat-padded params, dp-sharded."""
    pos: Tuple[int, ...]       # positions into the step's param list
    offsets: Tuple[int, ...]   # each param's start in the concat flat
    sizes: Tuple[int, ...]     # each param's padded length
    total: int
    mp: bool


class _Small(NamedTuple):
    """Sub-threshold params: one concatenated all-reduce, replicated
    per-param updates."""
    pos: Tuple[int, ...]
    sizes: Tuple[int, ...]     # unpadded flat lengths (concat offsets)


class _Plan(NamedTuple):
    buckets: Tuple[_Bucket, ...]
    smalls: Tuple[_Small, ...]
    singles: Tuple[int, ...]   # per-param ZeRO (norm-based optimizers)


def _padded(n: int, k: int) -> int:
    return ((max(n, 1) + k - 1) // k) * k


def _pad_flat(x, padded: int):
    """Flatten and zero-pad to the shard-divisible length (traced)."""
    f = x.reshape(-1)
    if f.shape[0] == padded:
        return f
    return jnp.pad(f, (0, padded - f.shape[0]))


def _pad_rows(g, padded: int):
    """Flatten a stacked ``(nshard,) + shape`` gradient per row and
    zero-pad each row to the shard-divisible length (traced)."""
    f = g.reshape(g.shape[0], -1)
    if f.shape[1] == padded:
        return f
    return jnp.pad(f, ((0, 0), (0, padded - f.shape[1])))


def _tree_map(fn, tree):
    """Map over a state tree (None | leaf | tuple), preserving shape."""
    if tree is None:
        return None
    if isinstance(tree, tuple):
        return tuple(_tree_map(fn, t) for t in tree)
    return fn(tree)


def _tree_multi(fn, trees):
    """Zip same-structure state trees; fn receives the leaf list."""
    if trees[0] is None:
        return None
    if isinstance(trees[0], tuple):
        return tuple(_tree_multi(fn, [t[i] for t in trees])
                     for i in range(len(trees[0])))
    return fn(trees)


def _mesh_devices(local_devices: List, dist: bool) -> List:
    """The global replica device list: the local replicas, or — on a
    multi-process (DCN) job — every process's matching local devices,
    process-ordered, so the one program spans the whole job."""
    if not dist or jax.process_count() == 1:
        return list(local_devices)
    nloc = len(local_devices)
    groups: Dict[int, List] = {}
    for d in jax.devices():
        groups.setdefault(d.process_index, []).append(d)
    out: List = []
    for p in sorted(groups):
        g = sorted(groups[p], key=lambda d: d.id)[:nloc]
        if len(g) != nloc:
            raise FusedUnsupported(
                f"spmd: process {p} exposes {len(groups[p])} devices, "
                f"need {nloc} per process for a rectangular mesh")
        out.extend(g)
    mine = [d for d in out if d.process_index == jax.process_index()]
    if set(mine) != set(local_devices):
        raise FusedUnsupported(
            "spmd: this process's replica devices are not its first "
            f"{nloc} local devices; the cross-process mesh would not "
            "cover them")
    return out


class SpmdUpdater(Updater):
    """Updater whose batch entry point (``update_all_mesh``) runs the
    gradient reduce AND the whole parameter update as one compiled
    program over the replica mesh, with optimizer states sharded across
    it (ZeRO-1).  Extends the serializable ``Updater``:
    ``get_states``/``set_states`` speak the identical single-payload
    format (states are gathered to canonical full-shape numpy on save),
    so checkpoints round-trip with the per-replica paths and resume
    onto a DIFFERENT mesh shape re-shards on load."""

    def __init__(self, optimizer: Optimizer,
                 zero_states: Optional[bool] = None):
        super().__init__(optimizer)
        self._zero = _env.get_bool("MXNET_ZERO_STATES") \
            if zero_states is None else bool(zero_states)
        self._mesh = None            # parallel.mesh.DeviceMesh
        self._layout = None          # mesh layout fingerprint
        self._flat = False           # ZeRO sharding active (nshard > 1)
        self._plan: Optional[_Plan] = None
        self._plan_indices: Optional[Tuple[int, ...]] = None
        # state storage mirrors the plan: one concatenated tree per
        # bucket, one per-param tree for smalls/singles
        self._bstate: Dict[int, Any] = {}    # bucket ordinal -> tree
        self._pstate: Dict[int, Any] = {}    # param index -> tree
        self._mp: Dict[int, bool] = {}
        self._meta: Dict[int, _Meta] = {}
        self._pending: Optional[Dict[int, Any]] = None  # numpy trees
        self._phased = {}            # sig -> (reduce, update, gather)
        # quantized collectives (MXNET_COMM_QUANT): static config, the
        # per-bucket error-feedback residual state ((grad, weight-delta)
        # pairs, dp-sharded rows beside _bstate), canonical residuals
        # pending from set_states, and the overlap-mode stage programs
        self._quant = _comm.config()
        self._overlap = _env.get_bool("MXNET_COMM_OVERLAP")
        # mxsan: updater-thread state, but checkpoint get/set_states
        # may read it cross-thread — Eraser proves the discipline
        self._qstate: Dict[int, Tuple] = _mxsan.track(
            {}, "optimizer.spmd._qstate")  # bucket ordinal -> (g, w)
        self._pending_q: Optional[Dict[str, Any]] = None
        self._overlap_fns = {}       # sig -> (bucket reduce fns, tail)
        # steady-state caches: the signature (treedef/avals never
        # change while the param set is stable) and the replicated
        # weight globals (last step's OUTPUT is next step's input when
        # nothing rebound the buffers externally)
        self._sig_cache: Optional[Tuple] = None
        self._w_global: Dict[int, Tuple] = {}

    # ---- mesh ------------------------------------------------------------
    def _ensure_mesh(self, local_devices: List, dist: bool):
        from ..parallel.mesh import layout_key, replica_mesh

        devs = _mesh_devices(local_devices, dist)
        if self._mesh is not None:
            if list(self._mesh.devices) != devs:
                raise FusedUnsupported(
                    "spmd: replica device layout changed mid-run; "
                    "falling back to the per-replica path")
        else:
            self._mesh = replica_mesh(devs)
            self._layout = layout_key(self._mesh)
            # ZeRO sharding only when there is something to shard
            # ACROSS; the degenerate 1-shard mesh keeps original shapes
            # (pad/slice copies would cost bandwidth and buy nothing)
            self._flat = self._zero and self._mesh.size(AXIS) > 1
        # re-set every step, not just at creation: tracing may enable
        # after the mesh engaged, and gauges must reflect the layout
        # of whichever trainer stepped last
        if _tracing._ENABLED:
            _ins.step_layout_axis_size(AXIS).set(self._mesh.size(AXIS))
            _ins.step_state_shard_factor().set(self.shard_factor())
        return self._mesh

    @property
    def nshard(self) -> int:
        return self._mesh.size(AXIS) if self._mesh is not None else 1

    def shard_factor(self) -> int:
        """Ways the (bucketed) optimizer states split across devices."""
        return self.nshard if self._flat else 1

    # ---- plan ------------------------------------------------------------
    def _build_plan(self, indices: List[int]) -> _Plan:
        opt = self.optimizer
        elementwise = bool(opt._FUSED_ELEMENTWISE)
        zero_min = _env.get_int("MXNET_ZERO_MIN_SIZE") or 0
        cap = _env.get_int("MXNET_SPMD_BUCKET_BYTES") \
            or _env.get_int("MXNET_FUSED_BUCKET_BYTES")
        buckets: List[_Bucket] = []
        smalls: Dict[Tuple, List[int]] = {}
        singles: List[int] = []
        cur: List[int] = []
        cur_key, cur_bytes = None, 0

        def close():
            nonlocal cur, cur_bytes
            if cur:
                sizes = tuple(self._meta[indices[q]].padded for q in cur)
                offs, off = [], 0
                for s in sizes:
                    offs.append(off)
                    off += s
                buckets.append(_Bucket(tuple(cur), tuple(offs), sizes,
                                       off, self._mp[indices[cur[0]]]))
            cur, cur_bytes = [], 0

        for p, i in enumerate(indices):
            m = self._meta[i]
            if not self._flat or m.size < zero_min:
                smalls.setdefault((m.dtype, self._mp[i]),
                                  []).append(p)
                continue
            if not elementwise:
                singles.append(p)
                continue
            key = (m.dtype, self._mp[i])
            nbytes = m.padded * np.dtype(m.dtype).itemsize
            if cur and (key != cur_key or cur_bytes + nbytes > cap):
                close()
            cur.append(p)
            cur_key, cur_bytes = key, cur_bytes + nbytes
        close()
        small_groups = tuple(
            _Small(tuple(ps),
                   tuple(self._meta[indices[p]].size for p in ps))
            for _, ps in sorted(smalls.items()))
        return _Plan(tuple(buckets), small_groups, tuple(singles))

    def _quant_buckets(self, plan: _Plan) -> Tuple[int, ...]:
        """Bucket ordinals whose collectives quantize: ZeRO sharding
        active (a 1-shard mesh moves no wire bytes) and the bucket
        clears MXNET_COMM_QUANT_MIN_SIZE."""
        if not (self._flat and self._quant.active):
            return ()
        return tuple(bi for bi, b in enumerate(plan.buckets)
                     if self._quant.applies(b.total))

    # ---- sharding/data movement -----------------------------------------
    def _shard(self, flat: bool) -> NamedSharding:
        return NamedSharding(self._mesh.mesh, P(AXIS) if flat else P())

    def _materialize_states(self, indices, weights0):
        """Build the plan-shaped global state storage from the pending
        payload and/or freshly created per-param states."""
        from ..parallel.spmd import _global_put

        opt = self.optimizer
        pend = self._pending or {}

        def host_tree(i, w):
            if i in pend:
                return _tree_map(np.asarray, pend[i])
            tree = opt.create_state_multi_precision(i, w)
            return _tree_map(
                lambda leaf: np.asarray(jax.device_get(leaf.data)), tree)

        host = {i: host_tree(i, w) for i, w in zip(indices, weights0)}
        plan = self._plan
        for bi, b in enumerate(plan.buckets):
            trees = [host[indices[p]] for p in b.pos]

            def cat(leaves, b=b):
                flats = []
                for leaf, p in zip(leaves, b.pos):
                    m = self._meta[indices[p]]
                    f = leaf.reshape(-1)
                    if f.size != m.padded:
                        f = np.pad(f, (0, m.padded - f.size))
                    flats.append(f)
                return _global_put(np.concatenate(flats),
                                   self._shard(True))

            self._bstate[bi] = _tree_multi(cat, trees)
        for g in plan.smalls:
            for p in g.pos:
                i = indices[p]
                self._pstate[i] = _tree_map(
                    lambda leaf: _global_put(leaf, self._shard(False)),
                    host[i])
        for p in plan.singles:
            i = indices[p]
            m = self._meta[i]

            def put_single(leaf, m=m):
                f = np.asarray(leaf).reshape(-1)
                if f.size != m.padded:
                    f = np.pad(f, (0, m.padded - f.size))
                return _global_put(f, self._shard(True))

            self._pstate[i] = _tree_map(put_single, host[i])
        # error-feedback residuals for the quantized buckets: restore
        # the canonical per-param residuals (grad side: total owed
        # signal, assigned to replica 0's row — the per-row split is a
        # mesh artifact, the SUM is the state; weight side: the flat
        # concat maps 1:1 onto the shard rows) or start at zero
        self._qstate.clear()
        qbis = self._quant_buckets(plan)
        if qbis:
            nshard = self.nshard
            pend_q = self._pending_q or {}
            pg = pend_q.get("grads") or {}
            pw = pend_q.get("weights") or {}
            row_sh = NamedSharding(self._mesh.mesh, P(AXIS, None))
            for bi in qbis:
                b = plan.buckets[bi]
                gres = np.zeros((nshard, b.total), np.float32)
                wflat = np.zeros((b.total,), np.float32)
                for p, off in zip(b.pos, b.offsets):
                    i = indices[p]
                    m = self._meta[i]
                    if i in pg:
                        gres[0, off:off + m.size] = \
                            np.asarray(pg[i], np.float32).reshape(-1)
                    if i in pw:
                        wflat[off:off + m.size] = \
                            np.asarray(pw[i], np.float32).reshape(-1)
                self._qstate[bi] = (
                    _global_put(gres, row_sh),
                    _global_put(wflat.reshape(nshard, -1), row_sh))
        self._pending_q = None
        self._pending = None

    def _gather_np(self, garr) -> np.ndarray:
        """Global (possibly sharded, possibly multi-process) array ->
        host numpy."""
        if not garr.is_fully_addressable:
            garr = jax.jit(
                lambda x: x,
                out_shardings=NamedSharding(self._mesh.mesh, P()))(garr)
            return np.asarray(garr.addressable_data(0))
        return np.asarray(garr)

    # ---- probes ----------------------------------------------------------
    def supports(self, indices: List[int],
                 weights: List[NDArray]) -> bool:
        """Static-compatibility probe, mutation-free: False when this
        parameter set must take a fallback path (same condition as the
        fused updater: in-kernel bias correction cannot trace t in half
        precision without a master copy)."""
        opt = self.optimizer
        if not opt._FUSED_T_HYPER:
            return True
        for w in weights:
            if (str(w.data.dtype) in ("float16", "bfloat16")
                    and not opt.multi_precision):
                return False
        return True

    # ---- the step --------------------------------------------------------
    def update_all_mesh(self, indices: List[int],
                        grads: List[List[NDArray]],
                        weights: List[List[NDArray]],
                        dist: bool = False) -> None:
        """One optimizer step for every parameter across every replica
        in a single dispatch.  ``grads[p][r]`` / ``weights[p][r]`` index
        parameter p's replica r; replica r of every parameter must live
        on the same device (the Trainer guarantees this)."""
        opt = self.optimizer
        nrep = len(weights[0])  # LOCAL replicas (this process's shards)
        local_devs = [w.ctx.jax_device for w in weights[0]]
        mesh = self._ensure_mesh(local_devs, dist)
        nshard = mesh.size(AXIS)  # GLOBAL replica count across the job

        if opt._FUSED_T_HYPER and not opt.multi_precision and any(
                str(w[0].data.dtype) in ("float16", "bfloat16")
                for w in weights):
            # raised before any count/state mutation (fused-path
            # precedent): the traced t cannot live in half precision
            raise FusedUnsupported(
                f"{type(opt).__name__}: half-precision weights without "
                "multi_precision need the eager loop")

        for i, w in zip(indices, weights):
            if i not in self._meta:
                shp = tuple(w[0].shape)
                n = int(np.prod(shp)) if shp else 1
                self._meta[i] = _Meta(shp, str(w[0].data.dtype), n,
                                      _padded(n, nshard))
            self._mp[i] = bool(
                opt.multi_precision
                and str(w[0].data.dtype) in ("float16", "bfloat16"))
        idx_key = tuple(indices)
        if self._plan is None or self._plan_indices != idx_key:
            if self._plan is not None:
                # param set changed: round states through the canonical
                # payload so the new plan re-shards them losslessly
                self.set_states(self.get_states(dump_optimizer=False))
            self._plan = self._build_plan(indices)
            self._plan_indices = idx_key
            self._materialize_states(indices,
                                     [w[0] for w in weights])
            self._sig_cache = None
            # drop cached all-gathered weights: entries for indices no
            # longer in the set would pin full-size device arrays for
            # the process lifetime (survivors fail the identity check
            # after the re-shard anyway and rebuild on first touch)
            self._w_global.clear()

        # shared-count parity with the per-replica paths: N bumps per
        # step, hyper computed at the FIRST bump (replica-0 trajectory)
        hypers = []
        for i in indices:
            opt._update_count(i)
            t_first = opt._index_update_count[i]
            for _ in range(nrep - 1):
                opt._update_count(i)
            hypers.append(opt.fused_hyper(i, t_first))
        h_vecs = {k: np.asarray([h[k] for h in hypers],  # mxlint: disable=MX002
                                np.float32)
                  for k in hypers[0]}

        w_sh = NamedSharding(mesh.mesh, P())
        w_tup = []
        for i, w in zip(indices, weights):
            cached = self._w_global.get(i)
            if cached is not None and len(cached[0]) == len(w) and all(
                    a is r.data for a, r in zip(cached[0], w)):
                # last step's all-gathered output IS this step's input
                w_tup.append(cached[1])
                continue
            w_tup.append(jax.make_array_from_single_device_arrays(
                self._meta[i].shape, w_sh, [r.data for r in w]))
        w_tup = tuple(w_tup)
        g_tup = tuple(
            jax.make_array_from_single_device_arrays(
                (nshard,) + self._meta[i].shape,
                NamedSharding(mesh.mesh, P(AXIS, *(
                    [None] * len(self._meta[i].shape)))),
                [r.data[None] for r in g])
            for i, g in zip(indices, grads))
        plan = self._plan
        qbis = self._quant_buckets(plan)
        s_tup = (tuple(self._bstate[bi]
                       for bi in range(len(plan.buckets))),
                 tuple(self._pstate[i] for i in indices
                       if i in self._pstate))
        if qbis:
            # residual state rides the donated states argument; the
            # traced per-quant-bucket scale multiplier is 1.0 except
            # under chaos (site comm.quant: a flipped scale must light
            # up mxhealth, not silently corrupt the run)
            from ..parallel.spmd import _global_put
            s_tup = s_tup + (tuple(self._qstate[bi] for bi in qbis),)
            qm = np.ones((len(qbis),), np.float32)
            if _chaos._ACTIVE \
                    and _chaos.check("comm.quant") == "corrupt":
                qm[0] = np.float32("inf")
            qmult = _global_put(qm, NamedSharding(mesh.mesh, P()))
        mp_flags = tuple(self._mp[i] for i in indices)
        metas = tuple(self._meta[i] for i in indices)

        hm = _mxhealth.mode() if _mxhealth._ACTIVE else None
        args = (w_tup, g_tup, s_tup, h_vecs) if not qbis \
            else (w_tup, g_tup, s_tup, h_vecs, qmult)
        # raise policy: donation off — pre-step state buffers must
        # survive the raise (fused-path precedent)
        donate = mesh.devices[0].platform not in ("cpu",) \
            and hm != "raise"
        sig_key = (idx_key, nrep, opt.fused_static_key(),
                   tuple(m.dtype for m in metas),
                   tuple(str(g[0].data.dtype) for g in grads),
                   tuple(h_vecs), hm, self._quant)
        if self._sig_cache is not None and self._sig_cache[0] == sig_key:
            sig = self._sig_cache[1]
        else:
            leaves, treedef = jax.tree_util.tree_flatten(args)
            # the layout fingerprint keys the PROGRAM; the concrete
            # device ids pin the AOT device assignment (stable across a
            # same-topology restart, so the persistent tier still warm-
            # starts — but two trainers on disjoint device subsets must
            # not share an executable bound to the wrong devices)
            sig = (type(opt), opt.fused_static_key(), mp_flags, metas,
                   plan, self._flat, donate, self._layout, hm,
                   tuple(str(d) for d in mesh.devices), treedef,
                   tuple(_leaf_aval(x) for x in leaves), self._quant)
            self._sig_cache = (sig_key, sig)

        # the phased (3-dispatch) variant keys on capture_active(), NOT
        # active(): the always-on mxprof sink must never serialize the
        # one-program step it exists to measure.  With mxhealth on, the
        # unified program runs even while capturing — the numerics
        # outputs (and the skip_step guard) live inside it, and a
        # capture must not turn the guard off.  MXNET_COMM_OVERLAP
        # outranks the phased variant: serializing the stages would
        # un-overlap exactly what the lane measures.
        # schedule-ledger record: ONE entry per step dispatch (the
        # fused program carries every bucket collective), logged before
        # the dispatch so a divergent rank that wedges inside the
        # program has already published what it entered.  The overlap
        # variant additionally records its per-bucket reduce dispatches
        # (its collectives are separate programs).
        from ..parallel import schedule as _schedule

        _schedule.record(
            "spmd.step", "fused-step",
            str(metas[0].dtype) if metas else "",
            sum(m.size * np.dtype(m.dtype).itemsize for m in metas))
        if self._overlap and self._flat and hm is None and plan.buckets:
            new_w, new_s = self._run_overlap(sig, args, mp_flags,
                                             metas, qbis)
        elif self._flat and _tracing.capture_active() and hm is None:
            new_w, new_s = self._run_phased(sig, args, mp_flags, metas,
                                            qbis)
        else:
            fn = _SPMD_CACHE.lookup(sig)
            if fn is None:
                fn = self._compile(sig, args, mp_flags, metas, donate,
                                   hm, qbis)
            out = fn(*args)
            if hm is not None:
                new_w, new_s, health = out
                # under policy "raise" this raises NonFiniteGradient
                # BEFORE any writeback: weights/states keep their
                # pre-step buffers (donation is off on this path)
                _mxhealth.monitor().on_step(_SPMD_CACHE.site, {
                    "gn2": health[0], "un2": health[1],
                    "pn2": health[2], "nonfinite": health[3],
                    "guarded": hm == "guard"})
            else:
                new_w, new_s = out
        snk = _tracing._SINK
        if snk is not None:  # mxprof: this step ran these FLOPs
            c = _SPMD_CACHE.cost(sig)
            if c is not None:
                snk.on_flops(_SPMD_CACHE.site, c)
        self._count_bytes(metas, plan, qbis)

        for i, w, nw in zip(indices, weights, new_w):
            per_dev = {s.device: s.data for s in nw.addressable_shards}
            bound = []
            for r in w:
                r._data = per_dev[r.ctx.jax_device]
                bound.append(r._data)
            self._w_global[i] = (tuple(bound), nw)
        nb_states, np_states = new_s[0], new_s[1]
        for bi, tree in enumerate(nb_states):
            self._bstate[bi] = tree
        pidx = [i for i in indices if i in self._pstate]
        for i, tree in zip(pidx, np_states):
            self._pstate[i] = tree
        if qbis:
            for j, bi in enumerate(qbis):
                self._qstate[bi] = new_s[2][j]

    def _count_bytes(self, metas, plan, qbis=()):
        snk = _tracing._SINK
        if not _tracing._ENABLED and snk is None:
            return
        def nbytes(pos):
            return sum(metas[p].size * np.dtype(metas[p].dtype).itemsize
                       for p in pos)
        rs = sum(nbytes(b.pos) for b in plan.buckets) \
            + nbytes(plan.singles)
        ar = sum(nbytes(g.pos) for g in plan.smalls)
        if rs:
            if _tracing._ENABLED:
                _ins.collective_bytes_total("reduce-scatter",
                                            AXIS).inc(rs)
                _ins.collective_bytes_total("all-gather", AXIS).inc(rs)
            if snk is not None:
                snk.on_bytes("reduce-scatter", AXIS, rs)
                snk.on_bytes("all-gather", AXIS, rs)
        if ar:
            if _tracing._ENABLED:
                _ins.collective_bytes_total("all-reduce", AXIS).inc(ar)
            if snk is not None:
                snk.on_bytes("all-reduce", AXIS, ar)
        # the WIRE view: what actually crosses the interconnect this
        # step, split by encoding.  Quantized buckets move 1-byte codes
        # plus one f32 scale per 512-element block on both legs;
        # everything
        # else moves its payload dtype as-is ('raw').  The logical
        # counters above stay flat by design — the two series disagree
        # exactly when MXNET_COMM_QUANT is earning its keep.
        mode, nshard, qset = self._quant.mode, self.nshard, set(qbis)
        wire: Dict[Tuple[str, str], int] = {}

        def add(op, enc, n):
            wire[(op, enc)] = wire.get((op, enc), 0) + n

        for bi, b in enumerate(plan.buckets):
            if bi in qset:
                n = _comm.wire_nbytes(b.total, nshard, mode)
                add("reduce-scatter", mode, n)
                add("all-gather", mode, n)
            else:
                n = nbytes(b.pos)
                add("reduce-scatter", "raw", n)
                add("all-gather", "raw", n)
        if plan.singles:
            n = nbytes(plan.singles)
            add("reduce-scatter", "raw", n)
            add("all-gather", "raw", n)
        if ar:
            add("all-reduce", "raw", ar)
        ob = getattr(snk, "on_wire_bytes", None) \
            if snk is not None else None
        for (op, enc), n in wire.items():
            if _tracing._ENABLED:
                _ins.collective_wire_bytes_total(op, AXIS, enc).inc(n)
            if ob is not None:
                ob(op, AXIS, enc, n)

    # ---- program builders ------------------------------------------------
    def _stages(self, mp_flags, metas, qbis=()):
        """The three stages of the step, split at the collective
        boundaries.  ``_build_step`` composes them into ONE program;
        the phased tracing variant runs them as three so trace_report
        can attribute wall time per phase; the overlap variant runs
        ``reduce_bucket`` as one tiny program per bucket (issued in
        gradient-ready order) and everything else as a tail program.

        Stage contracts (all traced, all pure):
          reduce(gstacks[, qres, qmult])   -> reduced parts
                                              (+ new grad residuals)
          update(weights, parts, states, hyper) -> (new flat/shaped
                                              weights parts, new states)
          gather(parts[, qres])            -> per-param full weights
                                              (+ new delta residuals)
        'parts' are plan-shaped: one concat flat per bucket (sharded),
        one concat flat per small group (replicated), one flat per
        single (sharded).

        ``qbis`` names the bucket ordinals whose collectives quantize
        (MXNET_COMM_QUANT): their gradient reduce becomes encode ->
        1-byte all-to-all + scale exchange -> local weighted sum, and
        their weight gather becomes a 1-byte all-gather of the encoded
        weight DELTA — every shard applies the identical dequantized
        delta to the identical replicated old weights, so replicas stay
        bit-identical.  Both legs carry error-feedback residuals (the
        quantization remainder re-enters the next step's payload).
        With ``qbis`` empty every traced op below is byte-identical to
        the unquantized program.
        """
        opt = self.optimizer
        plan = self._plan
        mesh = self._mesh
        nsh = mesh.size(AXIS)
        shard = NamedSharding(mesh.mesh, P(AXIS))
        repl = NamedSharding(mesh.mesh, P())
        row_sh = NamedSharding(mesh.mesh, P(AXIS, None))
        col_sh = NamedSharding(mesh.mesh, P(None, AXIS))
        csn = lax.with_sharding_constraint
        mode, ef = self._quant.mode, self._quant.ef
        qpos = {bi: j for j, bi in enumerate(qbis)}
        f32 = jnp.float32
        # static per-bucket segment-id arrays (element -> param position
        # in the hyper vector), built on the host ONCE.  A constant-index
        # gather partitions cleanly; jnp.repeat inside the sharded
        # program lowers to a dynamic gather the SPMD partitioner
        # serializes catastrophically (measured ~6000x slower on CPU).
        b_seg = [np.repeat(np.asarray(b.pos, np.int64),
                           np.asarray(b.sizes)) for b in plan.buckets]

        def reduce_bucket(bi, gsub, qpair=None, qmult=None):
            """One bucket's gradient reduce; ``gsub`` are the stacked
            grads for ``plan.buckets[bi].pos`` in order.  Unquantized:
            replica-sum then shard (-> (part,)).  Quantized: encode the
            per-replica rows (+ residual), exchange 1-byte codes, sum
            the dequantized rows locally (-> (part, new_gres))."""
            b = plan.buckets[bi]
            j = qpos.get(bi)
            if j is None:
                cat = jnp.concatenate(
                    [_pad_flat(g.reshape(g.shape[0], -1).sum(axis=0),
                               metas[p].padded)
                     for g, p in zip(gsub, b.pos)])
                return (csn(cat, shard),)          # reduce-scatter
            gdt = gsub[0].dtype
            rows = jnp.concatenate(
                [_pad_rows(g, metas[p].padded)
                 for g, p in zip(gsub, b.pos)], axis=1)
            rows = csn(rows, row_sh).astype(f32)   # (nshard, total)
            acc = rows + qpair[0] if ef else rows
            codes, scale = _comm.encode(acc, mode)
            scale = scale * qmult[j]               # chaos: comm.quant
            new_gres = csn(acc - _comm.decode(codes, scale), row_sh) \
                if ef else csn(jnp.zeros_like(acc), row_sh)
            codes_t = csn(codes, col_sh)           # all-to-all, 1B/elem
            scale_r = csn(scale, repl)             # scale exchange
            red = jnp.sum(_comm.decode(codes_t, scale_r),
                          axis=0).astype(gdt)
            return csn(red, shard), new_gres

        def reduce_rest(rmap):
            """The small-group all-reduces and single-param reduces;
            ``rmap`` maps param position -> stacked grads."""
            parts = []
            for g in plan.smalls:
                cat = jnp.concatenate(
                    [rmap[p].reshape(rmap[p].shape[0], -1)
                     for p in g.pos], axis=1).sum(axis=0)
                parts.append(csn(cat, repl))       # one all-reduce
            for p in plan.singles:
                parts.append(csn(_pad_flat(
                    rmap[p].sum(axis=0), metas[p].padded), shard))
            return tuple(parts)

        rest_pos = tuple(sorted(
            {p for g in plan.smalls for p in g.pos}
            | set(plan.singles)))

        def reduce_stage(gstacks, qres=(), qmult=None):
            parts, new_gres = [], []
            for bi, b in enumerate(plan.buckets):
                j = qpos.get(bi)
                out = reduce_bucket(
                    bi, tuple(gstacks[p] for p in b.pos),
                    qres[j] if j is not None else None, qmult)
                parts.append(out[0])
                if j is not None:
                    new_gres.append(out[1])
            parts.extend(reduce_rest({p: gstacks[p] for p in rest_pos}))
            if qbis:
                return tuple(parts), tuple(new_gres)
            return tuple(parts)

        def update_stage(weights, parts, states, hyper_vecs):
            bstates, pstates = states
            pstate_pos = [p for g in plan.smalls for p in g.pos] + \
                list(plan.singles)
            porder = {p: j for j, p in enumerate(sorted(pstate_pos))}
            new_parts, new_b, new_p = [], [], {}
            k = 0
            for bi, b in enumerate(plan.buckets):
                gf = parts[k]
                wf = csn(jnp.concatenate(
                    [_pad_flat(weights[p], metas[p].padded)
                     for p in b.pos]), shard)
                # per-element hyper: each param's scalar repeated over
                # its padded segment via the static segment-id gather
                h = {key: v[b_seg[bi]]
                     for key, v in hyper_vecs.items()}
                nwf, ns = apply_param(opt, wf, gf, bstates[bi],
                                      b.mp, h)
                new_parts.append(csn(nwf, shard))
                new_b.append(_tree_map(lambda x: csn(x, shard), ns))
                k += 1
            for g in plan.smalls:
                cat = parts[k]
                off = 0
                outs = []
                for p in g.pos:
                    m = metas[p]
                    gi = lax.slice(cat, (off,),
                                   (off + m.size,)).reshape(m.shape)
                    off += m.size
                    h = {key: v[p] for key, v in hyper_vecs.items()}
                    nw, ns = apply_param(opt, weights[p], gi,
                                         pstates[porder[p]],
                                         mp_flags[p], h)
                    outs.append(nw.reshape(-1))
                    new_p[p] = _tree_map(lambda x: csn(x, repl), ns)
                new_parts.append(csn(jnp.concatenate(outs), repl))
                k += 1
            for p in plan.singles:
                m = metas[p]
                gf = parts[k]
                wf = csn(_pad_flat(weights[p], m.padded), shard)
                h = {key: v[p] for key, v in hyper_vecs.items()}
                nwf, ns = apply_param(opt, wf, gf,
                                      pstates[porder[p]],
                                      mp_flags[p], h)
                new_parts.append(csn(nwf, shard))
                new_p[p] = _tree_map(lambda x: csn(x, shard), ns)
                k += 1
            new_pstates = tuple(new_p[p] for p in sorted(new_p))
            return tuple(new_parts), (tuple(new_b), new_pstates)

        def gather_stage(parts, weights, qres=()):
            """parts -> per-param full-shape weights (original order);
            `weights` supplies dtypes — and, for quantized buckets, the
            replicated OLD values the encoded delta applies to."""
            out: Dict[int, Any] = {}
            new_wres = []
            k = 0
            for bi, b in enumerate(plan.buckets):
                j = qpos.get(bi)
                if j is None:
                    full = csn(parts[k], repl)      # all-gather
                else:
                    # quantized: gather the encoded weight DELTA, not
                    # the weights — every shard applies the identical
                    # dequantized update to the identical replicated
                    # old flat, so replicas stay bit-identical and the
                    # wire moves 1 byte/elem
                    old_full = jnp.concatenate(
                        [_pad_flat(weights[p], metas[p].padded)
                         .astype(f32) for p in b.pos])
                    delta = parts[k].astype(f32) - csn(old_full, shard)
                    acc = csn(delta.reshape(nsh, -1), row_sh)
                    if ef:
                        acc = acc + qres[j][1]
                    codes, scale = _comm.encode(acc, mode)
                    new_wres.append(
                        csn(acc - _comm.decode(codes, scale), row_sh)
                        if ef else csn(jnp.zeros_like(acc), row_sh))
                    codes_r = csn(codes, repl)      # all-gather, 1B/elem
                    scale_r = csn(scale, repl)      # scale exchange
                    deq = _comm.decode(codes_r, scale_r).reshape(-1)
                    # pin the result replicated: it feeds straight back
                    # as next step's weights input (cached all-gather)
                    full = csn(old_full + deq, repl)
                for p, off, sz in zip(b.pos, b.offsets, b.sizes):
                    m = metas[p]
                    out[p] = lax.slice(full, (off,), (off + m.size,)) \
                        .reshape(m.shape).astype(weights[p].dtype)
                k += 1
            for g in plan.smalls:
                cat = parts[k]
                off = 0
                for p in g.pos:
                    m = metas[p]
                    out[p] = lax.slice(cat, (off,), (off + m.size,)) \
                        .reshape(m.shape).astype(weights[p].dtype)
                    off += m.size
                k += 1
            for p in plan.singles:
                m = metas[p]
                full = csn(parts[k], repl)          # all-gather
                out[p] = lax.slice(full, (0,), (m.size,)) \
                    .reshape(m.shape).astype(weights[p].dtype)
                k += 1
            full_w = tuple(out[p] for p in range(len(metas)))
            if qbis:
                # pin every weight output replicated — the constraint
                # on `full` doesn't survive the slice, and an extra
                # consumer (the mxhealth tail) can tip propagation
                # into dp-sharding an output that the per-replica
                # writeback and the next step's cached executable both
                # need as full copies
                full_w = tuple(csn(w, repl) for w in full_w)
                return full_w, tuple(new_wres)
            return full_w

        return (reduce_stage, update_stage, gather_stage,
                reduce_bucket, reduce_rest, rest_pos)

    def _build_step(self, mp_flags, metas, health_mode=None, qbis=()):
        reduce_stage, update_stage, gather_stage = self._stages(
            mp_flags, metas, qbis)[:3]

        def step(weights, gstacks, states, hyper_vecs, qmult=None):
            if qbis:
                parts, new_gres = reduce_stage(gstacks, states[2],
                                               qmult)
            else:
                parts = reduce_stage(gstacks)
            new_parts, new_s = update_stage(weights, parts,
                                            (states[0], states[1]),
                                            hyper_vecs)
            if qbis:
                new_w, new_wres = gather_stage(new_parts, weights,
                                               states[2])
                new_s = new_s + (tuple(zip(new_gres, new_wres)),)
            else:
                new_w = gather_stage(new_parts, weights)
            if health_mode is None:
                return new_w, new_s
            # mxhealth numerics, inside the SAME mesh program: grad
            # norm-squares per bucket/group (the reduced parts — one
            # NaN'd replica contribution poisons its sum, so the
            # post-reduce view detects it), update/param norm-squares
            # per parameter, and the global nonfinite count.  The
            # reductions run over dp-sharded flats; XLA inserts the
            # cross-shard combine — still one dispatch.
            f32 = jnp.float32
            gn2 = _sq_norms(parts)
            pn2 = _sq_norms(weights)
            un2 = jnp.stack([
                jnp.sum(jnp.square(nw.astype(f32) - w.astype(f32)))
                for nw, w in zip(new_w, weights)]) if weights \
                else jnp.zeros((0,), f32)
            nonfinite = _nonfinite_count(parts)
            if health_mode == "guard":
                ok = nonfinite == 0
                new_w = _tree_select(ok, new_w, weights)
                new_s = _tree_select(ok, new_s, states)
            return new_w, new_s, (gn2, un2, pn2, nonfinite)

        return step

    def _compile(self, sig, args, mp_flags, metas, donate,
                 health_mode=None, qbis=()):
        def build_traced():
            return jax.jit(
                self._build_step(mp_flags, metas, health_mode, qbis),
                donate_argnums=(2,) if donate else ()).trace(*args)

        # named sig view for compile provenance (sig layout: the tuple
        # built in update_multi above)
        components = {"optimizer": sig[0], "statics": sig[1],
                      "mp": sig[2], "metas": sig[3], "plan": sig[4],
                      "flat": sig[5], "donation": sig[6],
                      "layout": sig[7], "health_mode": sig[8],
                      "devices": sig[9], "treedef": sig[10],
                      "avals": sig[11], "quant": sig[12]}
        return _SPMD_CACHE.compile(sig, build_traced, self.optimizer,
                                   components=components, donate=donate)

    # ---- phased variant (tracing only) -----------------------------------
    def _run_phased(self, sig, args, mp_flags, metas, qbis=()):
        """Attribution mode: the same stages as the fused program run
        as three dispatches with spans (`reduce-scatter`,
        `shard-update`, `all-gather`), so ``trace_report`` shows where
        scaling efficiency goes.  Built lazily per signature only while
        tracing is active; the fast path stays ONE executable."""
        def _phase_metric(phase):
            return _ins.training_phase_seconds(phase) \
                if _tracing._ENABLED else None

        weights, gstacks, states, h_vecs = args[:4]
        qmult = args[4] if len(args) > 4 else None
        fns = self._phased.get(sig)
        if fns is None:
            reduce_stage, update_stage, gather_stage = self._stages(
                mp_flags, metas, qbis)[:3]
            fns = self._phased[sig] = (
                jax.jit(reduce_stage), jax.jit(update_stage),
                jax.jit(gather_stage))
        reduce_fn, update_fn, gather_fn = fns
        with _tracing.span("reduce-scatter", cat="training",
                           metric=_phase_metric("reduce-scatter")):
            if qbis:
                parts, new_gres = jax.block_until_ready(
                    reduce_fn(gstacks, states[2], qmult))
            else:
                parts = jax.block_until_ready(reduce_fn(gstacks))
        with _tracing.span("shard-update", cat="training",
                           metric=_phase_metric("shard-update")):
            new_parts, new_s = jax.block_until_ready(
                update_fn(weights, parts, (states[0], states[1]),
                          h_vecs))
        with _tracing.span("all-gather", cat="training",
                           metric=_phase_metric("all-gather")):
            if qbis:
                new_w, new_wres = jax.block_until_ready(
                    gather_fn(new_parts, weights, states[2]))
                new_s = new_s + (tuple(zip(new_gres, new_wres)),)
            else:
                new_w = jax.block_until_ready(
                    gather_fn(new_parts, weights))
        return new_w, new_s

    # ---- overlap variant (MXNET_COMM_OVERLAP) ----------------------------
    def _run_overlap(self, sig, args, mp_flags, metas, qbis):
        """Gradient-ready-order overlap: each bucket's reduce is its
        OWN dispatch, issued in reverse bucket order (buckets pack
        parameters in registration = forward order, so the LAST bucket's
        grads leave the backward first) and left in flight while later
        dispatches queue behind it; one tail program (small/single
        reduces + shard update + weight gather) then consumes the
        in-flight parts.  Nothing here blocks between bucket issues —
        the host races ahead exactly like the async engine's dependency
        queue, and the device overlaps each bucket's collective with the
        next one's staging, targeting step ~= max(compute, comm) rather
        than the sum.  The spans put only DISPATCH time under
        `reduce-scatter`; all wait lands in `shard-update`, so an
        overlapped run's roofline verdict reflects EXPOSED comm (~0 when
        the collectives hide), not total comm."""
        def _phase_metric(phase):
            return _ins.training_phase_seconds(phase) \
                if _tracing._ENABLED else None

        weights, gstacks, states, h_vecs = args[:4]
        qmult = args[4] if len(args) > 4 else None
        plan = self._plan
        qpos = {bi: j for j, bi in enumerate(qbis)}
        fns = self._overlap_fns.get(sig)
        if fns is None:
            (_, update_stage, gather_stage, reduce_bucket,
             reduce_rest, rest_pos) = self._stages(mp_flags, metas,
                                                   qbis)
            bucket_fns = tuple(
                jax.jit(lambda gsub, qpair, qm, bi=bi:
                        reduce_bucket(bi, gsub, qpair, qm))
                for bi in range(len(plan.buckets)))

            def tail(weights, bparts, rmap, states2, h_vecs, qres):
                parts = tuple(bparts) + reduce_rest(rmap)
                new_parts, new_s = update_stage(weights, parts,
                                                states2, h_vecs)
                if qbis:
                    new_w, new_wres = gather_stage(new_parts, weights,
                                                   qres)
                    return new_w, new_s, new_wres
                return gather_stage(new_parts, weights), new_s, ()

            fns = self._overlap_fns[sig] = (bucket_fns, jax.jit(tail),
                                            rest_pos)
        bucket_fns, tail_fn, rest_pos = fns
        nb = len(plan.buckets)
        bparts = [None] * nb
        new_gres = [None] * len(qbis)
        from ..parallel import schedule as _schedule

        with _tracing.span("reduce-scatter", cat="training",
                           metric=_phase_metric("reduce-scatter")):
            for bi in reversed(range(nb)):      # gradient-ready order
                j = qpos.get(bi)
                _schedule.record("spmd.reduce_bucket", "reduce-scatter",
                                 "", int(plan.buckets[bi].total))
                out = bucket_fns[bi](
                    tuple(gstacks[p] for p in plan.buckets[bi].pos),
                    states[2][j] if j is not None else None, qmult)
                bparts[bi] = out[0]
                if j is not None:
                    new_gres[j] = out[1]
        with _tracing.span("shard-update", cat="training",
                           metric=_phase_metric("shard-update")):
            new_w, new_s, new_wres = jax.block_until_ready(tail_fn(
                weights, tuple(bparts),
                {p: gstacks[p] for p in rest_pos},
                (states[0], states[1]), h_vecs,
                states[2] if qbis else ()))
        if qbis:
            new_s = new_s + (tuple(zip(new_gres, new_wres)),)
        return new_w, new_s

    # ---- serialization ---------------------------------------------------
    def get_states(self, dump_optimizer=False):
        """Gather-on-save: the payload holds canonical full-shape host
        state tensors per parameter index — byte-compatible with
        ``Updater.get_states``, so it loads into the per-replica paths
        and onto any mesh shape."""
        payload: Dict[int, Any] = {}
        indices = list(self._plan_indices or ())
        plan = self._plan
        if plan is not None:
            for bi, b in enumerate(plan.buckets):
                if bi not in self._bstate:
                    continue
                host = _tree_map(self._gather_np, self._bstate[bi])
                for p, off, sz in zip(b.pos, b.offsets, b.sizes):
                    i = indices[p]
                    m = self._meta[i]
                    payload[i] = _tree_map(
                        lambda leaf: leaf[off:off + m.size]
                        .reshape(m.shape), host)
            for i, tree in self._pstate.items():
                m = self._meta[i]

                def unflat(leaf, m=m):
                    h = self._gather_np(leaf)
                    if h.shape == m.shape:
                        return h
                    return h.reshape(-1)[:m.size].reshape(m.shape)

                payload[i] = _tree_map(unflat, tree)
        for i, tree in (self._pending or {}).items():
            if i not in payload:  # loaded but never stepped: pass through
                payload[i] = _tree_map(np.asarray, tree)
        # quantization error-feedback residuals travel WITH the
        # optimizer state (dropping them on resume re-introduces the
        # bias the feedback cancels).  Serialized canonically: per-param
        # full-shape arrays, grad side summed over replica rows — the
        # per-row split is a mesh artifact, so this loads onto any mesh
        # shape AND into the per-replica Updater, which stores unknown
        # string keys verbatim and re-emits them (fallback hand-off).
        if self._qstate and plan is not None:
            gsum_d: Dict[int, np.ndarray] = {}
            wflat_d: Dict[int, np.ndarray] = {}
            for bi, (gres, wres) in sorted(self._qstate.items()):
                b = plan.buckets[bi]
                gsum = self._gather_np(gres).sum(axis=0)
                wflat = self._gather_np(wres).reshape(-1)
                for p, off in zip(b.pos, b.offsets):
                    i = indices[p]
                    m = self._meta[i]
                    gsum_d[i] = gsum[off:off + m.size].reshape(m.shape)
                    wflat_d[i] = wflat[off:off + m.size] \
                        .reshape(m.shape)
            payload[_comm.RESIDUAL_KEY] = _comm.canonical_residuals(
                gsum_d, wflat_d, self._quant.mode)
        elif self._pending_q is not None:
            # loaded but never stepped: pass the residuals through
            payload[_comm.RESIDUAL_KEY] = self._pending_q
        if dump_optimizer:
            return pickle.dumps((payload,
                                 self.optimizer.__class__.__name__,
                                 self.optimizer.__dict__.copy()))
        return pickle.dumps(payload)

    def set_states(self, states, ctx=None):
        """Reshard-on-load: the payload re-shards lazily under whatever
        mesh/plan the next step runs on (``ctx`` is ignored — placement
        is global here)."""
        data = pickle.loads(states)
        if isinstance(data, tuple) and len(data) == 3:
            data = data[0]
        data = dict(data)
        self._pending_q = data.pop(_comm.RESIDUAL_KEY, None)
        self._pending = data
        self._bstate.clear()
        self._pstate.clear()
        self._qstate.clear()
        self._mp.clear()
        self._plan = None
        self._plan_indices = None
        self._sig_cache = None
