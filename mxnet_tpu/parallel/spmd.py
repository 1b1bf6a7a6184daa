"""SPMDTrainer — the whole training step as ONE sharded XLA program.

This is the TPU-native scale-out path that subsumes the reference's
Trainer + KVStore pipeline (SURVEY.md CS2/CS5).  Where the reference does
    forward (engine ops) -> backward (engine ops) -> kvstore push/pull
    (NCCL allreduce or ps-lite) -> optimizer update ops
as four separately-scheduled phases, here the entire step —
forward, backward, gradient allreduce, optimizer update — is a single
jitted program over a DeviceMesh.  XLA overlaps the gradient collectives
with remaining backward compute (bucketing for free) and the collectives
ride ICI; parameters/optimizer state stay resident in HBM in their sharded
layout; buffers are donated so updates are in-place.

Grad sync semantics: the loss is a mean over the GLOBAL batch, so the psum
XLA inserts for the 'dp'/'fsdp' axes IS the gradient allreduce — identical
math to KVStore('nccl') push/pull in the reference, one fused program here.
"""
from __future__ import annotations

import functools
import re
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from ..telemetry import instruments as _ins
from ..telemetry import mxgoodput as _goodput
from ..telemetry import mxhealth as _mxhealth
from ..telemetry import tracing as _tracing
from .. import optimizer as opt_mod
from .. import random as rnd
from .mesh import DeviceMesh, current_mesh, layout_key, make_mesh
from .sharding import (ShardingRules, DEFAULT_RULES, shard_batch,
                       zero_state_spec)

__all__ = ["SPMDTrainer", "functional_optimizer", "FunctionalOptimizer",
           "step_compile_stats", "step_programs"]

# mesh-wide fwd+bwd+update executables: routed through the persistent
# compile cache (PR 7) so a same-topology restart warm-starts the step
# without an XLA compile; program-text keys ONLY (the program embeds
# the user's model forward, which no framework version can pin)
_STEP_CACHE = opt_mod.fused.ExecutableCache(
    "parallel.spmd_step", "parallel.spmd._STEP_CACHE", "spmd",
    "spmd-compile", lambda: _ins.spmd_compile_seconds())


def step_compile_stats():
    """SPMDTrainer step-executable builds/loads in this process (same
    shape as optimizer.fused.compile_stats)."""
    return _STEP_CACHE.stats()


_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_MATMUL = re.compile(r"(?<![%\w.\-])(?:convolution|dot)\(")
_HLO_FUSION = re.compile(r"(?<![%\w.\-])fusion\(")


def program_table(hlo_text: str) -> Dict[str, Any]:
    """``{"module", "scoped", "ops"}`` of one compiled program's text
    (``Compiled.as_text()``): ``ops`` maps every instruction outside a
    fused computation, by its name without ``%``, to its ``op_name``
    (the name stack it was traced under: ``jit(mx_train_step)/
    jvp(net0)/dense0/FullyConnected/dot_general``).

    XLA keeps ONE instruction's metadata for a fusion, as a rule its
    root's, and an optimizer update rides in the epilogue of the weight-
    gradient convolution: by the root, whole convolutions would be
    booked to ``mx.update``.  So a fusion whose computation holds a
    convolution or a dot takes THAT instruction's ``op_name``, and its
    own only where it holds none.

    ``scoped`` is false where no ``op_name`` in the text (fused
    instructions included) holds ``mx.update``: an executable from
    before the scopes existed (they are not in JAX's compile-cache key)
    says so instead of reading as zeros."""
    module, comp, scoped = "", None, False
    matmul_of: Dict[str, str] = {}      # fused computation -> op_name
    rows = []       # (computation, instruction, op_name, fusion's callee)
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line[len("HloModule "):].split(",")[0].strip()
        elif line[:1] not in (" ", "}", "") and line.endswith("{"):
            comp = line.split(" (")[0].replace("ENTRY ", "").lstrip("%")
        elif " = " in line and line.startswith("  "):
            lhs, rest = line.split(" = ", 1)
            m = _HLO_OP_NAME.search(rest)
            own = m.group(1) if m else ""
            scoped = scoped or "mx.update" in own
            if own and comp not in matmul_of and _HLO_MATMUL.search(rest):
                matmul_of[comp] = own
            fusion = _HLO_FUSION.search(rest) and _HLO_CALLS.search(rest)
            rows.append((comp, lhs.split()[-1].lstrip("%"), own,
                         fusion.group(1) if fusion else None))
    fused = {callee for _c, _n, _o, callee in rows if callee}
    ops = {name: matmul_of.get(callee, own)
           for comp_, name, own, callee in rows if comp_ not in fused}
    return {"module": module, "scoped": scoped, "ops": ops}


def step_programs() -> List[Dict[str, Any]]:
    """The scope table of every step executable built or loaded in this
    process and still cached, oldest first: ``{"module", "origin":
    "compiled" | "cache", "scoped", "ops": {instruction: op_name}}``
    (see :func:`program_table`).  A device trace names instructions
    (``%fusion.14``) and not scopes; this is the program's own join
    from the one to the other, for whoever reads a profile.

    Built on the first call and memoised per executable: the text of a
    ResNet-50 step is megabytes, so nothing here runs in ``step()`` or
    in set-up."""
    out = []
    for ent in list(_STEP_CACHE.data.values()):
        if ent.program is None:
            ent.program = program_table(ent.fn.as_text())
        out.append({"origin": ent.origin, **ent.program})
    return out


# class qualname + param names + avals do NOT pin the model's forward
# MATH (two same-shape nets can wire differently), so the in-process
# sig carries a per-block token: only the same block instance short-
# circuits the trace; a different block re-lowers and lets the
# persistent tier dedupe by program text.  Weak-keyed so a dead block
# releases its executables' cache slot identity.
import itertools as _itertools
import threading as _threading
import weakref as _weakref

# distinct input SHAPES a trainer keeps hot executables for (the
# evicted ones stay reachable through _STEP_CACHE / the persistent
# tier — eviction costs a sig rebuild, never an XLA compile)
_STEP_FNS_MAX = 16

_BLOCK_TOKENS: "_weakref.WeakKeyDictionary" = _weakref.WeakKeyDictionary()
_BLOCK_TOKENS_LOCK = _threading.Lock()
_BLOCK_TOKEN_NEXT = _itertools.count()


def _block_token(block) -> int:
    with _BLOCK_TOKENS_LOCK:
        tok = _BLOCK_TOKENS.get(block)
        if tok is None:
            tok = next(_BLOCK_TOKEN_NEXT)
            _BLOCK_TOKENS[block] = tok
        return tok


# ---------------------------------------------------------------------------
# functional optimizers — pure (w, g, state, lr, t) -> (w', state') built on
# the same registered update ops the imperative Optimizer classes use
# (ops/optimizer_ops.py; ref src/operator/optimizer_op.cc)
# ---------------------------------------------------------------------------

class FunctionalOptimizer:
    def __init__(self, n_state: int, update: Callable, wd: float = 0.0,
                 clip_gradient: float = -1.0):
        self.n_state = n_state
        self._update = update
        self.wd = wd
        self.clip_gradient = clip_gradient
        # set from Optimizer.multi_precision by functional_optimizer()
        self.multi_precision = False

    def needs_master(self, value) -> bool:
        """Under Optimizer(multi_precision=True), low-precision params get
        fp32 optimizer state AND an fp32 master weight carried as the LAST
        element of the state tuple — the reference's mp_sgd_* / mp_adam
        weight32 state (ref: optimizer_op.cc MP_SGD kernels).  Without the
        master, updates below one bf16 ulp round away (reference non-mp
        behavior, the default there too); mp costs ~4% step time on the
        ResNet-50 bench."""
        return (self.multi_precision
                and value.dtype in (jnp.bfloat16, jnp.float16))

    def init(self, value: jax.Array) -> Tuple[jax.Array, ...]:
        # state dtype is FIXED from step 0 (update math runs in fp32; a
        # bf16 state that flipped to fp32 after step 1 would retrace)
        if self.needs_master(value):
            return tuple(jnp.zeros(value.shape, jnp.float32)
                         for _ in range(self.n_state)) + (
                value.astype(jnp.float32),)
        return tuple(jnp.zeros_like(value) for _ in range(self.n_state))

    def apply(self, value, grad, state, lr, t, lr_mult=1.0, wd_mult=1.0):
        return self._update(value, grad, state, lr * lr_mult,
                            self.wd * wd_mult, self.clip_gradient, t)


def _global_put(v, sh):
    """device_put that also works on multi-process meshes whose backend
    has no cross-host transfers (CPU+gloo).

    Host values: every process holds the same global value (the launcher
    contract), so each device takes its shard locally via
    make_array_from_callback.  Values that are ALREADY global jax arrays
    (e.g. optimizer state computed from global params) cannot be pulled
    to host; they reshard through a jitted identity, which moves data
    with in-program collectives instead of host transfers."""
    if getattr(sh, "is_fully_addressable", True):
        return jax.device_put(v, sh)
    if isinstance(v, jax.Array) and not v.is_fully_addressable:
        if v.sharding == sh:
            return v
        return jax.jit(lambda x: x, out_shardings=sh)(v)
    v = np.asarray(v)
    return jax.make_array_from_callback(v.shape, sh, lambda idx: v[idx])


def _pure(name):
    from ..ops.registry import apply_pure

    return functools.partial(apply_pure, name)


def functional_optimizer(opt) -> FunctionalOptimizer:
    """Build the pure update for an Optimizer instance (or name)."""
    if isinstance(opt, str):
        opt = opt_mod.create(opt)
    fo = _functional_optimizer_impl(opt)
    fo.multi_precision = bool(getattr(opt, "multi_precision", False))
    return fo


def _functional_optimizer_impl(opt) -> FunctionalOptimizer:
    wd = float(opt.wd)
    clip = float(opt.clip_gradient) if opt.clip_gradient is not None else -1.0
    kind = type(opt).__name__

    if kind in ("SGD", "NAG"):
        momentum = float(getattr(opt, "momentum", 0.0))
        if momentum == 0.0:
            upd = _pure("sgd_update")

            def update(w, g, s, lr, wd_, c, t):
                return upd(w, g, lr=lr, wd=wd_, clip_gradient=c), ()
            return FunctionalOptimizer(0, update, wd, clip)
        op_name = "nag_mom_update" if kind == "NAG" else "sgd_mom_update"
        upd = _pure(op_name)

        def update(w, g, s, lr, wd_, c, t):
            nw, nm = upd(w, g, s[0], lr=lr, momentum=momentum, wd=wd_,
                         clip_gradient=c)
            return nw, (nm,)
        return FunctionalOptimizer(1, update, wd, clip)

    if kind == "Adam":
        b1, b2, eps = float(opt.beta1), float(opt.beta2), float(opt.epsilon)
        upd = _pure("adam_update")

        def update(w, g, s, lr, wd_, c, t):
            # bias correction (ref: Adam.update computes coef host-side)
            tt = t.astype(jnp.float32)
            coef = jnp.sqrt(1.0 - b2 ** tt) / (1.0 - b1 ** tt)
            nw, nm, nv = upd(w, g, s[0], s[1], lr=1.0, beta1=b1, beta2=b2,
                             epsilon=eps, wd=wd_, clip_gradient=c)
            # adam_update applies lr directly; redo with scaled lr instead
            return w + (nw - w) * (lr * coef), (nm, nv)
        return FunctionalOptimizer(2, update, wd, clip)

    if kind == "RMSProp":
        g1 = float(getattr(opt, "gamma1", 0.9))
        g2 = float(getattr(opt, "gamma2", 0.9))
        eps = float(getattr(opt, "epsilon", 1e-8))
        if getattr(opt, "centered", False):
            upd = _pure("rmspropalex_update")

            def update(w, g, s, lr, wd_, c, t):
                nw, nn, ng, ndel = upd(w, g, s[0], s[1], s[2], lr=lr,
                                       gamma1=g1, gamma2=g2, epsilon=eps,
                                       wd=wd_, clip_gradient=c)
                return nw, (nn, ng, ndel)
            return FunctionalOptimizer(3, update, wd, clip)
        upd = _pure("rmsprop_update")

        def update(w, g, s, lr, wd_, c, t):
            nw, nn = upd(w, g, s[0], lr=lr, gamma1=g1, epsilon=eps, wd=wd_,
                         clip_gradient=c)
            return nw, (nn,)
        return FunctionalOptimizer(1, update, wd, clip)

    if kind == "AdaGrad":
        eps = float(getattr(opt, "float_stable_eps",
                            getattr(opt, "eps",
                                    getattr(opt, "epsilon", 1e-7))))
        upd = _pure("adagrad_update")

        def update(w, g, s, lr, wd_, c, t):
            nw, nh = upd(w, g, s[0], lr=lr, epsilon=eps, wd=wd_,
                         clip_gradient=c)
            return nw, (nh,)
        return FunctionalOptimizer(1, update, wd, clip)

    if kind in ("Signum", "SignSGD"):
        momentum = float(getattr(opt, "momentum", 0.0))
        if momentum == 0.0:
            upd = _pure("signsgd_update")

            def update(w, g, s, lr, wd_, c, t):
                return upd(w, g, lr=lr, wd=wd_, clip_gradient=c), ()
            return FunctionalOptimizer(0, update, wd, clip)
        upd = _pure("signum_update")

        def update(w, g, s, lr, wd_, c, t):
            nw, nm = upd(w, g, s[0], lr=lr, momentum=momentum, wd=wd_,
                         clip_gradient=c)
            return nw, (nm,)
        return FunctionalOptimizer(1, update, wd, clip)

    if kind == "AdaDelta":
        rho = float(opt.rho)
        eps = float(opt.epsilon)
        upd = _pure("adadelta_update")

        def update(w, g, s, lr, wd_, c, t):
            nw, na, nd = upd(w, g, s[0], s[1], lr=lr, rho=rho, epsilon=eps,
                             wd=wd_, clip_gradient=c)
            return nw, (na, nd)
        return FunctionalOptimizer(2, update, wd, clip)

    if kind == "Adamax":
        b1, b2 = float(opt.beta1), float(opt.beta2)
        upd = _pure("adamax_update")

        def update(w, g, s, lr, wd_, c, t):
            tt = t.astype(jnp.float32)
            lr_t = lr / (1.0 - b1 ** tt)
            nw, nm, nv = upd(w, g, s[0], s[1], lr=lr_t, beta1=b1, beta2=b2,
                             wd=wd_, clip_gradient=c)
            return nw, (nm, nv)
        return FunctionalOptimizer(2, update, wd, clip)

    if kind == "Ftrl":
        lamda1 = float(opt.lamda1)
        beta = float(opt.beta)
        upd = _pure("ftrl_update")

        def update(w, g, s, lr, wd_, c, t):
            nw, nz, nn = upd(w, g, s[0], s[1], lr=lr, lamda1=lamda1,
                             beta=beta, wd=wd_, clip_gradient=c)
            return nw, (nz, nn)
        return FunctionalOptimizer(2, update, wd, clip)

    raise MXNetError(
        f"no functional form for optimizer {kind}; supported: SGD, NAG, "
        "Adam, RMSProp, AdaGrad, Signum, SignSGD, AdaDelta, Adamax, Ftrl")


# ---------------------------------------------------------------------------
# SPMDTrainer
# ---------------------------------------------------------------------------

class SPMDTrainer:
    """One-program-per-step trainer over a DeviceMesh.

    Parameters
    ----------
    block : an initialized gluon (Hybrid)Block — the model.
    loss : callable applied as ``loss(out, *labels)`` inside the trace;
        a gluon Loss block works (its forward runs traced).
    optimizer : name or mxnet_tpu Optimizer instance.
    mesh : DeviceMesh (defaults to the active one, else all-devices 'dp').
    rules : ShardingRules mapping parameter names -> PartitionSpec.
    batch_spec / label_spec : PartitionSpec for each data / label input
        (defaults: dim 0 over dp/fsdp, rest replicated).

    Usage::

        mesh = parallel.make_mesh(dp=4, tp=2)
        with mesh:
            trainer = parallel.SPMDTrainer(net, loss, "sgd",
                                           {"learning_rate": 0.1})
            for data, label in batches:
                l = trainer.step(data, label)      # async; one XLA program
        trainer.sync_to_block()                    # params back to gluon
    """

    def __init__(self, block, loss: Callable, optimizer="sgd",
                 optimizer_params: Optional[dict] = None,
                 mesh: Optional[DeviceMesh] = None,
                 rules: ShardingRules = DEFAULT_RULES,
                 batch_spec: Optional[Sequence] = None,
                 label_spec: Optional[Sequence] = None,
                 n_labels: int = 1,
                 donate: bool = True,
                 remat: bool = False):
        #: remat: gradient mirroring for the fused train step — each
        #: sub-block becomes a jax.checkpoint segment, so the backward
        #: recomputes its activations instead of holding them in HBM
        #: across the whole fwd+bwd+update program
        #: (ref: MXNET_BACKWARD_DO_MIRROR role)
        self.remat = bool(remat)
        self.block = block
        self.loss = loss
        self.mesh = mesh or current_mesh() or make_mesh()
        self.rules = rules
        self._batch_spec = batch_spec
        self._label_spec = label_spec
        self.n_labels = n_labels
        self._donate = donate

        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        elif optimizer_params:
            raise MXNetError("optimizer_params must be None when optimizer "
                             "is an instance")
        self._optimizer = optimizer
        self._fopt = functional_optimizer(optimizer)

        self._plist = sorted(block.collect_params().items())
        self._mults = {
            n: (float(p.lr_mult), float(p.wd_mult)) for n, p in self._plist}
        self._trainable = {n: p.grad_req != "null" for n, p in self._plist}

        # shard parameters onto the mesh per the rules; optimizer
        # states get the ZeRO-1 layout (MXNET_ZERO_STATES, default on):
        # states of a dp-replicated parameter shard across the data
        # axes, so XLA turns the grad psum into reduce-scatter + the
        # weight refresh into all-gather (arXiv:2004.13336) and each
        # device holds 1/N of the state bytes
        from ..util import env as _envmod

        self._zero = bool(_envmod.get_bool("MXNET_ZERO_STATES"))
        self.params: Dict[str, jax.Array] = {}
        self._shardings: Dict[str, NamedSharding] = {}
        self._state_shardings: Dict[str, NamedSharding] = {}
        for n, p in self._plist:
            v = p.data().data
            spec = rules.spec_for(n, v.shape, self.mesh)
            sh = NamedSharding(self.mesh.mesh, spec)
            self._shardings[n] = sh
            sspec = zero_state_spec(
                spec, v.shape, self.mesh,
                min_size=_envmod.get_int("MXNET_ZERO_MIN_SIZE")) \
                if self._zero else spec
            self._state_shardings[n] = NamedSharding(self.mesh.mesh, sspec)
            self.params[n] = _global_put(v, sh)
        self.opt_state = {
            n: tuple(_global_put(s, self._state_shardings[n])
                     for s in self._fopt.init(v))
            for n, v in self.params.items() if self._trainable[n]}

        # replicated trainable params fuse into one flat update kernel per
        # (lr_mult, wd_mult) group; mesh-sharded params stay per-parameter
        from ..util import env

        self._has_master = {
            n: self._fopt.needs_master(v) for n, v in self.params.items()
            if self._trainable[n]}
        groups: Dict[Tuple, List[str]] = {}
        self._per_param: List[str] = []
        # default OFF: profiling showed the 1-D concat destroys conv-weight
        # tiled layouts and donation aliasing, costing far more than the
        # per-param fusions it merges (162ms vs 113ms ResNet-50 step); the
        # per-param updates fuse into the wgrad epilogue anyway
        flat_on = env.get_bool("MXNET_FUSED_OPTIMIZER")
        for n, p in self._plist:
            if not self._trainable[n]:
                continue
            if flat_on and self._shardings[n].is_fully_replicated:
                # dtype in the key: groups must be homogeneous (concat
                # would silently promote, and master-weight handling
                # differs between bf16 and fp32 params)
                key = self._mults[n] + (str(self.params[n].dtype),)
                groups.setdefault(key, []).append(n)
            else:
                self._per_param.append(n)
        self._flat_groups = [(tuple(names), lm, wm)
                             for (lm, wm, _dt), names in sorted(groups.items())]

        # per-shape fast path over _STEP_CACHE; LRU-bounded because
        # each value strong-refs a whole-step executable — an unbounded
        # dict would outlive _STEP_CACHE's own eviction (ragged last
        # batches / variable seq-len mint a new shape per epoch)
        self._step_fns: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._fwd_fn = None
        self._param_by_name = {n: p for n, p in self._plist}
        self._t = 0

    # ---- the pure step ---------------------------------------------------
    def _build_pure(self):
        plist = self._plist
        block, loss, fopt = self.block, self.loss, self._fopt
        mults, trainable = self._mults, self._trainable
        trainer = self

        from ..gluon.block import ActiveTrace

        name_of = {id(p): n for n, p in plist}

        def mx_train_step(params, opt_state, inputs, labels, key, lr, t):
            def loss_fn(pv):
                trace = ActiveTrace(
                    {id(p): pv[n] for n, p in plist}, train=True)
                trace.mirror = trainer.remat  # per-sub-block segments
                # the trainer's mesh scope is active for the whole
                # traced step, wherever step() was called from — code
                # consulting current_mesh() at trace time (ring/ulysses
                # attention, the fused-conv shard_map plan, sharding
                # constraints) sees THIS mesh, not the caller's ambient
                # scope
                with trainer.mesh, trace, \
                        rnd.key_provider(rnd.KeyProvider(key)):
                    out = block.forward(*inputs)
                    outs = out if isinstance(out, (list, tuple)) else (out,)
                    with jax.named_scope("mx.loss"):
                        l = loss(outs[0], *labels)
                lval = jnp.mean(l if not isinstance(l, (list, tuple))
                                else l[0])
                # aux (BatchNorm moving stats) keyed BY NAME in the traced
                # outputs — no side-channel ordering that a retrace could
                # skew (round-1 weak #10)
                aux_named = {name_of[id(p)]: v for p, v in
                             zip(trace.aux_params, trace.aux_values)}
                return lval, aux_named

            (lval, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_params, new_state = {}, {}
            for n, _ in plist:
                if not trainable[n]:
                    new_params[n] = params[n]

            # Fused flat update: replicated trainable params concatenate
            # into ONE elementwise update kernel per (lr_mult, wd_mult)
            # group instead of one tiny fusion per parameter — profiling
            # showed the per-parameter tail costing ~17% of the ResNet-50
            # step.  Mesh-sharded params keep the per-parameter path (a
            # concat across different shardings would force gathers).
            def apply_one(n, w, g, state, lm, wm):
                """Update one (possibly flat-concatenated) weight; the fp32
                master weight, when present, is the last state element and
                is what the update math runs on (mp_* semantics)."""
                if trainer._has_master[n]:
                    w32, st = state[-1], state[:-1]
                    nw32, ns = fopt.apply(w32, g, st, lr, t,
                                          lr_mult=lm, wd_mult=wm)
                    return nw32.astype(w.dtype), ns + (nw32,)
                nw, ns = fopt.apply(w, g, state, lr, t,
                                    lr_mult=lm, wd_mult=wm)
                return nw.astype(w.dtype), tuple(
                    sv.astype(state[i].dtype) for i, sv in enumerate(ns))

            with jax.named_scope("mx.update"):
                for names, lm, wm in trainer._flat_groups:
                    # concat in NATIVE dtypes — upcasts happen in-register
                    # inside the one fused update kernel, never materialized
                    n_st = len(opt_state[names[0]])
                    fw = jnp.concatenate(
                        [params[n].reshape(-1) for n in names])
                    fg = jnp.concatenate(
                        [grads[n].reshape(-1) for n in names])
                    fs = tuple(
                        jnp.concatenate(
                            [opt_state[n][i].reshape(-1) for n in names])
                        for i in range(n_st))
                    nw, ns = apply_one(names[0], fw, fg, fs, lm, wm)
                    off = 0
                    for n in names:
                        p = params[n]
                        sz = int(np.prod(p.shape)) if p.shape else 1
                        sl = lax.slice(nw, (off,), (off + sz,))
                        new_params[n] = sl.reshape(p.shape).astype(p.dtype)
                        new_state[n] = tuple(
                            lax.slice(s, (off,), (off + sz,))
                            .reshape(p.shape).astype(opt_state[n][i].dtype)
                            for i, s in enumerate(ns))
                        off += sz
                for n in trainer._per_param:
                    lm, wm = mults[n]
                    new_params[n], new_state[n] = apply_one(
                        n, params[n], grads[n], opt_state[n], lm, wm)
            # aux state (BatchNorm moving stats) accumulates across steps:
            # fold the traced updates back into the param dict so the next
            # step's trace reads them (stop_gradient — not a learnable path)
            for n, v in aux.items():
                new_params[n] = lax.stop_gradient(v).astype(params[n].dtype)
            return new_params, new_state, lval, aux

        return mx_train_step

    def _opt_static_fingerprint(self) -> Tuple:
        """Hashable fingerprint of the optimizer attrs BAKED into the
        traced program (wd, momentum, betas, ... — read at functional-
        optimizer construction).  lr and rescale_grad stay out: they
        are traced arguments and must never force a recompile."""
        skip = {"lr", "rescale_grad", "num_update", "begin_num_update"}
        return tuple(sorted(
            (k, v) for k, v in self._optimizer.__dict__.items()
            if k not in skip and isinstance(v, (int, float, bool, str))))

    def _get_step(self, args, ikey):
        if ikey not in self._step_fns:
            mesh = self.mesh
            psh = self._shardings
            state_sh = {n: tuple(self._state_shardings[n] for _ in s)
                        for n, s in self.opt_state.items()}
            repl = NamedSharding(mesh.mesh, P())
            jitted = jax.jit(
                self._build_pure(),
                in_shardings=(psh, state_sh, None, None, repl, repl, repl),
                out_shardings=(psh, state_sh, repl, None),
                donate_argnums=(0, 1) if self._donate else ())
            cell = {}

            def build_lowered():
                if "l" not in cell:
                    cell["l"] = jitted.lower(*args)
                return cell["l"]

            leaves, treedef = jax.tree_util.tree_flatten(args)
            block = self.block
            # the in-process signature pins everything the closure
            # bakes in: the block INSTANCE (class+param names don't pin
            # forward math), optimizer statics, mults, layout, and the
            # concrete devices (an executable is bound to its device
            # assignment — two trainers on disjoint subsets of the same
            # topology must not share one); the PERSISTENT key adds the
            # lowered program text, which pins the actual model code
            sig = ("spmd-train-step", _block_token(block),
                   f"{type(block).__module__}.{type(block).__qualname__}",
                   tuple(n for n, _ in self._plist),
                   tuple(sorted(self._mults.items())),
                   type(self._optimizer), self._opt_static_fingerprint(),
                   tuple(self._flat_groups), self.remat,
                   layout_key(self.mesh),
                   tuple(str(d) for d in mesh.devices),
                   self._zero, self._donate,
                   treedef,
                   tuple(opt_mod.fused._leaf_aval(x) for x in leaves))
            fn = _STEP_CACHE.lookup(sig)
            if fn is None:
                # named sig view for compile provenance (same order as
                # the sig tuple above)
                components = {
                    "block": sig[1:4], "mults": sig[4],
                    "optimizer": sig[5], "statics": sig[6],
                    "flat_groups": sig[7], "remat": sig[8],
                    "layout": sig[9], "devices": sig[10],
                    "zero": sig[11], "donation": sig[12],
                    "treedef": sig[13], "avals": sig[14]}
                fn = _STEP_CACHE.compile(sig, build_lowered,
                                         self._optimizer,
                                         alias_ok=False,
                                         components=components,
                                         donate=self._donate)
            # per-trainer fast path keyed by input avals: a batch-shape
            # change rebuilds (AOT does not silently retrace), a repeat
            # shape is one dict hit.  The executable's static cost
            # rides along for mxprof's whole-step MFU.
            self._step_fns[ikey] = (fn, _STEP_CACHE.cost(sig))
            while len(self._step_fns) > _STEP_FNS_MAX:
                self._step_fns.popitem(last=False)
        else:
            self._step_fns.move_to_end(ikey)
        return self._step_fns[ikey]

    # ---- data movement ---------------------------------------------------
    def _spec_sharding(self, spec, arr):
        if spec is None:
            return shard_batch(self.mesh, extra_dims=arr.ndim - 1)
        return NamedSharding(self.mesh.mesh, spec)

    def _place(self, x, spec):
        v = x.data if isinstance(x, NDArray) else jnp.asarray(x)
        return _global_put(v, self._spec_sharding(spec, v))

    # ---- public API ------------------------------------------------------
    def step(self, *args) -> NDArray:
        """Run one training step on a global batch; returns the loss
        (async — only .asnumpy() blocks).  The last ``n_labels`` args are
        labels, the rest model inputs.

        The call and its five phases are host spans in a ``jax.profiler``
        trace (``mx.step`` and ``mx.step.place`` / ``.scalars`` /
        ``.get_step`` / ``.dispatch`` / ``.rebind``, each carrying the
        step number), on the clock the device lines are on; outside a
        profiler session an annotation costs under a microsecond."""
        if _goodput._ACTIVE:
            # first post-resume step entry closes the goodput
            # preemption-recovery window (one falsy check when off)
            _goodput.on_step_entry()
        span, n = _tracing.annotation, self._t + 1
        with span("mx.step", step=n):
            with span("mx.step.place", step=n):
                n_lab = self.n_labels
                if n_lab == 0:
                    inputs, labels = args, ()
                else:
                    inputs, labels = args[:-n_lab], args[-n_lab:]
                bspecs = self._batch_spec or [None] * len(inputs)
                lspecs = self._label_spec or [None] * len(labels)
                # one line each: MXLINT_BASELINE.json knows them by their text
                ivals = tuple(self._place(x, s) for x, s in zip(inputs, bspecs))
                lvals = tuple(self._place(x, s) for x, s in zip(labels, lspecs))
            with span("mx.step.scalars", step=n):
                self._t += 1
                self._optimizer._update_count(0)
                lr = jnp.asarray(self._optimizer.learning_rate, jnp.float32)
                t = jnp.asarray(self._t, jnp.int32)
                key = rnd.next_key()
            with span("mx.step.get_step", step=n):
                args = (self.params, self.opt_state, ivals, lvals, key, lr, t)
                ikey = tuple((tuple(v.shape), str(v.dtype))
                             for v in ivals + lvals)
                step, step_cost = self._get_step(args, ikey)
            with span("mx.step.dispatch", step=n):
                out = self._dispatch(step, step_cost, args)
            with span("mx.step.rebind", step=n):
                self.params, self.opt_state, lval, aux = out
                # rebind aux state (BatchNorm moving stats) by parameter NAME
                for name, v in aux.items():
                    self._param_by_name[name].data()._data = v
                if _mxhealth._ACTIVE:
                    # loss-spike detection feed: the device scalar is handed
                    # off as-is; the monitor's fetch thread syncs it, the
                    # step path never does
                    _mxhealth.observe_loss(lval)
                from ..context import current_context

                return NDArray(lval, ctx=current_context())

    def _dispatch(self, step, step_cost, args):
        """The executable call, inside the telemetry that times it."""
        if not _tracing.active():
            return step(*args)
        if _tracing._ENABLED:
            for ax, size in self.mesh.axis_sizes.items():
                _ins.step_layout_axis_size(ax).set(size)
            factor = 1
            if self._zero:
                for ax in ("dp", "fsdp"):
                    factor *= self.mesh.size(ax)
            _ins.step_state_shard_factor().set(factor)
        with _tracing.span("spmd-step", cat="training",
                           metric=_ins.training_phase_seconds(
                               "spmd-step")
                           if _tracing._ENABLED else None):
            out = step(*args)
        snk = _tracing._SINK
        if snk is not None and step_cost is not None:
            # whole-step program: forward+backward+update FLOPs in
            # one executable — the gspmd path's MFU counts
            # everything.  AFTER the span: this step's record only
            # closes when the NEXT spmd-step span arrives, so flops
            # reported before the span would land one record early
            # (and double the first closed record's MFU).
            snk.on_flops(_STEP_CACHE.site, step_cost)
        return out

    def step_executable(self):
        """The compiled step program of the input shapes stepped last (a
        ``jax.stages.Compiled``: ``memory_analysis()``, ``as_text()``,
        ``cost_analysis()``), or None before the first step."""
        if not self._step_fns:
            return None
        return next(reversed(self._step_fns.values()))[0]

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def save_checkpoint(self, path: str):
        """Sharded (tensorstore) checkpoint of params + optimizer state +
        step; resumable on a different mesh (see parallel.checkpoint)."""
        from .checkpoint import save_sharded

        save_sharded(path, self)

    def load_checkpoint(self, path: str):
        from .checkpoint import load_sharded

        load_sharded(path, self)

    def sync_to_block(self):
        """Copy the (sharded) params back into the gluon Parameters —
        call before save_parameters()/export()."""
        for n, p in self._plist:
            v = self.params[n]
            gathered = jax.device_get(v)
            for c in list(p._data or {}):
                p._data[c]._data = jnp.asarray(gathered)

    def forward(self, *inputs) -> NDArray:
        """Sharded inference with the trainer's current params."""
        if self._fwd_fn is None:
            from ..gluon.block import ActiveTrace

            plist = self._plist
            block = self.block
            trainer = self

            def fwd(params, ivals, key):
                trace = ActiveTrace({id(p): params[n] for n, p in plist},
                                    train=False)
                # the trainer's mesh scope is active for the whole
                # traced step, wherever step() was called from — code
                # consulting current_mesh() at trace time (ring/ulysses
                # attention, the fused-conv shard_map plan, sharding
                # constraints) sees THIS mesh, not the caller's ambient
                # scope
                with trainer.mesh, trace, \
                        rnd.key_provider(rnd.KeyProvider(key)):
                    out = block.forward(*ivals)
                return out

            self._fwd_fn = jax.jit(fwd)
        bspecs = self._batch_spec or [None] * len(inputs)
        ivals = tuple(self._place(x, s) for x, s in zip(inputs, bspecs))
        out = self._fwd_fn(self.params, ivals, rnd.next_key())
        from ..context import current_context

        ctx = current_context()
        return jax.tree_util.tree_map(lambda v: NDArray(v, ctx=ctx), out)
