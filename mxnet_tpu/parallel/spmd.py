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

__all__ = ["SPMDTrainer", "step_compile_stats", "step_programs"]

# mesh-wide fwd+bwd+update executables: routed through the persistent
# compile cache (PR 7) so a same-topology restart warm-starts the step
# without an XLA compile; program-text keys ONLY (the program embeds
# the user's model forward, which no framework version can pin)
_STEP_CACHE = opt_mod.fused.ExecutableCache(
    "parallel.spmd_step", "parallel.spmd._STEP_CACHE", "spmd",
    "spmd-compile", lambda: _ins.spmd_compile_seconds())


def step_compile_stats():
    """SPMDTrainer step-executable builds/loads in this process (same
    shape as optimizer.fused.compile_stats): `seconds_total` is
    `trace_seconds` + `lower_seconds` + `backend_seconds` (+
    `audit_seconds`, where the mxir audit is on): the set-up phases
    `mx.build.*` of the site."""
    return _STEP_CACHE.stats()


_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_MATMUL = re.compile(r"(?<![%\w.\-])(?:convolution|dot)\(")
_HLO_FUSION = re.compile(r"(?<![%\w.\-])fusion\(")
# `<type> <opcode>(`: a type ends in `]`, `}` or, a tuple's, `)`
_HLO_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_HLO_DIMS = re.compile(r"\w+\[([\d,]*)\]")
_HLO_CONTRACTED = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_HLO_DIM_LABELS = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
_HLO_WINDOW = re.compile(r"window=\{([^}]*)\}")
_HLO_NUMBERING = re.compile(r"[.\d]+$")


def _whole_instructions(hlo_text: str):
    """The text's lines, with an instruction that XLA printed over
    several lines joined into one: a frontend attribute may hold a
    newline (splash attention's `kernel_metadata={` is followed by one),
    and the instruction's `metadata={op_name=...}` then sits on the last
    of its lines."""
    depth, whole = 0, []
    for line in hlo_text.splitlines():
        if depth > 0:
            whole[-1] += line
            depth += line.count("{") - line.count("}")
            continue
        whole.append(line)
        if line.startswith("  ") and " = " in line and line.endswith("{"):
            depth = line.count("{") - line.count("}")
    return whole


def _pass_of(name: str) -> str:
    """Which pass a name stack belongs to.  The forward done again under
    remat is traced under `checkpoint/rematted_computation/` inside the
    backward's `transpose(`, so it is asked for first."""
    if "rematted_computation" in name:
        return "recomputed"
    if "transpose(" in name:
        return "backward"
    if "jvp(" in name:
        return "forward"
    if "mx.update" in name:
        return "update"
    return "other"


def _dims(type_text: str):
    m = _HLO_DIMS.match(type_text)
    return [int(d) for d in m.group(1).split(",") if d] if m else None


def _operand_types(text: str, start: int, types: Dict[str, str]):
    """The types of an instruction's operands, `text[start]` being the
    first character after the opcode's `(`: printed in place
    (`f32[4,8]{1,0} %x`) or, inside a computation, by name alone, and
    then looked up among the computation's own instructions."""
    depth, cut, out = 0, start, []
    for i in range(start, len(text)):
        c = text[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            if depth == 0:
                out.append(text[cut:i])
                break
            depth -= 1
        elif c == "," and depth == 0:
            out.append(text[cut:i])
            cut = i + 1
    found = []
    for operand in out:
        kind, _, name = operand.strip().rpartition(" ")
        found.append(kind or types.get(name.lstrip("%"), ""))
    return found


def _product_flops(opcode: str, out_type: str, rest: str, start: int,
                   types: Dict[str, str]) -> int:
    """2 x the multiply-adds of one `dot` or `convolution`, from the
    shapes in the text; 0 where a shape cannot be read (a floor counts
    less, never more).  A dot: the output's elements x the contracted
    sizes.  A convolution: output batch x output features x the
    kernel's input-feature dimension x, a spatial dimension, the
    (output position, tap) pairs that can meet an input element: no
    more than outputs x taps, than inputs x taps (the zeros `lhs_dilate`
    puts between the elements of a strided convolution's data gradient
    are not multiplied) or than inputs x outputs (XLA writes a 1x1
    convolution as a 56x56 window sliding over one padded element).
    Padding of the ordinary kind counts, as the configurations count
    it."""
    out, operands = _dims(out_type), _operand_types(rest, start, types)
    try:
        lhs, rhs = _dims(operands[0]), _dims(operands[1])
        macs = 1
        if opcode == "dot":
            for d in out:
                macs *= d
            for k in _HLO_CONTRACTED.search(rest).group(1).split(","):
                if k:
                    macs *= lhs[int(k)]
            return 2 * macs
        lhs_at, rhs_at, out_at = _HLO_DIM_LABELS.search(rest).groups()
        window = _HLO_WINDOW.search(rest)
        taps = dict(f.split("=") for f in window.group(1).split()).get(
            "size", "").split("x") if window else []
        macs = out[out_at.index("b")] * out[out_at.index("f")] \
            * rhs[rhs_at.index("i")]
        for d in range(sum(c.isdigit() for c in out_at)):
            n_out = out[out_at.index(str(d))]
            n_in = lhs[lhs_at.index(str(d))]
            n_taps = int(taps[d]) if d < len(taps) and taps[d] else 1
            macs *= min(n_out * n_taps, n_in * n_taps, n_in * n_out)
        return 2 * macs
    except (AttributeError, IndexError, TypeError, ValueError):
        return 0        # a form this reader does not know: count nothing


def program_table(hlo_text: str) -> Dict[str, Any]:
    """``{"module", "scoped", "ops", "instructions"}`` of one compiled
    program's text (``Compiled.as_text()``): ``ops`` maps every
    instruction outside a fused computation, by its name without ``%``,
    to its ``op_name`` (the name stack it was traced under:
    ``jit(mx_train_step)/jvp(net0)/dense0/FullyConnected/dot_general``).

    XLA keeps ONE instruction's metadata for a fusion, as a rule its
    root's, and an optimizer update rides in the epilogue of the weight-
    gradient convolution: by the root, whole convolutions would be
    booked to ``mx.update``.  So a fusion whose computation holds a
    convolution or a dot takes THAT instruction's ``op_name``, and its
    own only where it holds none.

    ``instructions`` says, under the same keys, what each of them is:

    * ``opcode``: the HLO opcode (``fusion``, ``custom-call``, ``while``);
    * ``scopes``: every distinct name stack the instruction holds, its
      own first and then those of the fused computations it calls
      (through fusions nested in them; not the body of a ``while``, a
      ``conditional`` or a ``call``, whose instructions have rows of
      their own), the JAX primitive dropped, in the text's order: the
      names rule B leaves out of ``ops``;
    * ``pass``: ``forward``, ``recomputed`` (the forward done again
      under remat), ``backward``, ``update`` or ``other``, of the name
      ``ops`` books the instruction to; ``passes``: the same over every
      entry of ``scopes``, sorted (``["other"]`` where there is none);
    * ``flops``: 2 x the multiply-adds of every ``dot`` and
      ``convolution`` within the same reach, for ONE execution
      (:func:`_product_flops`), 0 where there is none;
    * ``kernel``: a Mosaic call's name (the instruction's, less XLA's
      numbering: ``mx_causal_attention_bwd``, ``gmm``), else None.

    ``scoped`` is false where no ``op_name`` in the text (fused
    instructions included) holds ``mx.update``: an executable from
    before the scopes existed (they are not in JAX's compile-cache key)
    says so instead of reading as zeros."""
    module, comp, scoped = "", None, False
    matmul_of: Dict[str, str] = {}      # fused computation -> op_name
    # what a computation holds itself: FLOPs, and its distinct name
    # stacks in the text's order (a dict kept for its order), a fusion
    # nested in it as the callee's name in a tuple
    flops_of: Dict[str, int] = {}
    held_of: Dict[str, dict] = {}
    types: Dict[str, str] = {}          # of the computation being read
    rows = []   # (computation, instruction, op_name, fusion's callee,
    #              opcode, own FLOPs, kernel)
    for line in _whole_instructions(hlo_text):
        if line.startswith("HloModule "):
            module = line[len("HloModule "):].split(",")[0].strip()
        elif line[:1] not in (" ", "}", "") and line.endswith("{"):
            comp = line.split(" (")[0].replace("ENTRY ", "").lstrip("%")
            types, flops_of[comp], held_of[comp] = {}, 0, {}
        elif " = " in line and line.startswith("  "):
            lhs, rest = line.split(" = ", 1)
            name = lhs.split()[-1].lstrip("%")
            m = _HLO_OP_NAME.search(rest)
            own = m.group(1) if m else ""
            scoped = scoped or "mx.update" in own
            if own and comp not in matmul_of and _HLO_MATMUL.search(rest):
                matmul_of[comp] = own
            fusion = _HLO_FUSION.search(rest) and _HLO_CALLS.search(rest)
            callee = fusion.group(1) if fusion else None
            m = _HLO_OPCODE.search(rest)
            opcode = m.group(1) if m else ""
            types[name] = rest[:m.start(1) - 1] if m else ""
            flops = _product_flops(opcode, types[name], rest, m.end(),
                                   types) \
                if opcode in ("dot", "convolution") else 0
            kernel = _HLO_NUMBERING.sub("", name) if (
                opcode == "custom-call"
                and 'custom_call_target="tpu_custom_call"' in rest) else None
            flops_of[comp] += flops
            if own:
                held_of[comp][own.rsplit("/", 1)[0]] = None
            if callee:
                held_of[comp][(callee,)] = None
            rows.append((comp, name, own, callee, opcode, flops, kernel))
    fused = {row[3] for row in rows if row[3]}
    ops = {name: matmul_of.get(callee, own)
           for comp_, name, own, callee, *_ in rows if comp_ not in fused}

    def reach(callee, scopes):
        """FLOPs of a fused computation and of those nested in it; their
        name stacks go into `scopes`, a dict as `held_of`'s are."""
        flops = flops_of.get(callee, 0)
        for held in held_of.get(callee, ()):
            if isinstance(held, tuple):
                flops += reach(held[0], scopes)
            else:
                scopes.setdefault(held)
        return flops

    instructions = {}
    for comp_, name, own, callee, opcode, flops, kernel in rows:
        if comp_ in fused:
            continue
        scopes = {own.rsplit("/", 1)[0]: None} if own else {}
        if callee:
            flops += reach(callee, scopes)
        instructions[name] = {
            "opcode": opcode, "scopes": list(scopes),
            "pass": _pass_of(ops[name]),
            "passes": sorted({_pass_of(s) for s in scopes}) or ["other"],
            "flops": flops, "kernel": kernel}
    return {"module": module, "scoped": scoped, "ops": ops,
            "instructions": instructions}


def step_programs() -> List[Dict[str, Any]]:
    """The scope table of every step executable built or loaded in this
    process and still cached, oldest first: ``{"module", "origin":
    "compiled" | "cache", "scoped", "ops": {instruction: op_name},
    "instructions": {instruction: what it is}, "param_uses"}`` (see
    :func:`program_table`).  A device trace names
    instructions (``%fusion.14``) and not scopes; this is the program's
    own join from the one to the other, for whoever reads a profile.
    ``param_uses`` is what the trace of the step counted, ``{reads:
    Parameters whose value the model read that often}``: a stack applied
    four times reads each of its parameters four times in one program
    (None where the executable was loaded without a trace).

    Built on the first call and memoised per executable: the text of a
    ResNet-50 step is megabytes, so nothing here runs in ``step()`` or
    in set-up."""
    out = []
    for ent in list(_STEP_CACHE.data.values()):
        if ent.program is None:
            ent.program = program_table(ent.fn.as_text())
        out.append({"origin": ent.origin, **ent.program,
                    "param_uses": ent.param_uses})
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
        #: across the whole fwd+bwd+update program; a segment keeps only
        #: its input and the values a kernel named for its backward
        #: (ops/residuals.py; `kept_residuals()` counts them)
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
        if optimizer.fused_static_key() is None:
            raise MXNetError(
                f"no functional form for optimizer "
                f"{type(optimizer).__name__}: the step program calls "
                "Optimizer.fused_apply, which needs _FUSED_STATIC declared "
                "(optimizer/optimizer.py)")
        self._optimizer = optimizer

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
        self._state_layouts: Dict[Any, Tuple] = {}
        # set-up phase `mx.setup.place`: nothing here waits for a
        # transfer, so one still in flight is paid for by whichever
        # later phase first needs its array
        with _tracing.phase("mx.setup.place") as placed:
            for n, p in self._plist:
                v = p.data().data
                spec = rules.spec_for(n, v.shape, self.mesh)
                sh = NamedSharding(self.mesh.mesh, spec)
                self._shardings[n] = sh
                sspec = zero_state_spec(
                    spec, v.shape, self.mesh,
                    min_size=_envmod.get_int("MXNET_ZERO_MIN_SIZE")) \
                    if self._zero else spec
                self._state_shardings[n] = NamedSharding(self.mesh.mesh,
                                                         sspec)
                self.params[n] = _global_put(v, sh)
            self.opt_state = {
                n: tuple(_global_put(s, self._state_shardings[n])
                         for s in self._init_state(v))
                for n, v in self.params.items() if self._trainable[n]}
            arrays = list(self.params.values()) + [
                s for states in self.opt_state.values() for s in states]
            placed["stats"].update(arrays=len(arrays),
                                   bytes=sum(a.nbytes for a in arrays))

        # per-shape fast path over _STEP_CACHE; LRU-bounded because
        # each value strong-refs a whole-step executable — an unbounded
        # dict would outlive _STEP_CACHE's own eviction (ragged last
        # batches / variable seq-len mint a new shape per epoch)
        self._step_fns: "OrderedDict[Tuple, Any]" = OrderedDict()
        # the layout gauges are set once a trainer: its mesh and its
        # ZeRO choice never change
        self._layout_published = False
        self._fwd_fns: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._param_by_name = {n: p for n, p in self._plist}
        self._traced_param_uses = None  # set by the step's trace
        self._t = 0

    # ---- optimizer state -------------------------------------------------
    def _state_layout(self, dtype):
        """``(treedef, leaf dtypes, has_master)`` of the optimizer's state
        for a weight of ``dtype``.  ``opt_state`` (and every checkpoint)
        keeps a FLAT tuple per parameter, the float32 master weight last;
        ``fused_apply`` takes what ``create_state`` builds: None, an
        array or a tuple, under multi-precision ``(that, master)``.  The
        structure is learnt from the optimizer itself on a one-element
        weight, so no full-size state is ever built on the host."""
        if dtype not in self._state_layouts:
            from ..ndarray.ndarray import zeros

            opt = self._optimizer
            w = zeros((1,), dtype=str(dtype))
            state = opt.create_state_multi_precision(0, w)
            leaves, treedef = jax.tree_util.tree_flatten(state)
            self._state_layouts[dtype] = (
                treedef, tuple(s.data.dtype for s in leaves),
                opt._mp_active(w, state))
        return self._state_layouts[dtype]

    def _init_state(self, value: jax.Array) -> Tuple[jax.Array, ...]:
        """Zeros beside ``value`` (its sharding, the state's own dtypes,
        FIXED from step 0: a state that changed dtype after one step
        would retrace) and, where the layout has one, the master copy."""
        _treedef, dtypes, has_master = self._state_layout(value.dtype)
        zeros = tuple(jnp.zeros_like(value, dtype=dt)
                      for dt in dtypes[:len(dtypes) - has_master])
        return zeros + ((value.astype(jnp.float32),) if has_master else ())

    # ---- the pure step ---------------------------------------------------
    def _build_pure(self):
        plist = self._plist
        block, loss, opt = self.block, self.loss, self._optimizer
        mults, trainable = self._mults, self._trainable
        trainer = self
        wd = float(opt.wd)

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
                trainer._traced_param_uses = trace.use_counts()
                return lval, aux_named

            (lval, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_params, new_state = {}, {}
            for n, _ in plist:
                if not trainable[n]:
                    new_params[n] = params[n]

            def update(w, g, flat, hyper):
                """One parameter through ``Optimizer.fused_apply``; the
                hyper scalars stay float32 whatever the weight's dtype,
                results return to the weight's and each state's."""
                treedef, _dtypes, has_master = trainer._state_layout(w.dtype)
                state = jax.tree_util.tree_unflatten(treedef, flat)
                if has_master:
                    nw, ns = opt_mod.fused.apply_master(opt, w, g, state,
                                                        hyper)
                else:
                    nw, ns = opt.fused_apply(w, g, state, hyper)
                return nw.astype(w.dtype), tuple(
                    sv.astype(old.dtype) for sv, old in
                    zip(jax.tree_util.tree_leaves(ns), flat))

            with jax.named_scope("mx.update"):
                # lr, t: traced; the mults, wd and rescale_grad (the loss
                # is a global mean): constants of the program
                tf = t.astype(jnp.float32)
                for n, _ in plist:
                    if not trainable[n]:
                        continue
                    lm, wm = mults[n]
                    hyper = opt.fused_fold_t(
                        {"lr": lr * lm, "wd": wd * wm, "rescale_grad": 1.0},
                        tf)
                    new_params[n], new_state[n] = update(
                        params[n], grads[n], opt_state[n], hyper)
            # aux state (BatchNorm moving stats) accumulates across steps:
            # fold the traced updates back into the param dict so the next
            # step's trace reads them (stop_gradient — not a learnable path)
            for n, v in aux.items():
                new_params[n] = lax.stop_gradient(v).astype(params[n].dtype)
            return new_params, new_state, lval, aux

        return mx_train_step

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
            leaves, treedef = jax.tree_util.tree_flatten(args)
            block = self.block
            # the in-process signature pins everything the closure
            # bakes in: the block INSTANCE (class+param names don't pin
            # forward math), optimizer statics, mults, layout, and the
            # concrete devices (an executable is bound to its device
            # assignment — two trainers on disjoint subsets of the same
            # topology must not share one); the PERSISTENT key adds the
            # lowered program text, which pins the actual model code.
            # One mapping: the hashable sig and the named components that
            # compile provenance prints cannot drift apart
            opt = self._optimizer
            named = {
                "block": (_block_token(block),
                          f"{type(block).__module__}."
                          f"{type(block).__qualname__}",
                          tuple(n for n, _ in self._plist)),
                "mults": tuple(sorted(self._mults.items())),
                "optimizer": type(opt),
                "statics": opt.fused_static_key() + (("wd", float(opt.wd)),),
                "remat": self.remat,
                "layout": layout_key(self.mesh),
                "devices": tuple(str(d) for d in mesh.devices),
                "zero": self._zero, "donation": self._donate,
                "treedef": treedef,
                "avals": tuple(opt_mod.fused._leaf_aval(x) for x in leaves)}
            sig = ("spmd-train-step",) + tuple(named.items())
            fn = _STEP_CACHE.lookup(sig)
            if fn is None:
                self._traced_param_uses = None  # a load traces nothing
                fn = _STEP_CACHE.compile(
                    sig, lambda: jitted.trace(*args), opt, alias_ok=False,
                    components=named, donate=self._donate)
                _STEP_CACHE.data[sig].param_uses = self._traced_param_uses
                fn = self._timed_first_call(ikey, fn, _STEP_CACHE.cost(sig))
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

    def _timed_first_call(self, ikey, fn, cost):
        """What `_get_step` files for an executable it has just built or
        loaded: its first call runs under the set-up phase
        `mx.step.first_dispatch` (on the TPU it holds the program's load
        onto the chip) and puts the bare executable in its own place,
        so that every later step calls `fn` itself."""
        def first_dispatch(*args):
            self._step_fns[ikey] = (fn, cost)
            with _tracing.phase("mx.step.first_dispatch",
                                site=_STEP_CACHE.site):
                return fn(*args)
        return first_dispatch

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
        if _tracing._ENABLED and not self._layout_published:
            self._layout_published = True
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

    def _build_forward(self, ivals, key):
        """The inference program for inputs like `ivals`, built ahead of
        time under the set-up phases a step's build has (`mx.build.*`,
        `program` "forward"; `optimizer.fused.ProgramBuild`)."""
        from ..gluon.block import ActiveTrace

        plist = self._plist
        block = self.block
        trainer = self

        def forward(params, ivals, key):
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

        return opt_mod.fused.ProgramBuild(
            lambda: jax.jit(forward).trace(self.params, ivals, key),
            "parallel.spmd_forward").compile()

    def forward(self, *inputs) -> NDArray:
        """Sharded inference with the trainer's current params: one
        program per input shapes and dtypes, kept as the step's are."""
        bspecs = self._batch_spec or [None] * len(inputs)
        ivals = tuple(self._place(x, s) for x, s in zip(inputs, bspecs))
        key = rnd.next_key()
        ikey = tuple((tuple(v.shape), str(v.dtype)) for v in ivals)
        if ikey not in self._fwd_fns:
            self._fwd_fns[ikey] = self._build_forward(ivals, key)
            while len(self._fwd_fns) > _STEP_FNS_MAX:
                self._fwd_fns.popitem(last=False)
        else:
            self._fwd_fns.move_to_end(ikey)
        out = self._fwd_fns[ikey](self.params, ivals, key)
        from ..context import current_context

        ctx = current_context()
        return jax.tree_util.tree_map(lambda v: NDArray(v, ctx=ctx), out)
