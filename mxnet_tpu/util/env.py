"""Central registry of `MXNET_*` environment knobs.

Every env-var knob the framework honors is DECLARED here once — name,
type, default, and documentation — and read through the typed accessors
(:func:`get_int`, :func:`get_bool`, :func:`get_str`, :func:`get_float`).
Reading an undeclared ``MXNET_*`` name raises :class:`MXNetError`, so a
typo'd knob dies at the read site instead of silently returning its
default forever (the bug class mxlint rule MX003 exists to catch).

The registry is the single source of truth for ``docs/env_vars.md``
(generated via ``python tools/mxlint.py --env-docs``) and is fully
populated at import time, so documentation can never trail the code.

Declared defaults are what the accessor returns when the variable is
unset; a call site may pass ``default=`` to override — used by knobs
whose default is computed (worker counts), which declare
``default=None`` and document the dynamic rule.
"""
from __future__ import annotations

import os as _os
import threading
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from ..base import MXNetError
from ..base import convert_env as _convert_env
from ..base import get_env as _raw_get_env  # the untyped low-level reader

__all__ = [
    "Knob", "Tunable", "declare", "knobs", "is_declared", "tunables",
    "get_int", "get_bool", "get_str", "get_float",
    "apply_overlay", "overlay_info", "clear_overlay",
    "resolved", "fingerprint", "generate_docs",
]


class Tunable(NamedTuple):
    """Optional search-space metadata a knob declares about itself, so
    mxtune's space is derived from the registry instead of duplicated
    beside it.  Either a numeric range (``lo``/``hi``, with ``scale``
    'linear' or 'log' — log doubles/halves under neighborhood moves) or
    an explicit ``choices`` tuple (categorical / bool knobs)."""
    lo: Optional[float] = None
    hi: Optional[float] = None
    scale: str = "linear"
    choices: Optional[Tuple[Any, ...]] = None


class Knob(NamedTuple):
    name: str
    typ: type
    default: Any
    doc: str
    tunable: Optional[Tunable] = None


_KNOBS: Dict[str, Knob] = {}
_LOCK = threading.Lock()

_UNSET = object()

# Tuned-config overlay (mxnet_tpu.autotune): name -> RAW string value,
# consulted by _get only when the process env leaves the knob unset.
# Explicit MXNET_* settings therefore always win — the overlay is a
# better default, never an override.
_OVERLAY: Dict[str, str] = {}
_OVERLAY_META: Optional[Dict[str, Any]] = None


def declare(name: str, typ: type, default: Any, doc: str,
            tunable: Optional[Tunable] = None) -> Knob:
    """Register a knob. Duplicate registration raises loudly — even an
    identical re-declaration means two call sites each believe they own
    the knob, and the second would silently shadow doc/tunable edits to
    the first. Every knob is declared exactly once, in this module."""
    if not name.startswith("MXNET_"):
        raise MXNetError(
            f"env knob {name!r} must use the MXNET_ prefix; other "
            "process env vars are not framework knobs")
    if tunable is not None and typ is bool and tunable.choices is None:
        tunable = tunable._replace(choices=(False, True))
    k = Knob(name, typ, default, doc, tunable)
    with _LOCK:
        if name in _KNOBS:
            prev = _KNOBS[name]
            raise MXNetError(
                f"env knob {name} already registered "
                f"({prev.typ.__name__}, default {prev.default!r}) — "
                "duplicate declaration; every knob is declared exactly "
                "once in mxnet_tpu/util/env.py")
        _KNOBS[name] = k
    return k


def is_declared(name: str) -> bool:
    return name in _KNOBS


def knobs() -> List[Knob]:
    """All declared knobs, sorted by name (docs generation order)."""
    with _LOCK:
        return sorted(_KNOBS.values(), key=lambda k: k.name)


def tunables() -> List[Knob]:
    """The knobs that declared :class:`Tunable` metadata — mxtune's
    search-space surface, sorted by name."""
    return [k for k in knobs() if k.tunable is not None]


def _get(name: str, typ: type, default: Any) -> Any:
    knob = _KNOBS.get(name)
    if knob is None:
        raise MXNetError(
            f"unregistered env knob {name!r} — declare it in "
            f"mxnet_tpu/util/env.py (known: {sorted(_KNOBS)[:20]}...)")
    if knob.typ is not typ:
        raise MXNetError(
            f"env knob {name} is declared as {knob.typ.__name__}, "
            f"read as {typ.__name__}")
    dflt = knob.default if default is _UNSET else default
    raw = _os.environ.get(name)
    if (raw is None or raw == "") and name in _OVERLAY:
        # precedence: explicit env (non-empty) > tuned overlay > default
        return _convert_env(name, _OVERLAY[name], typ)
    return _raw_get_env(name, dflt, typ)


def get_int(name: str, default: Any = _UNSET) -> Optional[int]:
    return _get(name, int, default)


def get_bool(name: str, default: Any = _UNSET) -> Optional[bool]:
    return _get(name, bool, default)


def get_str(name: str, default: Any = _UNSET) -> Optional[str]:
    return _get(name, str, default)


def get_float(name: str, default: Any = _UNSET) -> Optional[float]:
    return _get(name, float, default)


def apply_overlay(config: Dict[str, Any], fingerprint: str = "",
                  source: str = "") -> Dict[str, Any]:
    """Install a tuned-config overlay (mxtune startup / trial runs).

    ``config`` maps knob names to values (any JSON scalar; stored as the
    string the environment would have carried).  Precedence is fixed:
    a knob the process env sets explicitly (non-empty) keeps its env
    value — those names are recorded as ``shadowed``; unregistered names
    are recorded as ``ignored`` and dropped (a stale store entry naming
    a since-removed knob must not poison the process).  Returns the
    application record, also available via :func:`overlay_info` and
    stamped into mxprof dumps as ``tuned_config``."""
    global _OVERLAY_META
    applied, shadowed, ignored = [], [], []
    with _LOCK:
        for name in sorted(config):
            if name not in _KNOBS:
                ignored.append(name)
                continue
            raw = _os.environ.get(name)
            if raw is not None and raw != "":
                shadowed.append(name)
                continue
            value = config[name]
            _OVERLAY[name] = ("1" if value else "0") \
                if isinstance(value, bool) else str(value)
            applied.append(name)
        _OVERLAY_META = {
            "fingerprint": fingerprint,
            "source": source,
            "applied": applied,
            "shadowed": shadowed,
            "ignored": ignored,
        }
        return dict(_OVERLAY_META)


def overlay_info() -> Optional[Dict[str, Any]]:
    """The record of the last :func:`apply_overlay`, or None when no
    tuned config is active."""
    with _LOCK:
        return dict(_OVERLAY_META) if _OVERLAY_META is not None else None


def clear_overlay() -> None:
    with _LOCK:
        global _OVERLAY_META
        _OVERLAY.clear()
        _OVERLAY_META = None


# Harness control vars that legitimately use the MXNET_ prefix without
# being knobs (test seeding, nightly stage marking) — exempt from the
# unknown-env warning below.
_NON_KNOB_ENV = {"MXNET_NIGHTLY", "MXNET_TEST_SEED", "MXNET_TEST_PLATFORM"}
_warned_unknown_env = False


def _warn_unknown_env_once() -> None:
    """Warn (once per process) about MXNET_* env vars that match no
    registered knob — a typo'd knob is otherwise silently ignored
    forever.  Runs at the first resolved() call, i.e. the first time
    anything snapshots the configuration surface."""
    global _warned_unknown_env
    with _LOCK:
        if _warned_unknown_env:
            return
        _warned_unknown_env = True
        known = sorted(_KNOBS)
    import difflib
    import warnings

    for name in sorted(_os.environ):
        if (not name.startswith("MXNET_") or name in _KNOBS
                or name in _NON_KNOB_ENV):
            continue
        close = difflib.get_close_matches(name, known, n=1)
        hint = f" — did you mean {close[0]}?" if close else ""
        warnings.warn(
            f"env var {name} is not a registered MXNET_ knob and has "
            f"no effect{hint} (see docs/env_vars.md)",
            RuntimeWarning, stacklevel=3)


def resolved() -> Dict[str, Any]:
    """Every declared knob's RESOLVED value (env override, tuned
    overlay, or declared default; dynamic defaults resolve to None).
    This is the performance-relevant configuration surface of the
    process — what a bench artifact records so `perf_compare` can say
    "a knob changed" instead of just "it got slower"."""
    _warn_unknown_env_once()
    _GET = {int: get_int, bool: get_bool, str: get_str,
            float: get_float}
    out = {}
    for k in knobs():
        try:
            out[k.name] = _GET[k.typ](k.name)
        except Exception:  # noqa: BLE001 — one bad value must not hide the rest
            out[k.name] = "<unreadable>"
    return out


def fingerprint() -> str:
    """sha256 over the sorted resolved knob table — the one-line
    "did any registered knob change" answer regression attribution
    compares across runs."""
    import hashlib

    h = hashlib.sha256()
    for name, value in sorted(resolved().items()):
        h.update(f"{name}={value!r}\x1f".encode())
    return h.hexdigest()


def generate_docs() -> str:
    """Markdown reference for every declared knob (docs/env_vars.md)."""
    lines = [
        "# Environment variables",
        "",
        "Generated from the knob registry (`mxnet_tpu/util/env.py`) by",
        "`python tools/mxlint.py --env-docs`.  **Do not edit by hand** —",
        "a tier-1 test (`tests/test_mxlint.py`) fails when this file is",
        "out of sync with the registry.",
        "",
        "| Variable | Type | Default | Description |",
        "|---|---|---|---|",
    ]
    for k in knobs():
        dflt = "*(dynamic)*" if k.default is None else f"`{k.default!r}`"
        doc = " ".join(k.doc.split())
        lines.append(f"| `{k.name}` | {k.typ.__name__} | {dflt} | {doc} |")
    lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# The knob catalogue.  One declaration per knob the framework honors;
# grouped by subsystem.  Keep alphabetical within each group.
# ---------------------------------------------------------------------------

# -- engine / dispatch ------------------------------------------------------
declare("MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
        "Execution engine. 'ThreadedEnginePerDevice' (default) is the "
        "async PjRt dispatch path; 'NaiveEngine' makes every op call "
        "block_until_ready for debugging (ref: src/engine/naive_engine.cc).")
declare("MXNET_CPU_WORKER_NTHREADS", int, None,
        "Worker threads of the native dependency engine. Default is "
        "computed: max(2, os.cpu_count()).")
declare("MXNET_USE_NATIVE", bool, True,
        "Load/build the native C++ modules (engine, RecordIO, image "
        "pipeline). 0 forces the pure-Python fallbacks.")

# -- contexts / memory ------------------------------------------------------
declare("MXNET_DEFAULT_CONTEXT", str, None,
        "Force the default device context ('cpu' or 'tpu'). Default is "
        "computed: tpu(0) when an accelerator is visible, else cpu(0).")
declare("MXNET_GPU_MEM_POOL_RESERVE", int, None,
        "Percent of device memory kept OUT of the allocator pool "
        "(reference spelling); mapped to XLA_PYTHON_CLIENT_MEM_FRACTION "
        "at import. Unset = XLA default.")

# -- training ---------------------------------------------------------------
declare("MXNET_BACKWARD_DO_MIRROR", bool, False,
        "Gradient mirroring: recompute activations in the backward "
        "(jax.checkpoint) instead of keeping them in HBM — trades MXU "
        "FLOPs for memory.")
declare("MXNET_FUSED_BUCKET_BYTES", int, 4 << 20,
        "Bucket size for the fused gradient allreduce "
        "(KVStore.pushpull_fused): one collective per ~this many bytes "
        "of dtype-homogeneous dense gradients.",
        tunable=Tunable(lo=256 << 10, hi=64 << 20, scale="log"))
declare("MXNET_KVSTORE_TIMEOUT", float, None,
        "Seconds a distributed collective may block before the worker "
        "aborts loudly instead of hanging on a dead peer. Unset/0 = wait "
        "forever.")
declare("MXNET_SPMD", bool, False,
        "Route Trainer.step through the unified GSPMD path: ONE donated "
        "jit program over the replica mesh (gradient reduce + sharded "
        "optimizer apply) instead of N per-replica dispatches. "
        "Trainer(spmd=...) overrides per trainer. Transparent per-step "
        "fallback to the per-replica path for sparse gradients, ragged "
        "layouts, or optimizers without a fused form. See "
        "docs/sharding.md.")
declare("MXNET_ZERO_STATES", bool, True,
        "Under the SPMD step path, shard optimizer states (and the "
        "weight-update computation) across the data-parallel axis "
        "(ZeRO-1 / arXiv:2004.13336): reduce-scatter grads, update the "
        "local state shard, all-gather fresh weights. 0 keeps states "
        "replicated (the collective is then a plain all-reduce).")
declare("MXNET_ZERO_MIN_SIZE", int, 2048,
        "Smallest parameter (elements) whose optimizer states shard "
        "across the data axis under MXNET_ZERO_STATES: big tensors "
        "carry the memory, tiny biases would pay collective latency "
        "for nothing and stay replicated.",
        tunable=Tunable(lo=256, hi=65536, scale="log"))
declare("MXNET_SPMD_BUCKET_BYTES", int, 0,
        "Bucket size for the SPMD mesh-collective gradient reduce "
        "(KVStore.pushpull_fused under MXNET_SPMD=1). 0 = inherit "
        "MXNET_FUSED_BUCKET_BYTES.")
declare("MXNET_COMM_QUANT", str, "none",
        "Wire encoding for the SPMD bucket collectives (the gradient "
        "reduce and the fresh-weight gather in optimizer/spmd.py, and "
        "KVStore.pushpull_fused's SPMD bucket all-reduce): 'int8' "
        "(symmetric linear, 1 byte/elem) or 'fp8' (e4m3 emulation, "
        "1 byte/elem) quantize with per-512-element-block scales and error-feedback "
        "residuals; 'none' keeps full-precision collectives. See "
        "docs/sharding.md#quantized-collectives.",
        tunable=Tunable(choices=("none", "int8", "fp8")))
declare("MXNET_COMM_QUANT_EF", bool, True,
        "Carry error-feedback residuals for MXNET_COMM_QUANT (the "
        "quantization remainder re-enters the next step's payload "
        "before encoding). Disable ONLY for A/B experiments — without "
        "feedback the rounding bias accumulates into the weights.",
        tunable=Tunable())
declare("MXNET_COMM_QUANT_MIN_SIZE", int, 2048,
        "Smallest bucket (padded elements) MXNET_COMM_QUANT encodes; "
        "tiny buckets stay fp32 — their scale rows and encode/decode "
        "work would cost more than the bytes they save.",
        tunable=Tunable(lo=256, hi=262144, scale="log"))
declare("MXNET_COMM_OVERLAP", bool, False,
        "Dispatch each SPMD bucket's gradient reduce as its own "
        "program, issued in gradient-ready (reverse-bucket) order "
        "while the backward is still executing, so collectives overlap "
        "compute and the step approaches max(compute, comm) instead "
        "of their sum. See docs/performance.md.",
        tunable=Tunable())

# -- ops / kernels ----------------------------------------------------------
declare("MXNET_BN_EXACT_VAR", bool, False,
        "BatchNorm uses the exact two-pass variance instead of the "
        "single-pass shifted estimator; also disables the fused Conv+BN "
        "path (whose statistics are inherently single-pass).")
declare("MXNET_FUSED_CONVBN", bool, False,
        "Route ResNet V1 residual blocks through the fused Pallas "
        "Conv+BN+ReLU kernels when tracing in NHWC layout.")
declare("MXNET_FUSED_CONVBN_BWD", bool, False,
        "Opt-in Pallas backward for the fused Conv+BN units (roughly "
        "doubles the probe-compile surface).")
declare("MXNET_PALLAS_INTERPRET", bool, False,
        "Run Pallas kernels in interpreter mode (CPU testing): no "
        "Mosaic compile, bit-accurate reference semantics.")
declare("MXNET_USE_PALLAS", bool, True,
        "Master switch for Pallas kernels (flash attention in inference "
        "and in training, fused Conv+BN). 0 selects the XLA fallbacks "
        "with identical semantics (attention dropout keeps its mask: "
        "every route takes it from the same hash).")

# -- compile cache ----------------------------------------------------------
declare("MXNET_COMPILE_CACHE_BYTES", int, 0,
        "Byte cap for the on-disk compile cache; least-recently-used "
        "entries are evicted past it. 0 = unbounded (size the volume "
        "instead).")
declare("MXNET_COMPILE_CACHE_DIR", str, "",
        "Directory of the persistent (cross-process) AOT executable "
        "cache. Empty = persistent cache off; call sites keep their "
        "in-process caches either way. See docs/compile_cache.md.")
declare("MXNET_COMPILE_CACHE_DISABLE", bool, False,
        "Kill switch: 1 ignores MXNET_COMPILE_CACHE_DIR and compiles "
        "everything fresh (e.g. when a shared cache volume is "
        "suspected bad).")
declare("MXNET_COMPILE_CACHE_OPS", bool, False,
        "Opt-in: route the ops-registry jit/grad executables through "
        "the persistent compile cache (AOT per input signature). "
        "Serving buckets and the fused optimizer step use the cache "
        "whenever MXNET_COMPILE_CACHE_DIR is set; eager per-op "
        "programs are many and small, so they are opt-in.")
declare("MXNET_FUSED_CACHE_MAX", int, 256,
        "Entry cap of the in-process FusedUpdater executable cache "
        "(LRU eviction past it). One entry per optimizer/tree/shape "
        "signature per device.",
        tunable=Tunable(lo=32, hi=1024, scale="log"))
declare("MXNET_OP_CACHE_MAX", int, 4096,
        "Entry cap of each in-process ops-registry executable cache "
        "(jit and grad, LRU eviction past it). One entry per "
        "(op, attrs) — plus signature when MXNET_COMPILE_CACHE_OPS=1.",
        tunable=Tunable(lo=512, hi=16384, scale="log"))

# -- autotune ---------------------------------------------------------------
declare("MXNET_AUTOTUNE", bool, True,
        "Apply the stored tuned knob config (mxtune) at import when the "
        "config store has a matching winner: tuned values become the "
        "process defaults via an env-overlay that any explicitly set "
        "MXNET_* variable always overrides. 0 boots on declared "
        "defaults only. See docs/autotune.md.")
declare("MXNET_AUTOTUNE_DIR", str, "",
        "Directory of the persistent tuned-config store "
        "(autotune.store). Empty = derive <MXNET_COMPILE_CACHE_DIR>/"
        "autotune when the compile cache dir is set, else the store "
        "is off and startup never applies a tuned config.")
declare("MXNET_AUTOTUNE_SCENARIO", str, "",
        "Scenario tag the startup overlay matches store entries "
        "against (a model fingerprint or a named bench scenario such "
        "as 'mlp_train'). Empty = accept the newest entry for this "
        "framework version regardless of scenario.")
declare("MXNET_AUTOTUNE_TRIAL_TIMEOUT_S", float, 120.0,
        "Wall-clock budget of one autotune trial subprocess "
        "(tools/autotune.py). Past it the trial is killed and counted "
        "as pruned — a hung or crashed trial must never crash the "
        "tune itself.")

# -- data pipeline ----------------------------------------------------------
declare("MXNET_PREFETCH_DEPTH", int, None,
        "DataLoader prefetch depth: batches each iterator keeps in "
        "flight ahead of the consumer, in both the process and thread "
        "worker pools. Default is computed: 2 * num_workers. The "
        "DataLoader(prefetch=) argument overrides per loader.",
        tunable=Tunable(lo=1, hi=16, scale="log"))

# -- resilience -------------------------------------------------------------
declare("MXNET_BREAKER_COOLDOWN_MS", float, 1000.0,
        "Serving circuit breaker: milliseconds an OPEN breaker waits "
        "before letting one half-open probe request through.",
        tunable=Tunable(lo=100.0, hi=5000.0, scale="log"))
declare("MXNET_BREAKER_THRESHOLD", int, 5,
        "Serving circuit breaker: consecutive executor failures that "
        "open the breaker (that model answers 503 until a probe "
        "succeeds; the process never dies).")
declare("MXNET_CHAOS", bool, False,
        "Master switch for the fault-injection harness "
        "(resilience.chaos). Off = every injection site is a single "
        "falsy flag check with zero behavior change.")
declare("MXNET_CHAOS_SEED", int, 0,
        "Seed for probabilistic chaos plans (kind@pF in "
        "MXNET_CHAOS_SPEC) — schedules replay deterministically.")
declare("MXNET_CHAOS_SPEC", str, "",
        "Comma-separated chaos plans installed at import when "
        "MXNET_CHAOS=1: 'kind@N' (fail Nth call), 'kind@xN' (next N), "
        "'kind@pF' (probability F), optional ':action' "
        "(error/die/hang/preempt). See docs/resilience.md.")
declare("MXNET_CKPT_EVERY", int, 0,
        "Auto-checkpoint cadence in optimizer steps (resilience."
        "AutoCheckpoint default). 0 = only preemption-triggered saves.")
declare("MXNET_CKPT_KEEP", int, 3,
        "Auto-checkpoint retention: keep the last K step directories, "
        "prune older ones after each successful save.")
declare("MXNET_ELASTIC", bool, False,
        "Set by the elastic supervisor (tools/elastic_run.py) in every "
        "worker's env: this process runs under coordinated rank-failure "
        "recovery (heartbeats, reserved exit codes, commit-marker "
        "resume). Never set by hand; off = zero elastic code on the "
        "step path. See docs/resilience.md (Elastic recovery).")
declare("MXNET_ELASTIC_DIR", str, "",
        "Shared coordination directory of an elastic job: per-rank "
        "heartbeat stamps (hb-rank<k>.json), per-rank checkpoint "
        "subdirs (rank<k>/step-N), the job-level COMMIT.json resume "
        "marker, and per-generation worker logs. Exported by the "
        "supervisor.")
declare("MXNET_ELASTIC_RANK", int, None,
        "This worker's job rank, exported by the elastic supervisor "
        "(also what chaos rank= plan selectors match against). Default "
        "is dynamic: unset outside an elastic job.")
declare("MXNET_ELASTIC_WORLD", int, None,
        "The elastic job's current world size (shrink-mode restarts "
        "re-export a smaller value). Default is dynamic: unset outside "
        "an elastic job.")
declare("MXNET_ELASTIC_HEARTBEAT_S", float, 2.0,
        "Interval of the background heartbeat thread "
        "(resilience.heartbeat.HeartbeatWriter.start()); per-step "
        "beat() calls ignore it.")
declare("MXNET_ELASTIC_HEARTBEAT_TIMEOUT_S", float, 30.0,
        "Heartbeat age past which the supervisor declares a "
        "still-running rank HUNG and opens a failure epoch. Also the "
        "default MXNET_KVSTORE_TIMEOUT the supervisor exports so "
        "survivors' collective watchdogs fire instead of waiting "
        "forever on the dead peer.")
declare("MXNET_ELASTIC_MAX_RESTARTS", int, 3,
        "Restart budget of the elastic supervisor: failure epochs "
        "beyond this declare the job dead instead of thrashing "
        "restarts against a persistent fault.")
declare("MXNET_ELASTIC_GRACE_S", float, 30.0,
        "Seconds the supervisor waits after SIGTERMing survivors for "
        "them to cut their sync checkpoint and exit with a reserved "
        "rc; anything still alive is SIGKILLed and classified hung. "
        "Raised automatically to the collective watchdog timeout + 5s "
        "when that is longer.")
declare("MXNET_DRAIN_TIMEOUT_MS", float, 30000.0,
        "Hard deadline for InferenceServer.shutdown(drain=True): past "
        "it, still-queued requests fail with ServerClosed instead of "
        "the shutdown hanging forever on a wedged batch.")
declare("MXNET_RANKCHECK", bool, True,
        "Master switch of the runtime collective-schedule ledger "
        "(parallel.schedule): every collective site appends "
        "(site, op, dtype, nbytes, seq) to a rolling fingerprint, and "
        "a collective watchdog timeout compares fingerprints across "
        "ranks to reclassify schedule divergence (a deterministic "
        "program bug — see mxlint MX019/MX020) as ScheduleDivergence "
        "instead of burning restarts on PeerFailed. Off = one boolean "
        "check per collective.")
declare("MXNET_RANKCHECK_WINDOW", int, 256,
        "Entries kept in the rolling collective-schedule fingerprint "
        "window (minimum 8). Divergence older than the window on BOTH "
        "ranks cannot be pinpointed; larger windows cost only memory "
        "and stamp-file size.")
declare("MXNET_RANKCHECK_WAIT_S", float, 3.0,
        "How long the collective-watchdog timeout path polls peers' "
        "schedule fingerprints before giving up and keeping the "
        "PeerFailed classification. Bounded so a genuinely dead peer "
        "(no fingerprint forthcoming) only delays the failure epoch "
        "by this much.")
declare("MXNET_RETRY_BASE_MS", float, 50.0,
        "Retry policy: first backoff delay in milliseconds (doubles "
        "per attempt, jittered ±50%, capped at MXNET_RETRY_MAX_MS).",
        tunable=Tunable(lo=10.0, hi=500.0, scale="log"))
declare("MXNET_RETRY_BUDGET_MS", float, 10000.0,
        "Retry policy: hard wall-clock budget across all attempts of "
        "one call, including backoff sleeps.")
declare("MXNET_RETRY_MAX_ATTEMPTS", int, 3,
        "Retry policy: total attempts per retryable call site "
        "(1 = no retry). Only transient errors retry.")
declare("MXNET_RETRY_MAX_MS", float, 2000.0,
        "Retry policy: backoff delay ceiling in milliseconds.",
        tunable=Tunable(lo=500.0, hi=10000.0, scale="log"))

# -- observability ----------------------------------------------------------
declare("MXNET_BLACKBOX", bool, False,
        "Enable mxblackbox, the always-on crash-forensics layer, at "
        "import: a bounded per-rank event journal (ring + append-only "
        "spill file) fed by alert transitions, health events, chaos "
        "fires, retry exhaustions, checkpoint/commit and elastic "
        "lifecycle events, plus crash-bundle emission on every "
        "abnormal-exit path. mxblackbox.enable() does the same at "
        "runtime. See docs/observability.md (Crash forensics).")
declare("MXNET_BLACKBOX_DIR", str, "mxblackbox",
        "Directory for mxblackbox artifacts: per-rank journal spill "
        "files, crash-bundle directories, per-rank bundle indexes, "
        "and supervisor INCIDENT-epoch<N>.json reports. The elastic "
        "Supervisor exports <dir>/blackbox to its workers.")
declare("MXNET_BLACKBOX_GEN", int, None,
        "Elastic generation number stamped into journal entries and "
        "crash-bundle metadata. Exported by the Supervisor to each "
        "worker generation; postmortem filters bundles by it.")
declare("MXNET_BLACKBOX_HISTORY", int, 64,
        "Crash-bundle index depth: each per-rank index file keeps "
        "the newest N bundle entries (the mxtriage capture-history "
        "shape; bundle directories themselves are not deleted).")
declare("MXNET_BLACKBOX_RING", int, 512,
        "Event-journal in-memory ring capacity (entries). The ring "
        "is what a crash bundle embeds; the on-disk spill file keeps "
        "the longer history.")
declare("MXNET_BLACKBOX_SPILL_MB", int, 8,
        "Event-journal spill-file size bound in MiB. Past it the "
        "spill rotates once to a '.1' suffix, bounding disk use at "
        "roughly twice this value per rank.")
declare("MXNET_BLACKBOX_STDERR_TAIL_KB", int, 64,
        "Per-rank stderr tail bound in KiB: the Supervisor keeps at "
        "most this much of each worker's stderr file per generation "
        "and attaches it to supervisor-side scrape bundles.")
declare("MXNET_BLACKBOX_TAIL", int, 200,
        "Journal-tail depth embedded in a crash bundle (newest N "
        "entries), and the scrape depth when the supervisor reads a "
        "dead rank's spill file.")
declare("MXNET_GOODPUT", bool, False,
        "Enable mxgoodput, the job-level goodput/badput wall-clock "
        "ledger, at import: productive step seconds vs compile / "
        "data_wait / checkpoint / preemption-recovery / retry-backoff "
        "/ comm-stall badput, summing to wall-clock. Rides the mxprof "
        "flight recorder; mxgoodput.enable() does the same at "
        "runtime. See docs/observability.md (Goodput accounting).")
declare("MXNET_GOODPUT_MIN", float, 0.9,
        "Goodput-ratio alert floor: the stock goodput_rules table "
        "(telemetry.alerts) pages when mx_goodput_ratio drops below "
        "this for the rule's for_-duration. Also the default "
        "production bar tools/goodput_report.py documents.")
declare("MXNET_GOODPUT_UNATTRIBUTED_MAX", float, 0.5,
        "Clean-run noise floor for the goodput known-answer gate "
        "(tools/goodput_report.py): the fraction of wall-clock a "
        "clean run may leave unattributed (host-side Python between "
        "spans) before the gate fails. Production jobs with real "
        "step times sit far below it.")
declare("MXNET_HEALTH", bool, False,
        "Enable mxhealth, the in-graph numerics telemetry layer, at "
        "import: the fused/SPMD step programs additionally emit "
        "grad/update/param norms and a global nonfinite count as tiny "
        "extra outputs of the already-compiled step (no extra "
        "dispatch). mxhealth.enable() does the same at runtime. See "
        "docs/observability.md (Training health).")
declare("MXNET_HEALTH_ALERT_TICK_MS", float, 1000.0,
        "Interval of the alert-engine background ticker "
        "(telemetry.alerts.AlertEngine.start()) in milliseconds.")
declare("MXNET_HEALTH_EVERY", int, 1,
        "Host-fetch cadence of the mxhealth numerics outputs: every "
        "Nth step's norms/nonfinite-count are handed to the monitor "
        "(asynchronously — the step never blocks on the fetch). The "
        "in-graph skip_step guard runs EVERY step regardless, and "
        "the raise policy checks every step synchronously (a "
        "cadence-skipped NaN step would otherwise be written back "
        "before the raise).",
        tunable=Tunable(lo=1, hi=64, scale="log"))
declare("MXNET_HEALTH_POLICY", str, "record",
        "What a nonfinite gradient step does: 'record' (event + "
        "metrics only), 'raise' (NonFiniteGradient from Trainer.step, "
        "params left at their pre-step values), or 'skip_step' "
        "(in-graph guard keeps params AND optimizer states "
        "bit-identical to the pre-step values, training continues).")
declare("MXNET_HEALTH_RATIO_MAX", float, 0.1,
        "Update/param-ratio drift threshold: a health sample whose "
        "update-norm / param-norm exceeds this records an "
        "'update-ratio' event (a healthy step moves parameters by a "
        "small fraction of their magnitude). 0 disables the check.")
declare("MXNET_HEALTH_RING", int, 512,
        "mxhealth bounded history: the last N health samples and the "
        "last N detector events are kept; memory is flat no matter "
        "how long the job runs.")
declare("MXNET_HEALTH_SPIKE_K", float, 8.0,
        "Rolling median/MAD spike threshold: a loss or grad-norm "
        "sample more than K median-absolute-deviations above the "
        "rolling median records a spike event.")
declare("MXNET_HEALTH_WINDOW", int, 64,
        "Window (samples) of the rolling median/MAD spike detectors "
        "for loss and grad-norm.")
declare("MXNET_IR_AUDIT", bool, False,
        "Enable mxir, the StableHLO program auditor, at every "
        "executable-cache compile (fused step, SpmdUpdater, "
        "SPMDTrainer, serving buckets): rules MX014-MX018 run over "
        "the lowered module text and violations increment "
        "mx_ir_violations_total{rule}. Opt-in; audit-off overhead is "
        "one boolean check per compile. See docs/static_analysis.md "
        "(Program audits).")
declare("MXNET_IR_OUT", str, "",
        "When set (and MXNET_IR_AUDIT is on), path the runtime audit "
        "hook rewrites with the cumulative MXIR.json report after "
        "each audited compile.")
declare("MXNET_IR_REPL_BYTES", int, 64 << 20,
        "MX015 threshold in bytes: a tensor at least this large "
        "pinned or returned REPLICATED in a multi-partition program "
        "is an oversized-replicated violation (every device "
        "materializes the full value - the PR 18 gather-replication "
        "bug class).")
declare("MXNET_IR_WIRE_TOL", float, 0.25,
        "MX017 drift tolerance: relative disagreement allowed between "
        "the static per-program wire-bytes model and the measured "
        "mx_collective_wire_bytes_total lane before the drift itself "
        "becomes a violation. The default absorbs the ~0.8% "
        "quant-scale overhead the static model does not price.")
declare("MXNET_PROFILER_AUTOSTART", bool, False,
        "Start the chrome-trace profiler at import (ref: "
        "MXNET_PROFILER_AUTOSTART).")
declare("MXNET_SAN", bool, False,
        "Enable mxsan, the runtime concurrency & dispatch sanitizer, "
        "at import — lock-order graph, Eraser-style lockset races on "
        "tracked caches, recompile-storm detection. Opt-in; see "
        "docs/static_analysis.md (Dynamic analysis).")
declare("MXNET_SAN_OUT", str, "MXSAN.json",
        "Path the mxsan pytest plugin writes its JSON report to at "
        "session end (relative to the working directory).")
declare("MXNET_SAN_SUPPRESS", str, "",
        "Comma-separated substrings; an mxsan violation whose message "
        "contains one is dropped — the escape hatch for a finding "
        "that is understood and accepted (document why where you set "
        "it).")
declare("MXNET_TELEMETRY", bool, False,
        "Enable telemetry span tracing at import (metrics are always "
        "on; this turns on trace-event emission — see "
        "docs/observability.md).")
declare("MXNET_MXPROF", bool, False,
        "Enable the mxprof flight recorder at import: an always-on "
        "(not capture-window-gated) ring buffer of per-step "
        "attribution records — phase seconds, collective bytes, "
        "data-wait, compile events, MFU, HBM. telemetry.enable() also "
        "engages it; dump via mxprof.dump() or SIGUSR2. See "
        "docs/observability.md (mxprof).")
declare("MXNET_MXPROF_RING", int, 512,
        "mxprof flight-recorder capacity: the last N step records are "
        "kept in a bounded ring; older steps fall off. Memory is flat "
        "no matter how long the job runs.")
declare("MXNET_MXPROF_HBM_EVERY", int, 0,
        "Sample per-device HBM allocator stats every N closed step "
        "records (0 = only on dump/snapshot). Allocator stats are one "
        "cheap PjRt call; the live-array fallback scan only runs on "
        "explicit dumps.")
declare("MXNET_MXPROF_DUMP", str, "",
        "Path the SIGUSR2 handler writes the mxprof flight-recorder "
        "dump to. Empty = mxprof-rank<r>.json in the working "
        "directory once dist.init() stamped the process rank "
        "(containerized multi-host ranks share pids and must not "
        "clobber on a shared filesystem), else mxprof-<pid>.json.")
declare("MXNET_TRIAGE_DIR", str, "mxtriage",
        "Base directory mxtriage deep-capture artifacts land in (one "
        "subdirectory per capture, indexed in index.json beside them). "
        "Relative paths resolve against the working directory.")
declare("MXNET_TRIAGE_SECONDS", float, 3.0,
        "Default wall-clock window of a deep capture when the caller "
        "passes neither steps= nor seconds= (SIGUSR1 and bare "
        "POST /profilez use it).")
declare("MXNET_TRIAGE_ALERT_INTERVAL_S", float, 600.0,
        "Minimum seconds between alert-triggered deep captures "
        "(action='deep_capture' rules): a flapping alert must not turn "
        "the profiler into a DoS on its own process. Suppressed "
        "triggers are counted in mx_triage_suppressed_total.")
declare("MXNET_TRIAGE_STEP_TIMEOUT_S", float, 60.0,
        "Watchdog for steps=N deep captures: if the expected step "
        "boundaries stop arriving (training stalled or finished), the "
        "capture force-stops after this many seconds instead of "
        "holding the admission slot forever.")
declare("MXNET_TRIAGE_HISTORY", int, 64,
        "Entries kept in the mxtriage capture index (index.json); "
        "older capture records rotate out of the index (their artifact "
        "directories are left on disk).")
declare("MXNET_PEAK_FLOPS", float, None,
        "Per-device peak FLOP/s used as the MFU denominator "
        "(mx_step_mfu). Unset = resolved from the device kind table "
        "(known TPU generations); unknown devices report MFU as null "
        "rather than a made-up ratio.")

# -- init / test harness ----------------------------------------------------
declare("MXNET_TEST_DEFAULT_CONTEXT", str, "",
        "Test-suite context override: 'tpu' or 'cpu' "
        "(ref: test_utils.default_context).")
declare("MXNET_USE_SIGNAL_HANDLER", bool, True,
        "Install faulthandler crash signal handlers at import (ref: "
        "src/initialize.cc).")
