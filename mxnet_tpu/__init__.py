"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities
of the Apache-MXNet-1.x lineage reference (`fegin/mxnet`).

Imperative NDArray with device contexts (`mx.tpu()`), tape autograd, Gluon
Block/HybridBlock/Trainer with hybridize()->XLA jit, Module compat, a
KVStore lowered to XLA collectives over ICI/DCN, data pipeline, optimizers,
metrics, model zoo.  See SURVEY.md at the repo root for the full layer map.

Conventional import:  import mxnet_tpu as mx
"""
from __future__ import annotations

import time as _time

# the zero of the set-up recorder (telemetry.tracing.phase): the span
# `mx.setup.import` runs from here to the last import below
_T_IMPORT = _time.perf_counter()

import os as _os
import sys as _sys

__version__ = "0.1.0"

from . import base
from .base import MXNetError
from . import util  # knob registry (util.env) — see docs/env_vars.md

# mxsan must engage BEFORE the submodule imports below so every
# module-level lock and tracked cache the framework builds is
# instrumented (enabling later only covers what is constructed later).
# Known gap: locks constructed while importing `base`/`util` above
# (e.g. the knob registry's own _LOCK) predate the patch and stay
# uninstrumented — the registry must exist to read the knob at all.
if util.env.get_bool("MXNET_SAN"):
    from .analysis import sanitizer as _mxsan

    _mxsan.enable(suppress=tuple(
        s.strip() for s in
        (util.env.get_str("MXNET_SAN_SUPPRESS") or "").split(",")
        if s.strip()))

# mxtune: apply the stored tuned knob config (if the config store has a
# matching winner) BEFORE the submodule imports below read their knobs.
# The overlay only fills knobs the process env leaves unset — explicit
# MXNET_* settings always win — and this call never raises and never
# initializes an accelerator backend.  See docs/autotune.md.
if util.env.get_bool("MXNET_AUTOTUNE"):
    from .autotune import startup as _mxtune_startup

    _mxtune_startup.apply_startup_overlay(framework_version=__version__)

# jax's own import, where this one is the first to ask for it: most of
# `mx.setup.import`, and not this package's to shorten (here, after
# mxsan and mxtune above, is where the submodules would import it)
_T_JAX = None           # (start, end) of that import, where it ran here
if "jax" not in _sys.modules:
    _t = _time.perf_counter()
    import jax as _jax  # noqa: F401
    _T_JAX = (_t, _time.perf_counter())

from . import context
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import ops
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray, waitall
from . import autograd
from . import random
from . import profiler
from . import telemetry
from . import serialization
from . import operator
from . import storage
from . import initialize as _initialize

_initialize.initialize()

_import_span = telemetry.tracing.record_phase(
    "mx.setup.import", _T_IMPORT, _time.perf_counter())
if _T_JAX is not None:
    telemetry.tracing.record_phase("mx.setup.import.jax", *_T_JAX,
                                   parent=_import_span["id"])
del _import_span

if _os.environ.get("DMLC_ROLE") == "server":
    # reference semantics: a server-role process parks inside the import
    # (kvstore_server._init_kvstore_server_module) until the tracker
    # ends the job — it must NOT fall through into the training script
    from . import kvstore_server as _kvstore_server  # noqa: F401

__all__ = [
    "MXNetError", "Context", "cpu", "gpu", "tpu", "current_context",
    "num_gpus", "num_tpus", "nd", "ndarray", "NDArray", "waitall",
    "autograd", "random", "profiler", "telemetry",
]


def __getattr__(name):
    # Subsystems that import lazily to keep `import mxnet_tpu` light and to
    # tolerate partial builds during bring-up.
    import importlib

    lazy = {"gluon", "optimizer", "initializer", "metric", "kvstore",
            "lr_scheduler", "io", "image", "symbol", "module", "parallel",
            "callback", "model", "test_utils", "engine", "runtime",
            "visualization", "recordio", "contrib", "monitor", "name", "rnn",
            "attribute", "resource", "rtc", "kvstore_server", "serving",
            "resilience", "compile_cache"}
    if name == "sym":
        mod = importlib.import_module(".symbol", __name__)
        globals()["sym"] = mod
        return mod
    if name == "kv":
        mod = importlib.import_module(".kvstore", __name__)
        globals()["kv"] = mod
        return mod
    if name == "AttrScope":
        from .attribute import AttrScope

        globals()["AttrScope"] = AttrScope
        return AttrScope
    if name in ("mod", "viz"):
        target = {"mod": "module", "viz": "visualization"}[name]
        mod = importlib.import_module(f".{target}", __name__)
        globals()[name] = mod
        return mod
    if name == "mon":
        mod = importlib.import_module(".monitor", __name__)
        globals()["mon"] = mod
        return mod
    if name in lazy:
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'mxnet_tpu' has no attribute {name!r}")
