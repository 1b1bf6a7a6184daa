"""Where JAX's own persistent compilation cache lives, decided once.

The ``.mxcc`` store beside this module persists the framework's AOT
executables and is off unless ``MXNET_COMPILE_CACHE_DIR`` is set; this
module is about the cache JAX itself keeps for everything ``jit`` and
``lower().compile()`` build.  The measurement entry points
(``chip_smoke.py``, ``bench.py``, ``bench_all.py``'s children, the
on-chip test lane) call :func:`configure` before their first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it on its own and
    nothing is set in code, so whoever placed the cache from outside
    (a machine that keeps one across runs) finds it used;
  * unset: a FIXED directory inside the checkout, ``<repo>/.jax_cache``
    (git-ignored).  Never a temporary directory, a pid or a timestamp: a
    cache that moves is never hit again.
"""
from __future__ import annotations

import os
import threading
from typing import Dict

__all__ = ["JaxCache", "configure", "counts", "seconds", "DEFAULT_DIR"]

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
# JAX's own stopwatches, one event a program and stage: every program of
# the process, whoever built it
_STAGE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}


_lock = threading.Lock()
_counts = {"hits": 0, "misses": 0}
_seconds = dict.fromkeys(_STAGE.values(), 0.0)
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == _HIT or event == _MISS:
        with _lock:
            _counts["hits" if event == _HIT else "misses"] += 1


def _on_duration(event: str, duration: float, **_kw) -> None:
    stage = _STAGE.get(event)
    if stage is not None:
        with _lock:
            _seconds[stage] += duration


def _listen() -> None:
    """Start listening to JAX's monitoring events, once (under _lock)."""
    global _listening
    if not _listening:
        import jax

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def counts() -> Dict[str, int]:
    """{"hits": executables JAX served from its persistent cache,
    "misses": executables compiled and written there} in this process,
    counted from the first call (which starts listening).  Programs
    under JAX's own thresholds (compile time, entry size) are neither.
    A rise in "hits" across one ``lower().compile()`` is the only sign
    that the executable was loaded and not built."""
    with _lock:
        _listen()
        return dict(_counts)


def seconds() -> Dict[str, float]:
    """{"trace", "lower", "backend", "cache_retrieval"}: JAX's own
    duration events summed over EVERY program this process traced,
    lowered and built since the first call of this or of :func:`counts`:
    jaxpr trace, jaxpr to MLIR module, the backend's compile with a
    cache load inside it, and of that the cache retrieval alone.
    "trace" is an upper bound: a jitted function called inside another,
    as most of `jax.numpy` is, counts by itself and again in its
    caller's.  What the set-up phases `mx.build.*`
    (`telemetry.tracing.phase`) measure for the framework's own programs
    is part of these totals; the rest is what else compiled here."""
    with _lock:
        _listen()
        return dict(_seconds)


class JaxCache:
    """The cache directory in effect, and how often JAX has hit and
    missed it in this process since this object was made."""

    def __init__(self, directory: str):
        self.directory = directory
        self._base = counts()

    def counts(self) -> Dict[str, int]:
        """:func:`counts` since this object was made."""
        now = counts()
        return {k: now[k] - self._base[k] for k in now}


def configure() -> JaxCache:
    """Place the cache (see the module docstring) and start counting.
    Call it once per process, before the first compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return JaxCache(jax.config.jax_compilation_cache_dir)
