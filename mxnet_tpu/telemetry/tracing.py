"""Span tracing: trace/span IDs with parent links, emitted into the
profiler's chrome-trace buffer.

A *span* is one timed phase (`"ph": "X"`) carrying `trace_id`,
`span_id`, and `parent_id` in its `args`, so chrome://tracing shows the
nesting and `tools/trace_report.py` can reassemble a request or a
training step from the flat event list.  Cross-thread hand-offs (a
serving request enqueued on one thread, executed by the batcher
thread) are linked with chrome flow arrows (`"ph": "s"` / `"ph": "f"`)
keyed by the trace id.

Enablement is ONE module-level flag (`_ENABLED`): instrument sites on
hot paths read it directly (`tracing._ENABLED`) so the disabled cost
is a single predicate check.  Span *events* are only appended while
the profiler is running (the capture window is what bounds the buffer;
`profiler.dump(finished=True)` clears it); metric side-effects
(histograms/counters) follow the flag alone, so a long-lived server
can scrape `/metrics` without ever starting a trace capture.

Thread-local context (`contextvars`) carries the current span so
nested `with span(...)` blocks parent automatically; cross-thread
parents are passed explicitly (`trace_id=` / `parent_id=`).
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import threading
import time
from typing import Optional

import jax

from .. import _T_IMPORT
from .. import profiler as _prof
from ..util import env

__all__ = [
    "enable", "disable", "enabled", "Span", "span", "current_span",
    "new_trace_id", "record_complete", "flow_start", "flow_end",
    "counter_event", "capture_active", "set_sink", "set_rank",
    "annotation", "phase", "record_phase", "startup_spans",
    "startup_seconds",
]

_ENABLED = env.get_bool("MXNET_TELEMETRY")

# the mxprof flight recorder (telemetry/mxprof) registers itself here;
# a non-None sink makes spans *measure* (active() below) even with the
# telemetry flag off and no profiler capture — that is the "always-on"
# half of step attribution.  Instrument sites read the module global
# directly so the disabled cost stays one predicate check.
_SINK = None

# process rank (jax.process_index), stamped into span args once known
# (parallel.dist.init sets it) so multi-rank trace dumps can be merged
# and attributed per rank by tools/trace_report.py --merge.
_RANK: Optional[int] = None

_span_ctx: "contextvars.ContextVar[Optional[Span]]" = \
    contextvars.ContextVar("mx_telemetry_span", default=None)

# span ids only need process-uniqueness; trace ids cross processes
# (they name a request end-to-end) so they get random 64-bit hex
_span_seq = itertools.count(1)
_seq_lock = threading.Lock()


def enable() -> None:
    """Turn instrumentation on (metrics always; trace events while the
    profiler is running)."""
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def active() -> bool:
    """Whether instrumentation sites should do any work at all: the
    telemetry flag, a running profiler capture, OR an attached mxprof
    flight recorder (which needs phase durations even when nothing
    else is on)."""
    return _ENABLED or _prof.is_running() or _SINK is not None


def capture_active() -> bool:
    """Whether a *capture* (telemetry or profiler) is on — excludes the
    mxprof sink.  Sites whose instrumented variant changes execution
    shape (e.g. the SPMD phased step, which serializes one program
    into three dispatches) key on this, so an always-on flight
    recorder never distorts what it measures."""
    return _ENABLED or _prof.is_running()


def set_sink(sink) -> None:
    """Attach (or detach, with None) the mxprof flight recorder.  The
    sink receives ``on_event(name, cat, duration_s, args)`` for every
    finished span and retroactive record, on the finishing thread."""
    global _SINK
    _SINK = sink


def set_rank(r: Optional[int]) -> None:
    """Record this process's job rank; spans emitted from here on carry
    ``args.rank`` so per-rank dumps can be clock-aligned and merged."""
    global _RANK
    _RANK = None if r is None else int(r)


def new_trace_id() -> str:
    return os.urandom(8).hex()


def _next_span_id() -> str:
    with _seq_lock:
        return f"{next(_span_seq):x}"


class Span:
    """One timed phase.  Use the `span()` context manager on a single
    thread; construct directly (then `finish()`) for hand-built spans
    that start and end on different call paths."""

    __slots__ = ("name", "cat", "trace_id", "span_id", "parent_id",
                 "args", "t0", "duration", "_token", "_metric")

    def __init__(self, name: str, cat: str = "user",
                 trace_id: Optional[str] = None,
                 parent_id: Optional[str] = None,
                 args: Optional[dict] = None, metric=None,
                 root: bool = False):
        parent = None if root else _span_ctx.get()
        if parent_id is None and parent is not None:
            parent_id = parent.span_id
            if trace_id is None:
                trace_id = parent.trace_id
        self.name, self.cat = name, cat
        self.trace_id = trace_id or new_trace_id()
        self.span_id = _next_span_id()
        self.parent_id = parent_id
        self.args = args
        self.t0 = time.perf_counter()
        self.duration = None
        self._token = None
        self._metric = metric

    def attach(self) -> "Span":
        """Make this span the ambient parent for the current context."""
        self._token = _span_ctx.set(self)
        return self

    def finish(self, end: Optional[float] = None) -> float:
        """Close the span: record the chrome event (if capturing) and
        observe the attached histogram (if telemetry is enabled).
        Returns the duration in seconds."""
        t1 = time.perf_counter() if end is None else end
        self.duration = t1 - self.t0
        if self._token is not None:
            try:
                _span_ctx.reset(self._token)
            except ValueError:
                pass  # finished on a different thread than attach()ed
            self._token = None
        record_complete(self.name, self.cat, self.t0, self.duration,
                        trace_id=self.trace_id, span_id=self.span_id,
                        parent_id=self.parent_id, args=self.args)
        if _ENABLED and self._metric is not None:
            self._metric.observe(self.duration)
        return self.duration


@contextlib.contextmanager
def span(name: str, cat: str = "user", trace_id: Optional[str] = None,
         parent_id: Optional[str] = None, args: Optional[dict] = None,
         metric=None):
    """`with span("forward", cat="training"): ...` — no-op (yields
    None) when neither telemetry nor the profiler is active.  With only
    the mxprof sink attached, the span is measured on a minimal path
    (two clock reads, no Span object, no ids, no context switch) so
    always-on attribution stays within its overhead budget."""
    if not (_ENABLED or _prof.is_running()):
        snk = _SINK
        if snk is None:
            yield None
            return
        t0 = time.perf_counter()
        try:
            yield None
        finally:
            snk.on_event(name, cat, time.perf_counter() - t0, args)
        return
    s = Span(name, cat, trace_id=trace_id, parent_id=parent_id,
             args=args, metric=metric).attach()
    try:
        yield s
    finally:
        s.finish()


def annotation(name: str, **stats):
    """A host span in the ``jax.profiler`` trace (the ``.xplane.pb``),
    beside the device lines and on their clock, which the chrome
    buffer's ``perf_counter`` is not: the one span kind that lets a
    gap on the device be laid against what the host was doing.  Nest
    them to parent them; ``stats`` (``step=12``) ride on the event.  It
    gates itself: outside a profiler session entering and leaving one
    costs under a microsecond, so sites use it unconditionally."""
    return jax.profiler.TraceAnnotation(name, **stats)


# ---- time to the first step: the set-up recorder ----------------------
# Always on, for sites that run in set-up only (import, parameter init,
# cast, placement, a program's trace / lowering / backend build, the
# first call of a new executable).  No flag, no environment variable, no
# sink: a site costs two clock reads and a list append, and nothing here
# is ever entered from the steady path of a step.  See
# docs/observability.md, "Time to first step".

#: `perf_counter` when `import mxnet_tpu` began: every record's zero
_T0 = _T_IMPORT
_STARTUP: list = []     # the records, in the order their phases opened
_phase_ctx: "contextvars.ContextVar[Optional[dict]]" = \
    contextvars.ContextVar("mx_startup_phase", default=None)


def record_phase(name: str, start: float, end: float,
                 parent: Optional[int] = None, **stats) -> dict:
    """File one already-measured set-up record (`start` and `end` as
    `perf_counter` read them): for a span that closed before this module
    could be imported, the package's own import."""
    return _new_record(name, parent, start, end, 1, stats)


def _new_record(name, parent, start, end, calls, stats) -> dict:
    with _seq_lock:     # a record's id is its place in the list
        rec = {"id": len(_STARTUP), "name": name, "parent": parent,
               "start": start - _T0,
               "end": None if end is None else end - _T0,
               "seconds": 0.0 if end is None else end - start,
               "calls": calls, "stats": stats}
        _STARTUP.append(rec)
    return rec


@contextlib.contextmanager
def phase(name: str, merge: bool = False, **stats):
    """`with phase("mx.setup.place", arrays=n, bytes=b) as rec:` one
    phase of set-up: an `annotation` (so it is on the device trace's
    clock whenever someone profiles a start-up) and one
    record in the process-wide list behind `startup_spans()`:

        {"id", "name", "parent": id of the enclosing phase or None,
         "start", "end": seconds on `perf_counter` since `import
         mxnet_tpu` began, "seconds": end - start, "calls": 1,
         "stats": {...}}

    The record is yielded, so a site can add to `rec["stats"]` what it
    learns inside (`origin` of a backend build).  `merge=True` is for a
    site entered once an item (a parameter's init): an entry whose
    predecessor under the same parent has the same name accumulates into
    that record: `seconds` and the numeric `stats` add up, `calls`
    counts, `start` is the first entry and `end` the last exit.  So one
    model's parameters make one record, and an init after another phase
    has run (a second model, a deferred shape) a record of its own.  A phase entered inside a phase of its own
    name (a block's `cast` reaching its children's) is that phase."""
    outer = _phase_ctx.get()
    if outer is not None and outer["name"] == name:
        yield outer
        return
    parent = None if outer is None else outer["id"]
    rec = None
    if merge:
        last = next((r for r in reversed(_STARTUP)
                     if r["parent"] == parent), None)
        if last is not None and last["name"] == name:
            rec = last
    with annotation(name, **stats):
        start = time.perf_counter()
        if rec is None:
            rec = _new_record(name, parent, start, None, 0, stats)
        else:
            for k, v in stats.items():
                rec["stats"][k] = rec["stats"].get(k, 0) + v
        token = _phase_ctx.set(rec)
        try:
            yield rec
        finally:
            _phase_ctx.reset(token)
            end = time.perf_counter()
            rec["end"] = end - _T0
            rec["seconds"] += end - start
            rec["calls"] += 1


def startup_spans() -> list:
    """Copies of the set-up records so far, oldest first (see `phase`).
    A phase still open has `end` None."""
    return [dict(r, stats=dict(r["stats"])) for r in _STARTUP]


def startup_seconds() -> dict:
    """`{name: self-seconds}` over the records: a phase's `seconds` less
    what its children cover, summed by name."""
    spans = list(_STARTUP)
    own = [r["seconds"] for r in spans]
    for r in spans:
        if r["parent"] is not None:
            own[r["parent"]] -= r["seconds"]
    out: dict = {}
    for r, s in zip(spans, own):
        out[r["name"]] = out.get(r["name"], 0.0) + s
    return out


def current_span() -> Optional[Span]:
    return _span_ctx.get()


def record_complete(name: str, cat: str, t0: float, duration: float,
                    trace_id: Optional[str] = None,
                    span_id: Optional[str] = None,
                    parent_id: Optional[str] = None,
                    args: Optional[dict] = None) -> None:
    """Append one already-measured X event (used for retroactive spans
    like queue-wait, where the start is a stored timestamp).  The
    mxprof sink — when attached — sees every event regardless of the
    profiler capture window: that is what makes the flight recorder
    always-on."""
    snk = _SINK
    if snk is not None:
        snk.on_event(name, cat, duration, args)
    if not _prof.is_running():
        return
    a = dict(args) if args else {}
    if trace_id is not None:
        a["trace_id"] = trace_id
    if span_id is not None:
        a["span_id"] = span_id
    if parent_id is not None:
        a["parent_id"] = parent_id
    if _RANK is not None:
        a["rank"] = _RANK
    ev = {"name": name, "ph": "X", "cat": cat, "ts": t0 * 1e6,
          "dur": duration * 1e6, "pid": os.getpid(),
          "tid": threading.get_ident()}
    if a:
        ev["args"] = a
    _prof.append_event(ev)


# ---- chrome flow arrows (cross-thread request hand-off) ---------------
# flow events bind on (cat, name, id): emit the start where the request
# is enqueued and the finish where the batch executes, both keyed by the
# request's trace id.

def flow_start(trace_id: str, name: str = "request",
               cat: str = "serving") -> None:
    _prof.append_event({
        "name": name, "ph": "s", "cat": cat, "id": trace_id,
        "ts": time.perf_counter() * 1e6, "pid": os.getpid(),
        "tid": threading.get_ident()})


def flow_end(trace_id: str, name: str = "request",
             cat: str = "serving") -> None:
    _prof.append_event({
        "name": name, "ph": "f", "bp": "e", "cat": cat, "id": trace_id,
        "ts": time.perf_counter() * 1e6, "pid": os.getpid(),
        "tid": threading.get_ident()})


def counter_event(name: str, value, cat: str = "user") -> None:
    """Chrome counter-lane sample (`"ph": "C"`) — the trace-side mirror
    of a registry counter/gauge update."""
    _prof.append_event({
        "name": name, "ph": "C", "cat": cat,
        "ts": time.perf_counter() * 1e6, "pid": os.getpid(),
        "args": {name: value}})
