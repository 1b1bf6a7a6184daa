"""From a profiler trace (.xplane.pb) to the numbers the per-layer metrics
read.  One reduction, kept with the benchmark, read with JAX alone
(`jax.profiler.ProfileData`, no TensorFlow).

What is read:
  * each `/device:TPU:n` plane: the "XLA Ops" line (the device's serial
    op timeline) for busy intervals, op names and collectives; the "XLA
    Modules" line (one event per execution of a compiled program) to cut
    the window into steps;
  * the host plane: the benchmark's own spans, `bench.step_call` around
    each trainer.step and `bench.block` around each wait.

The window is the last `steps` executions of the step program on chip 0,
from the end of the execution before them to the end of the last: whole
periods of a steady loop, each with the gap before it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass

# A collective op, three ways this XLA prints one on the "XLA Ops" line
# (seen by hand in the dp=4 ResNet-50 trace, PR 22):
#   * its HLO opcode directly before the operand list: `all-reduce(`,
#     `all-gather(`, `all-reduce-start(` ... (an op that only consumes a
#     collective's result names it `%all-reduce.7`, which does not match);
#   * a custom fusion that IS a collective: `fusion(...), kind=kCustom,
#     calls=%all-reduce-scatter.25` (the combined gradient reduce-scatter);
#   * the two halves of an asynchronous one, labelled
#     `%async-collective-start.N` / `%async-collective-done.N`.
# Compute fusions with `calls=%async_collective_fusion.N` are NOT counted:
# they are the compute an asynchronous collective hides under.
_KINDS = (r"all-reduce-scatter|all-reduce|all-gather|reduce-scatter"
          r"|collective-permute|all-to-all")
_OPCODE = re.compile(rf"(?<![%\w.-])({_KINDS})(?:-(start|done))?\(")
_CALLS = re.compile(rf"kind=kCustom, calls=%({_KINDS})[.\d]*(?![\w-])")
_ASYNC = re.compile(r"^%?async-collective-(start|done)[.\d]*$")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


# ---- interval arithmetic (closed-open [start, end) in ns) ---------------

def union(intervals):
    """Merged, sorted, non-overlapping."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of union(a) that union(b) does not cover."""
    out = []
    b = union(b)
    for s, e in union(a):
        for bs, be in b:
            if be <= s:
                continue
            if bs >= e:
                break
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


# ---- names ---------------------------------------------------------------

def label(name: str) -> str:
    """`%fusion.123 = bf16[...] fusion(...)` -> `fusion`: the XLA label
    with its numbering removed.  Unstable across XLA versions."""
    lhs = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", lhs)


def collective_kind(name: str):
    """("all-reduce", "start" | "done" | "") where the event is a
    collective op, else None."""
    lhs, _, text = name.partition(" = ")
    m = _ASYNC.match(lhs)
    if m:
        return "async-collective", m.group(1)
    m = _OPCODE.search(text or name)
    if m:
        return m.group(1), m.group(2) or ""
    m = _CALLS.search(text)
    return (m.group(1), "") if m else None


def _number(name: str) -> str:
    """`%all-reduce-start.12 = ...` -> `.12`: what a -start and its -done
    share."""
    return re.search(r"([.\d]*)$", name.partition(" = ")[0]).group(1)


@dataclass
class Chip:
    ops: list                     # (start, end, name) inside the window
    busy: list                    # merged busy intervals inside the window
    busy_ns: float
    collective_ns: float
    collective_exposed_ns: float
    collectives: list             # (start, end, opcode) start..done


@dataclass
class Trace:
    steps: int
    window: tuple                 # (start, end) ns, chip 0's clock
    chips: dict                   # device index -> Chip
    spans: dict                   # span name -> [(start, end)] host clock

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    @property
    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the chips."""
        return sum(c.busy_ns for c in self.chips.values()) \
            / len(self.chips) / 1e9

    def breakdown(self) -> dict:
        """The ten ops with most device time on chip 0 (XLA's labels:
        unstable names), and the five longest idle gaps on chip 0, each
        with the benchmark span the host was in at its start."""
        by_label = defaultdict(float)
        chip = self.chips[0]
        for s, e, name in chip.ops:
            by_label[label(name)] += (e - s) / 1e9
        ops = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
        gaps = subtract([self.window], chip.busy)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:5]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self._host_span_at(s), (e - s) / 1e9]
                              for s, e in gaps]}

    def _host_span_at(self, t) -> str:
        for name, spans in self.spans.items():
            if any(s <= t < e for s, e in spans):
                return name
        return "outside the benchmark's spans"


def _collective_intervals(tagged):
    """(start, end, kind) per collective among `tagged` ops (start, end,
    name, collective_kind(name)): a synchronous one is its own event; an
    asynchronous one runs from its -start to the -done with the same
    number."""
    out, started = [], {}
    for s, e, name, tag in tagged:
        if tag is None:
            continue
        kind, phase = tag
        if phase == "start":
            started[kind, _number(name)] = s
        elif phase == "done":
            out.append((started.pop((kind, _number(name)), s), e, kind))
        else:
            out.append((s, e, kind))
    return out


def read(path: str):
    """(ops per chip, step-program executions per chip, host spans) as
    lists of (start_ns, end_ns, name), straight from the file."""
    from jax.profiler import ProfileData

    device_ops, modules, spans = {}, {}, defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                into = device_ops if line.name == "XLA Ops" else modules
                into[int(m.group(1))] = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events]
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return device_ops, modules, dict(spans)


def reduce(path: str, steps: int):
    """The Trace of the last `steps` step executions in the file, or None
    where it holds no TPU plane (a CPU rehearsal)."""
    return from_events(*read(path), steps=steps)


def from_events(device_ops, modules, spans, steps: int):
    if not device_ops:
        return None
    # the step program is the module with most device time on chip 0
    by_module = defaultdict(float)
    for s, e, name in modules[0]:
        by_module[name] += e - s
    step_module = max(by_module, key=by_module.get)
    runs = sorted((s, e) for s, e, name in modules[0]
                  if name == step_module)
    if len(runs) < steps + 1:
        raise ValueError(f"{len(runs)} executions of {step_module!r} in "
                         f"the trace, need {steps + 1}")
    window = (runs[-steps - 1][1], runs[-1][1])
    chips = {}
    for i, ops in sorted(device_ops.items()):
        ops = sorted((max(s, window[0]), min(e, window[1]), name)
                     for s, e, name in ops
                     if min(e, window[1]) > max(s, window[0]))
        busy = union((s, e) for s, e, _ in ops)
        tagged = [(s, e, name, collective_kind(name)) for s, e, name in ops]
        coll = _collective_intervals(tagged)
        others = [(s, e) for s, e, _, tag in tagged if tag is None]
        coll_iv = [(s, e) for s, e, _ in coll]
        chips[i] = Chip(
            ops=ops, busy=busy, busy_ns=total(busy),
            collective_ns=total(union(coll_iv)),
            collective_exposed_ns=total(subtract(coll_iv, others)),
            collectives=coll)
    return Trace(steps=steps, window=window, chips=chips,
                 spans={k: sorted(v) for k, v in spans.items()})


def describe(path: str) -> dict:
    """Planes, lines and event counts: for looking at a trace by hand."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        out[plane.name] = {
            line.name: sum(1 for _ in line.events) for line in plane.lines}
    return out
