"""Device time by the program's own scopes, and the program's host spans.

A device trace names instructions (`%fusion.14 = ...`), not scopes: the
profiler binding the benchmark has (`jax.profiler.ProfileData`) does not
expose the `op_name` an "XLA Ops" event was compiled from.  The program
hands out the join itself, `mxnet_tpu.parallel.spmd.step_programs()`:
for every step executable `{"module", "origin", "scoped", "ops":
{instruction: op_name}}`, where an `op_name` is the name stack the
instruction was traced under,

    jit(mx_train_step)/jvp(bertmodel0)/encoder/layer3/attn/dot_product_attention/exp
    jit(mx_train_step)/transpose(jvp(resnetv10))/stage2/unit1/batchnorm0/BatchNorm/reduce_sum
    jit(mx_train_step)/mx.update/adam_update/add

What is read here, from chip 0's ops inside the traced window
(`harness/trace_reduce.py:Trace`):

  * phase of an op: *backward* if its `op_name` holds `transpose(`, else
    *forward* if it holds `jvp(`, else *update* if it holds `mx.update`,
    else *other* (copies, ops of the tiny programs between two steps,
    anything traced outside a scope, anything not in the table);
  * op scope: the innermost path component that is a registered op name
    (`BatchNorm`, `dot_product_attention`, ...), the last component (the
    JAX primitive) left out;
  * every instant of busy time goes to exactly one op, the one that
    started last among those running (a `while` and the ops of its body
    overlap on the "XLA Ops" line), so the phases add up to the busy time
    `device_step_ms` is made of;
  * from the file again (`trace_reduce.read` keeps only `bench.*`): the
    executions of the step module on chip 0, so that an instruction of
    another program that happens to share a name is never looked up, and
    the program's `mx.step.*` host spans that start inside the counted
    `bench.step_call` spans.

`read(run)` is what the readers in `layer_metrics/` call.  It gives None
where there is no trace, where the program has no table (the parent of
PR 24) or where its step executable is not `scoped` (loaded from a
compile cache written before the scopes existed: they are not in JAX's
cache key).
"""
from __future__ import annotations

import heapq
import json
import os
import re
import statistics
from collections import defaultdict
from dataclasses import dataclass

from harness import trace_reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE_DIR = os.path.join(REPO, ".bench_trace")     # as benchmark/run.py

PHASES = ("forward", "backward", "update", "other")
_WRAPPED = re.compile(r"^(?:transpose\(|jvp\()+(.*?)\)+$")


def instruction(event_name: str) -> str:
    """`%fusion.14 = bf16[...] fusion(...)` -> `fusion.14`."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def phase(op_name: str) -> str:
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    if "mx.update" in op_name:
        return "update"
    return "other"


def op_scope(op_name: str, registered) -> str | None:
    """The innermost component of the path that is a registered op name.
    XLA joins the names of merged instructions with `;`: the first path
    that holds one decides."""
    for path in op_name.split(";"):
        for part in reversed(path.split("/")[:-1]):
            m = _WRAPPED.match(part)
            part = m.group(1) if m else part
            if part in registered:
                return part
    return None


def self_times(ops):
    """[(ns, (start, end, name))] for `ops` [(start, end, name)]: every
    instant in which some op runs is given to the op that started last
    among those running, so the times add up to the union of the
    intervals."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    edges = sorted({t for s, e, _ in ops for t in (s, e)})
    out = [0.0] * len(ops)
    running, k = [], 0          # heap of (-start, -index, end)
    for a, b in zip(edges, edges[1:]):
        while k < len(ops) and ops[k][0] <= a:
            heapq.heappush(running, (-ops[k][0], -k, ops[k][1]))
            k += 1
        while running and running[0][2] <= a:
            heapq.heappop(running)
        if running:
            out[-running[0][1]] += b - a
    return list(zip(out, ops))


@dataclass
class ScopeTime:
    steps: int
    busy_ns: float
    phase_ns: dict        # one of PHASES -> ns in the window, chip 0
    op_ns: dict           # op scope -> forward + backward ns
    label_ns: dict        # (op scope, XLA label) -> forward + backward ns
    other_ns: dict        # XLA label -> ns of what no scope placed
    missing_ns: float     # of that, step ops the table does not hold at all
    host_spans: dict      # "mx.step.dispatch" -> [ns] in the counted calls
    program: dict         # module, origin and size of the table used

    def ms_per_step(self, ns: float) -> float:
        return ns / self.steps / 1e6

    def report(self) -> dict:
        """What a person reads beside the metrics (an `[info]` line)."""
        ms = self.ms_per_step
        top = sorted(self.other_ns.items(), key=lambda kv: -kv[1])[:8]
        return {
            "program": self.program,
            "phase_ms": {p: ms(self.phase_ns[p]) for p in PHASES},
            "op_scope_ms": {k: ms(v) for k, v in sorted(
                self.op_ns.items(), key=lambda kv: -kv[1])[:12]},
            # which of XLA's (unstable) labels an op scope's time is in
            "op_scope_label_ms": {"/".join(k): ms(v) for k, v in sorted(
                self.label_ns.items(), key=lambda kv: -kv[1])[:12]},
            "unattributed_ms": {k: ms(v) for k, v in top},
            "not_in_table_ms": ms(self.missing_ns),
            "host_span_median_ms": {
                k: statistics.median(v) / 1e6
                for k, v in sorted(self.host_spans.items()) if v}}


def attribute(trace, program, registered, step_runs=None,
              host_spans=None) -> ScopeTime:
    """Chip 0's busy time in `trace`'s window by phase and by op scope,
    through `program["ops"]`.  `step_runs`, where known, are the
    executions [(start, end)] of the step module on chip 0: an op outside
    them belongs to another program and is *other* whatever its name."""
    chip = trace.chips[0]
    table = program["ops"]
    runs = sorted(step_runs) if step_runs is not None else None
    phase_ns = dict.fromkeys(PHASES, 0.0)
    op_ns, other_ns, missing_ns = defaultdict(float), defaultdict(float), 0.0
    label_ns = defaultdict(float)
    for ns, (start, _end, name) in self_times(chip.ops):
        if not ns:
            continue
        in_step = runs is None or any(s <= start < e for s, e in runs)
        op_name = table.get(instruction(name)) if in_step else ""
        if op_name is None:
            op_name, missing_ns = "", missing_ns + ns
        ph = phase(op_name)
        phase_ns[ph] += ns
        if ph == "other":
            other_ns[trace_reduce.label(name)] += ns
        elif ph != "update":
            scope = op_scope(op_name, registered)
            if scope is not None:
                op_ns[scope] += ns
                label_ns[scope, trace_reduce.label(name)] += ns
    return ScopeTime(steps=trace.steps, busy_ns=chip.busy_ns,
                     phase_ns=phase_ns, op_ns=dict(op_ns),
                     label_ns=dict(label_ns),
                     other_ns=dict(other_ns), missing_ns=missing_ns,
                     host_spans=host_spans or {},
                     program={"module": program["module"],
                              "origin": program["origin"],
                              "instructions": len(table)})


def from_file(path: str):
    """-> (chip 0's module executions [(start, end, name)], the program's
    host spans {name: [(start, end)]}) of one .xplane.pb."""
    from jax.profiler import ProfileData

    modules, spans = [], defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("mx.step"):
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return modules, dict(spans)


def step_module(modules):
    """(name without its `(id)`, [(start, end)]) of the module with most
    device time: the rule `trace_reduce.from_events` cuts the window by."""
    by_name = defaultdict(float)
    for s, e, name in modules:
        by_name[name] += e - s
    name = max(by_name, key=by_name.get)
    return (name.split("(")[0],
            [(s, e) for s, e, n in modules if n == name])


def counted(spans, trace):
    """Durations of the program's spans that start inside the last
    `trace.steps` `bench.step_call` spans."""
    calls = trace.spans.get("bench.step_call", [])[-trace.steps:]
    return {name: [e - s for s, e in found
                   if any(cs <= s < ce for cs, ce in calls)]
            for name, found in spans.items()}


def compute(trace, programs, registered, path=None):
    """The ScopeTime of `trace` under the newest of `programs` whose
    module is the step's (the file at `path` says which that is), or
    None where no such program is scoped."""
    runs, spans = None, {}
    if path is not None:
        modules, spans = from_file(path)
        if modules:
            name, runs = step_module(modules)
            programs = [p for p in programs if p["module"] == name]
    if not programs or not programs[-1]["scoped"]:
        return None
    return attribute(trace, programs[-1], registered, step_runs=runs,
                     host_spans=counted(spans, trace))


def _from_the_program():
    """(step_programs(), registered op names), or ([], ()) from a program
    that has no table."""
    try:
        from mxnet_tpu.ops.registry import list_ops
        from mxnet_tpu.parallel.spmd import step_programs
    except ImportError:
        return [], ()
    return step_programs(), frozenset(list_ops())


def read(run):
    """The ScopeTime of a run's trace, computed once per trace, or None
    (see the module docstring)."""
    trace = run["trace"]
    if trace is None:
        return None
    memo = vars(trace)
    if "_scope_time" not in memo:
        programs, registered = _from_the_program()
        st = None
        if programs:        # else nothing to join: leave the file unread
            try:
                path = trace_reduce.newest_xplane(TRACE_DIR)
            except FileNotFoundError:
                path = None
            st = compute(trace, programs, registered, path)
        memo["_scope_time"] = st
        if st is not None:
            print("[info] " + json.dumps({"scope_time": st.report()}),
                  flush=True)
    return memo["_scope_time"]


def phase_ms(run, which: str):
    st = read(run)
    return None if st is None else st.ms_per_step(st.phase_ns[which])


def op_ms(run, scope: str):
    st = read(run)
    return None if st is None else st.ms_per_step(st.op_ns.get(scope, 0.0))
