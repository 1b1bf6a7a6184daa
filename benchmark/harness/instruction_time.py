"""Device time by kind of work, against the FLOPs the step executes.

`scope_time` splits chip 0's busy time by the scope an instruction is
booked to.  That cannot say what the instruction IS: XLA's label
`fusion` names a matmul at its roof and a float32 relayout alike, a
weight gradient's fusion carries the optimizer's update in its epilogue
under the matmul's name, and nothing says which instructions are the
forward done again under remat.  The program says it per instruction,
`mxnet_tpu.parallel.spmd.step_programs()[i]["instructions"]`:

    {"opcode", "scopes", "pass", "passes", "flops", "kernel"}

What is read here, with `scope_time`'s own rules (every instant of busy
time to the op that started last among those running; only the
executions of the step module are looked up in the table):

  * kind of an instruction, first match: `kernel` where `kernel` is set
    (a Mosaic call); `wgrad_update` where `flops` > 0 and `update` is
    among `passes` (a weight gradient's product with the optimizer's
    update fused behind it); `matmul_fusion` where `flops` > 0; `vector`
    for the rest: fusions without a product, copies, a `while`'s own
    remainder, collectives, and, whatever its name, an op outside the
    step module's executions.  The four add up to the busy time
    `device_step_ms` is made of;
  * executed FLOPs: an instruction's `flops` (one execution, from the
    shapes in the compiled text) x its events in the window, so the body
    of a `while` counts once a trip;
  * pass: the record's `pass`; `recomputed` is the work `mfu_pct` does
    not count.

`read(run)` gives None where there is no trace or where the program's
table has no `instructions` (the parent of PR 51): the readers then
leave their metrics out.
"""
from __future__ import annotations

import json
from collections import Counter, defaultdict
from dataclasses import dataclass

from harness import scope_time, trace_reduce

KINDS = ("matmul_fusion", "wgrad_update", "kernel", "vector")
_OUTSIDE = {"opcode": "", "scopes": [], "pass": "other",
            "passes": ["other"], "flops": 0, "kernel": None}


def kind(record: dict) -> str:
    if record["kernel"]:
        return "kernel"
    if record["flops"] > 0:
        return "wgrad_update" if "update" in record["passes"] \
            else "matmul_fusion"
    return "vector"


@dataclass
class InstructionTime:
    steps: int
    busy_ns: float
    kind_ns: dict         # one of KINDS -> ns in the window, chip 0
    kind_flops: dict      # one of KINDS -> FLOPs executed in the window
    pass_ns: dict         # pass -> ns
    pass_flops: dict      # pass -> FLOPs executed
    kernel_calls: dict    # kernel name -> executions in the window
    kernel_instances: dict    # kernel name -> instructions in the table
    dearest: list         # (ns, instruction, executions, record), ten
    missing_ns: float     # step ops the table does not hold at all

    def ms_per_step(self, ns: float) -> float:
        return ns / self.steps / 1e6

    def roofline_pct(self, which: str, peak_flops: float) -> float:
        """The kind's executed FLOPs at the peak over its time; 0 where
        the step holds no instruction of the kind (under dp=4 the ZeRO-1
        update runs after the gradients' reduce-scatter, so no weight
        gradient carries it), because a metric without a `workloads`
        list has to be in every cell's line."""
        if not self.kind_ns[which]:
            return 0.0
        return 100.0 * self.kind_flops[which] / peak_flops \
            / (self.kind_ns[which] / 1e9)

    def report(self) -> dict:
        """What a person reads beside the metrics (an `[info]` line)."""
        ms, steps = self.ms_per_step, self.steps
        return {
            "kind_ms": {k: ms(self.kind_ns[k]) for k in KINDS},
            "kind_tflop": {k: self.kind_flops[k] / steps / 1e12
                           for k in KINDS},
            "pass_ms": {k: ms(v) for k, v in sorted(self.pass_ns.items())},
            "pass_tflop": {k: v / steps / 1e12
                           for k, v in sorted(self.pass_flops.items())},
            "kernel_calls_a_step": {k: v / steps for k, v in sorted(
                self.kernel_calls.items())},
            "kernel_instances": dict(sorted(self.kernel_instances.items())),
            "dearest": [{"instruction": name, "ms": ms(ns),
                         "executions_a_step": n / steps,
                         "kind": kind(record), "pass": record["pass"],
                         "tflop": record["flops"] * n / steps / 1e12,
                         "scopes": record["scopes"]}
                        for ns, name, n, record in self.dearest],
            "not_in_table_ms": ms(self.missing_ns)}


def attribute(trace, program, step_runs=None) -> InstructionTime:
    """Chip 0's busy time in `trace`'s window by kind and by pass,
    through `program["instructions"]`.  `step_runs` as in
    `scope_time.attribute`."""
    chip = trace.chips[0]
    table = program["instructions"]
    runs = sorted(step_runs) if step_runs is not None else None
    by_name, executions, missing_ns = defaultdict(float), Counter(), 0.0
    outside_ns = 0.0
    for ns, (start, _end, name) in scope_time.self_times(chip.ops):
        if runs is not None and not any(s <= start < e for s, e in runs):
            outside_ns += ns
            continue
        name = scope_time.instruction(name)
        executions[name] += 1
        by_name[name] += ns
        if name not in table:
            missing_ns += ns
    kind_ns, kind_flops = dict.fromkeys(KINDS, 0.0), dict.fromkeys(KINDS, 0)
    pass_ns, pass_flops = defaultdict(float), defaultdict(int)
    calls = Counter()
    kind_ns["vector"] += outside_ns
    pass_ns["other"] += outside_ns
    for name, ns in by_name.items():
        record = table.get(name, _OUTSIDE)
        flops = record["flops"] * executions[name]
        kind_ns[kind(record)] += ns
        kind_flops[kind(record)] += flops
        pass_ns[record["pass"]] += ns
        pass_flops[record["pass"]] += flops
        if record["kernel"]:
            calls[record["kernel"]] += executions[name]
    dearest = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return InstructionTime(
        steps=trace.steps, busy_ns=chip.busy_ns, kind_ns=kind_ns,
        kind_flops=kind_flops, pass_ns=dict(pass_ns),
        pass_flops=dict(pass_flops), kernel_calls=dict(calls),
        kernel_instances=dict(Counter(
            r["kernel"] for r in table.values() if r["kernel"])),
        dearest=[(ns, name, executions[name], table.get(name, _OUTSIDE))
                 for name, ns in dearest],
        missing_ns=missing_ns)


def compute(trace, programs, path=None):
    """The InstructionTime of `trace` under the newest of `programs`
    whose module is the step's (as `scope_time.compute` chooses it), or
    None where that program is not scoped or its table has no
    `instructions`."""
    runs = None
    if path is not None:
        modules, _spans = scope_time.from_file(path)
        if modules:
            name, runs = scope_time.step_module(modules)
            programs = [p for p in programs if p["module"] == name]
    if not programs or not programs[-1]["scoped"] \
            or "instructions" not in programs[-1]:
        return None
    return attribute(trace, programs[-1], step_runs=runs)


def _programs():
    """step_programs(), or [] from a program that has no table."""
    try:
        from mxnet_tpu.parallel.spmd import step_programs
    except ImportError:
        return []
    return step_programs()


def read(run):
    """The InstructionTime of a run's trace, computed once per trace, or
    None (see the module docstring)."""
    trace = run["trace"]
    if trace is None:
        return None
    memo = vars(trace)
    if "_instruction_time" not in memo:
        programs = _programs()
        it = None
        if any("instructions" in p for p in programs):
            try:
                path = trace_reduce.newest_xplane(scope_time.TRACE_DIR)
            except FileNotFoundError:
                path = None
            it = compute(trace, programs, path)
        memo["_instruction_time"] = it
        if it is not None:
            print("[info] " + json.dumps({"instruction_time": it.report()}),
                  flush=True)
    return memo["_instruction_time"]


def kind_ms(run, which: str):
    it = read(run)
    return None if it is None else it.ms_per_step(it.kind_ns[which])


def kind_roofline_pct(run, which: str):
    it = read(run)
    return None if it is None else it.roofline_pct(
        which, run["peak"].flops_bf16)
