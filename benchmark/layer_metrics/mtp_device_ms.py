"""Layer: models.  Device time per step on chip 0, forward and backward
(with the forward done again under remat), of every instruction of the
step program whose name stack passes through the multi-token-prediction
module's block (`mtp`: `gluon.model_zoo.joyai.MTP_NAME`): its join, its
own sparse layer (latent attention, router, experts), its second pass
through the shared embedding and head, and its loss term, which the
step block traces under the same name.  Not the update of its weights
where XLA fuses that into a weight-gradient matmul of the module's
(rule B books the fusion to the matmul, so it IS counted) or runs it
apart (`optimizer_device_ms`).

A reader of its own over the program's table (`parallel.spmd.
step_programs()`), since `scope_time` reads innermost registered op
names and a block is none: every instant of chip 0's busy time in the
window goes to one op as there (`scope_time.self_times`), and the ops
whose `op_name` holds the module's name as a path component are summed.
None where there is no trace, no table, or no such component (a program
without the module)."""
import re

from harness import scope_time, trace_reduce

BLOCK = "mtp"
_IN_BLOCK = re.compile(rf"(?:^|[/(]){BLOCK}\)*(?:/|$)")


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    programs, _registered = scope_time._from_the_program()
    try:
        modules, _spans = scope_time.from_file(
            trace_reduce.newest_xplane(scope_time.TRACE_DIR))
    except FileNotFoundError:
        return None
    if not programs or not modules:
        return None
    name, runs = scope_time.step_module(modules)
    programs = [p for p in programs if p["module"] == name]
    if not programs or not programs[-1]["scoped"]:
        return None
    table = programs[-1]["ops"]
    ns = sum(
        ns for ns, (start, _end, event) in scope_time.self_times(
            trace.chips[0].ops)
        if any(s <= start < e for s, e in runs)
        and _IN_BLOCK.search(table.get(scope_time.instruction(event)) or ""))
    return ns / trace.steps / 1e6 if ns else None
