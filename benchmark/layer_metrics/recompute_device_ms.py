"""Layer: models.  Device time per step on chip 0 of every instruction
whose `pass` is `recomputed` (booked under `rematted_computation`),
whatever its kind: the forward done again under remat, which `mfu_pct`
does not count.  0 where the step recomputes nothing: a metric without a
`workloads` list has to be in every cell's line."""
from harness import instruction_time


def read(run):
    it = instruction_time.read(run)
    return None if it is None else \
        it.ms_per_step(it.pass_ns.get("recomputed", 0.0))
