"""Layer: models.  Device time per step on chip 0 of the backward ops:
those whose scope in the step program (block and op scopes, read through
parallel.spmd.step_programs()) lies under `transpose(`."""
from harness import scope_time


def read(run):
    return scope_time.phase_ms(run, "backward")
