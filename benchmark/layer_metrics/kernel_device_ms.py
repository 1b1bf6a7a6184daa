"""Layer: kernels.  Device time per step on chip 0 of the Mosaic calls
(instructions whose `kernel` is set), the repo's and upstream's."""
from harness import instruction_time


def read(run):
    return instruction_time.kind_ms(run, "kernel")
