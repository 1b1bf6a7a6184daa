"""Layer: kernels.  Executions a step on chip 0 of the instructions whose
`kernel` is set; the `instruction_time` info line has them by kernel
name, beside the distinct instances the program's table holds."""
from harness import instruction_time


def read(run):
    it = instruction_time.read(run)
    return None if it is None else \
        sum(it.kernel_calls.values()) / it.steps
