"""Layer: kernels.  Device time per step on chip 0 of everything that is
no product and no Mosaic call (`flops` 0, `kernel` None): fusions of
vector work, copies, a `while`'s own remainder, collectives, and the
tiny programs between two steps.  With the other three `_device_ms` of
the kinds it adds up to `device_step_ms`."""
from harness import instruction_time


def read(run):
    return instruction_time.kind_ms(run, "vector")
