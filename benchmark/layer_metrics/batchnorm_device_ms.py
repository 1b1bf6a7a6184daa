"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of the ops whose op scope (ops/registry.py:apply_pure) is `BatchNorm`.
A statistics reduction fused into a convolution is booked to the
convolution (the fusion rule of parallel.spmd.program_table)."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "BatchNorm")
