"""Layer: optimizer.  Device time per step on chip 0 of the ops under the
`mx.update` scope of the step program.  An update fused into the epilogue
of a weight-gradient convolution or dot is booked to that matmul (the
fusion rule of parallel.spmd.program_table), so this is the update that
runs as kernels of its own."""
from harness import scope_time


def read(run):
    return scope_time.phase_ms(run, "update")
