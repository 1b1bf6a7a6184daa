"""Layer: kernels.  Device time per step on chip 0 of the step's
instructions of the kind `matmul_fusion`: those whose `flops` (the
program's `instructions` table) are above 0 and whose `passes` hold no
`update`: forward, recomputed and data-gradient products with whatever
XLA fused around them."""
from harness import instruction_time


def read(run):
    return instruction_time.kind_ms(run, "matmul_fusion")
