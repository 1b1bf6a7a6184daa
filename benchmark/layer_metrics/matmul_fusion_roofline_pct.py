"""Layer: kernels.  The executed FLOP floor of the kind `matmul_fusion`
(each instruction's `flops` x its executions a step, at the chip's
bfloat16 peak) over `matmul_fusion_device_ms`.  The floor is what the
compiled program's products hold, recomputation included, so it reads
how far the fusions are off the MXU's roof and nothing else."""
from harness import instruction_time


def read(run):
    return instruction_time.kind_roofline_pct(run, "matmul_fusion")
