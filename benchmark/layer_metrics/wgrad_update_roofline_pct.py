"""Layer: optimizer.  The executed FLOP floor of the kind `wgrad_update`
(`flops` x executions a step at the chip's bfloat16 peak) over
`wgrad_update_device_ms`: how far the update in the epilogue holds the
weight gradients' products off their roof.  0 where no weight gradient
carries the update (`resnet50_dp4_bs1024`: the ZeRO-1 update runs after
the gradients' reduce-scatter, as fusions of its own)."""
from harness import instruction_time


def read(run):
    return instruction_time.kind_roofline_pct(run, "wgrad_update")
