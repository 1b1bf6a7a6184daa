"""Layer: optimizer.  Device time per step on chip 0 of the kind
`wgrad_update`: instructions with `flops` above 0 and `update` among
their `passes`, i.e. a weight gradient's product with the optimizer's
update in its epilogue.  Rule B books these to the matmul's scope, so
`optimizer_device_ms` does not see them."""
from harness import instruction_time


def read(run):
    return instruction_time.kind_ms(run, "wgrad_update")
