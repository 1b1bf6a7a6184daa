"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of the expert layers' own two stages: the op scopes `moe_route` (scores,
top-k, weights, the rows' layout) and `moe_experts` (gather, the two
grouped products, combine); not the latent projections or the shared
expert, which are `FullyConnected`."""
from harness import scope_time


def read(run):
    route = scope_time.op_ms(run, "moe_route")
    experts = scope_time.op_ms(run, "moe_experts")
    return None if route is None or experts is None else route + experts
