"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of the sparse layers' own two stages: the op scopes `moe_route` (scores
over 256, top-8, weights, the rows' layout) and `moe_experts` (gather,
the two grouped products around silu-gate x up, combine); not the shared
expert, which is `FullyConnected`.  `moe_device_ms`'s reader under a
second name, because that metric lists its cells by name and this PR may
not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "moe_device_ms")
