"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of the sparse layers' own two stages: the op scopes `moe_route` (scores
over 32, top-4, weights, the rows' layout) and `moe_experts` (gather,
the two grouped products around silu-gate x up at 2,048 rows an expert
expected, combine); there is no shared expert.  `moe_device_ms`'s reader
under a second name, because that metric lists its cells by name and
this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "moe_device_ms")
