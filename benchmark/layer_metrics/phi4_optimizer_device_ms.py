"""Layer: optimizer.  Device time per step on chip 0 of the ops under the
bare `mx.update` scope in this configuration's step: the updates XLA
does not fuse into a weight-gradient matmul (the float32 A_log, D,
dt_bias, lambda vectors and sub-norm gains among them).
`optimizer_device_ms`'s reader under a second name, because that metric lists its
cells by name and this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "optimizer_device_ms")
