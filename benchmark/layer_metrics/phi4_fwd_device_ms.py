"""Layer: models.  Device time per step on chip 0 of the forward ops
(scopes under `jvp(` and not `transpose(`) in this configuration's step.
`fwd_device_ms`'s reader under a second name, because that metric lists its
cells by name and this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "fwd_device_ms")
