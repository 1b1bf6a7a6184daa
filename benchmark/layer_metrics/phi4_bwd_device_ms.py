"""Layer: models.  Device time per step on chip 0 of the backward ops
(scopes under `transpose(jvp(`; under remat the layers' forward done
again, the scan's forward kernel among it, is booked here) in this
configuration's step.
`bwd_device_ms`'s reader under a second name, because that metric lists its
cells by name and this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "bwd_device_ms")
