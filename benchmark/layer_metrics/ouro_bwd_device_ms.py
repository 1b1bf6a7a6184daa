"""Layer: models.  Device time per step on chip 0 of the backward ops
(scopes under `transpose(`) in this configuration's step, the forward
done again under remat among them: 4 N layer segments and 4 exit
segments.  `bwd_device_ms`'s reader under a second name, because that
metric lists its cells by name and this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "bwd_device_ms")
