"""Layer: device.  Share of chip 0's busy time in the window that the step
program's table placed under no forward, backward or update scope, in
this configuration's step: the instrument's own health.
`scope_unattributed_pct`'s reader under a second name, because that metric lists its
cells by name and this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "scope_unattributed_pct")
