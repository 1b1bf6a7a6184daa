"""Layer: one-program step, host side.  Median `mx.step.dispatch` span (the
executable call alone) over the counted steps of this configuration's
step, 129 parameters and their states.
`host_dispatch_ms`'s reader under a second name, because that metric lists its
cells by name and this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "host_dispatch_ms")
