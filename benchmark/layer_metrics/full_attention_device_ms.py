"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of the ops whose op scope is `dot_product_attention`: in this
configuration the three full layers' causal grouped-query cores at
S = 8192 (the flash kernels and the key/value heads' repeat), apart from
the windowed cores, which have an op scope of their own.
`causal_attention_device_ms`'s reader under a second name, because that
metric lists its cells by name and this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "causal_attention_device_ms")
