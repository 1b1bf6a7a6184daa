"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of the ops whose op scope is `dot_product_attention`: in this
configuration the three attention layers' causal grouped-query cores at
head size 64 and S = 8192 (the splash kernels, 32 query heads over 8
key/value heads, and the head-split copies), not the q/k norms, the
rotation or the projections.  `causal_attention_device_ms`'s reader
under a second name, because that metric lists its cells by name and
this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "causal_attention_device_ms")
