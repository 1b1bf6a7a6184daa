"""Layer: kernels.  Device time per step on chip 0, forward and backward
(the forward again under remat), of the ops whose op scope is
`dot_product_attention`: in this configuration the causal cores of the
4 N layer applications at S = 8192, 16 query heads over 16 key/value
heads of 128 (the splash kernels at a group of one, and the head-split
copies), not the rotation or the projections.
`causal_attention_device_ms`'s reader under a second name, because that
metric lists its cells by name and this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "causal_attention_device_ms")
