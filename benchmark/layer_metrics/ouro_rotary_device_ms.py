"""Layer: kernels.  Device time per step on chip 0, forward and backward
(the forward again under remat), of the ops whose op scope is
`rotary_embedding`: here the rotation of q and k (16 heads of 128 each)
in the 4 N layer applications.  `rotary_device_ms`'s reader under a
second name, because that metric lists its cells by name and this PR
may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "rotary_device_ms")
