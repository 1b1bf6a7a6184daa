"""Layer: kernels.  Device time per step on chip 0, forward and backward
(the forward again under remat), of the ops whose op scope is
`rotary_embedding`: here the rotation of q (32 heads) and k (8) of the
three attention layers at head size 64, which `ops/rotary.py` holds in
128 lanes; the kernel's first use at that size.  `rotary_device_ms`'s
reader under a second name, because that metric lists its cells by name
and this PR may not append to the list."""
from harness import lookup

read = lookup.metric_reader("layer_metrics", "rotary_device_ms")
