"""Layer: kernels.  Device time per step on chip 0, forward and backward
(the layer's forward done again under remat booked as `scope_time` books
it, without the forward kernel, whose output and logsumexp the segment
keeps), of the ops whose op scope is `latent_attention`: the latent
(MLA) cores of the six layers and of the prediction module's (the splash
kernels at 192-wide queries and keys and 128-wide values, the head-split
copies and the keys' concatenation [k_nope ; shared rotary key]), not
the low-rank projections (`mla_projection_device_ms`) nor the rotation."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "latent_attention")
