"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of the ops whose op scope is `dot_product_attention`: in this
configuration the causal grouped-query core at S = 8192 (the flash
kernels and the key/value heads' repeat), not the q/k/v/o projections.
The same reading as `attention_device_ms`, which is listed for BERT's
cells only."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "dot_product_attention")
