"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of the ops whose op scope (ops/registry.py:apply_pure) is
`dot_product_attention`: the S x S core (scores, mask, softmax, dropout,
PV), not the q/k/v/proj `FullyConnected`."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "dot_product_attention")
