"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of the ops whose op scope is `rotary_embedding`: the rotation of q and k
in all nine layers (float32 inside), not the tables, which the model
makes once a pass outside any op scope."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "rotary_embedding")
