"""Layer: kernels.  Device time per step on chip 0, forward and backward
(the forward done again under remat booked as `scope_time` books it), of
the ops whose op scope is `sliding_window_attention`: the windowed
layers' cores (the splash kernels over the band and the layout changes
around them), not their projections, rotary or gate."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "sliding_window_attention")
