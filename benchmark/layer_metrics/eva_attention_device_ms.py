"""Layer: kernels.  Device time per step on chip 0, forward and backward
(the forward done again under remat booked as `scope_time` books it), of
the ops whose op scope is `eva_attention`: the EVA cores (the splash
kernels over [keys ; summaries] under the local | remote mask, the
head-split copies and the concatenations around them), not the
projections, the rotation or the chunk summaries."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "eva_attention")
