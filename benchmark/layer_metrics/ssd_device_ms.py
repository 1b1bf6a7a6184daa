"""Layer: kernels.  Device time per step on chip 0, forward and backward
(and, under remat, the recomputed forward), of the ops whose op scope is
`ssd_scan`: the Mamba-2 scan's core (discretisation, the four products,
the recurrence over chunks), not the projections, the convolution or the
gated norm around it."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "ssd_scan")
