"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of the ops whose op scope is `eva_chunk_summary`: every 16 keys and
values of a head pooled into one by the learned per-head softmax (plain
XLA: it reads k and v once and writes a sixteenth of them)."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "eva_chunk_summary")
