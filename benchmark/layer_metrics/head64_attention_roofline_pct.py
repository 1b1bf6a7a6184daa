"""Layer: kernels.  The 64-wide causal cores' analytic FLOP floor over
their device time: the two products over exactly the causal pairs at
the published 64 (scores) + 64 (values) a head, 32 heads, forward and
backward, no recomputation (`attention_flops_per_sample` in the
configuration's model.py) at the chip's bfloat16 peak, over
`head64_attention_device_ms`.  FLOP-bound by construction.  A product
that contracts over 64 fills half the MXU's depth, the kernels visit
whole blocks of the triangle, and the split backward forms the scores
in both its kernels: a kernel at peak reads well under 100% (PERF.md
section 3)."""
from harness import lookup, scope_time

CELL = "lfm2_8b_a1b_s8192"


def read(run):
    ms = scope_time.op_ms(run, "dot_product_attention")
    if not ms:
        return None
    cell = lookup.cell(CELL)
    flops = (cell.model.attention_flops_per_sample(
        cell.config, cell.traffic) * run["samples_per_step"])
    floor_s = flops / (run["chips"] * run["peak"].flops_bf16)
    return 100.0 * floor_s / (ms / 1e3)
