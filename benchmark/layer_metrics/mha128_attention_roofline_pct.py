"""Layer: kernels.  The causal cores' analytic FLOP floor over their
device time: the two products over exactly the causal pairs (33,558,528
a head) at 128 (scores) + 128 (values), 16 heads, 4 N applications,
forward and backward, no recomputation (`attention_flops_per_sample` in
the configuration's model.py) at the chip's bfloat16 peak, over
`mha128_attention_device_ms`.  FLOP-bound by construction.  The kernels
visit whole blocks of the triangle, the split backward forms the scores
in both its kernels and the forward runs again under remat: a kernel at
peak reads well under 100% (PERF.md section 3)."""
from harness import lookup, scope_time

CELL = "ouro_2_6b_s8192"


def read(run):
    ms = scope_time.op_ms(run, "dot_product_attention")
    if not ms:
        return None
    cell = lookup.cell(CELL)
    flops = (cell.model.attention_flops_per_sample(
        cell.config, cell.traffic) * run["samples_per_step"])
    floor_s = flops / (run["chips"] * run["peak"].flops_bf16)
    return 100.0 * floor_s / (ms / 1e3)
