"""Layer: kernels.  The EVA cores' analytic FLOP floor over their device
time: the two products over exactly the (query, key) and (query,
summary) pairs a head sums over, forward and backward, no recomputation
(`eva_attention_flops_per_sample` in the configuration's model.py) at
the chip's bfloat16 peak, over `eva_attention_device_ms`.  FLOP-bound by
construction.  The kernels visit whole blocks (92 of 1024 x 1024 for
the 62 the pairs fill at S 32768), the scope's time holds the forward
done again under remat, and the split backward forms the scores in both
its kernels: a kernel at peak would read ~37% (PERF.md section 3)."""
from harness import lookup, scope_time

CELL = "evabyte_s32768"


def read(run):
    ms = scope_time.op_ms(run, "eva_attention")
    if not ms:
        return None
    cell = lookup.cell(CELL)
    flops = (cell.model.eva_attention_flops_per_sample(
        cell.config, cell.traffic) * run["samples_per_step"])
    floor_s = flops / (run["chips"] * run["peak"].flops_bf16)
    return 100.0 * floor_s / (ms / 1e3)
