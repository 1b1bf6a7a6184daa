"""Layer: kernels.  The windowed cores' analytic FLOP floor over their
device time: the two products over exactly the (query, key) pairs inside
the band, forward and backward, no recomputation
(`window_attention_flops_per_sample` in the configuration's model.py) at
the chip's bfloat16 peak, over `window_attention_device_ms`.  The kernels
visit whole blocks (2 key blocks of 512 for the 512 keys a query sees)
and the scope's time holds the forward done again under remat, so the
share stays under 100% by construction."""
from harness import lookup, scope_time

CELL = "laguna_xs2_s8192"


def read(run):
    ms = scope_time.op_ms(run, "sliding_window_attention")
    if not ms:
        return None
    cell = lookup.cell(CELL)
    flops = (cell.model.window_attention_flops_per_sample(
        cell.config, cell.traffic) * run["samples_per_step"])
    floor_s = flops / (run["chips"] * run["peak"].flops_bf16)
    return 100.0 * floor_s / (ms / 1e3)
