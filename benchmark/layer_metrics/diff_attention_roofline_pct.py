"""Layer: kernels.  The causal differential cores' analytic FLOP floor
over their device time: the two products over exactly the causal pairs
at the published 64 (scores) + 128 (values: a pair's two value heads
side by side) a head, 40 heads, in the full and the cross layer, forward
and backward, no recomputation (`diff_attention_flops_per_sample` in the
configuration's model.py) at the chip's bfloat16 peak, over
`diff_attention_device_ms`.  FLOP-bound by construction.  The score
product contracts over 64 and fills half the MXU's depth, the blocks of
1,024 rows visit more than the pairs fill (the triangle's diagonal
blocks are whole), the backward's five products a pair cost more than
twice the forward's two, and the scope holds the pairing copies: a
kernel at the peak it can reach reads well under 100% (PERF.md section
3)."""
from harness import lookup

CELL = "phi4_mini_flash_s16384"

_core_ms = lookup.metric_reader("layer_metrics", "diff_attention_device_ms")


def read(run):
    ms = _core_ms(run)
    if not ms:
        return None
    cell = lookup.cell(CELL)
    flops = (cell.model.diff_attention_flops_per_sample(
        cell.config, cell.traffic) * run["samples_per_step"])
    floor_s = flops / (run["chips"] * run["peak"].flops_bf16)
    return 100.0 * floor_s / (ms / 1e3)
