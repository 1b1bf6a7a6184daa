"""Layer: models.  The conv operators' floor over their device time:
the two projections' FLOPs, forward and backward, no recomputation
(`short_conv_block_flops_per_sample` in the configuration's model.py) at
the chip's bfloat16 peak, PLUS `short_conv`'s HBM bytes, every operand
read once and every result written once, forward and backward
(`short_conv_bytes_per_sample`) at the HBM peak, over
`short_conv_block_device_ms`: numerator and denominator cover the same
work, whichever fusion XLA puts the op's passes in.  The sum is the roof
of a block that does not overlap the op's streams with its products;
the time holds the forward done again under remat and the floor does
not, so as executed the products alone need 4/3 of their term and a
block at both peaks reads ~83%."""
from harness import lookup

CELL = "lfm2_8b_a1b_s8192"

_block_ms = lookup.metric_reader("layer_metrics",
                                 "short_conv_block_device_ms")


def read(run):
    ms = _block_ms(run)
    if not ms:
        return None
    cell = lookup.cell(CELL)
    model, chips = cell.model, run["chips"]
    per_step = run["samples_per_step"] / chips
    floor_s = per_step * (
        model.short_conv_block_flops_per_sample(cell.config, cell.traffic)
        / run["peak"].flops_bf16
        + model.short_conv_bytes_per_sample(cell.config, cell.traffic)
        / run["peak"].hbm_bytes_s)
    return 100.0 * floor_s / (ms / 1e3)
