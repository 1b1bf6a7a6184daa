"""Layer: models.  The four exits' analytic FLOP floor over their device
time: the products with the head's array, hidden x vocabulary a token
and exit, forward and backward, no recomputation
(`exit_flops_per_sample` in the configuration's model.py: 4 x 3 x 2 x
8,192 x 2048 x 49,152 = 19.79 TFLOP a sequence) at the chip's bfloat16
peak, over `exit_device_ms`: numerator and denominator cover the same
work.  The time holds the forward done again under remat, the
log-softmax over 49,152 rows a token (HBM-bound, no FLOP counted) and
the objective; the floor does not, so exits at the MXU's peak read 75%
at the most."""
from harness import lookup

CELL = "ouro_2_6b_s8192"

_exit_ms = lookup.metric_reader("layer_metrics", "exit_device_ms")


def read(run):
    ms = _exit_ms(run)
    if not ms:
        return None
    cell = lookup.cell(CELL)
    flops = (cell.model.exit_flops_per_sample(cell.config, cell.traffic)
             * run["samples_per_step"])
    floor_s = flops / (run["chips"] * run["peak"].flops_bf16)
    return 100.0 * floor_s / (ms / 1e3)
