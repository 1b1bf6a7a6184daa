"""Layer: kernels.  The scan's own analytic FLOP floor over its device
time: the FLOPs of the chunked scan's four products, forward and
backward, no recomputation (`ssd_flops_per_sample` in the
configuration's model.py) at the chip's bfloat16 peak, over
`ssd_device_ms`.  FLOP-bound by construction; the scan is in fact bound
by memory and vector work, which is what a low share says."""
from harness import lookup, scope_time

CELL = "nemotron3_super_s8192"


def read(run):
    ms = scope_time.op_ms(run, "ssd_scan")
    if not ms:
        return None
    cell = lookup.cell(CELL)
    flops = (cell.model.ssd_flops_per_sample(cell.config, cell.traffic)
             * run["samples_per_step"])
    floor_s = flops / (run["chips"] * run["peak"].flops_bf16)
    return 100.0 * floor_s / (ms / 1e3)
