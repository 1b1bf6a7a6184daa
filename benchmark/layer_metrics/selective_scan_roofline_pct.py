"""Layer: kernels.  The selective scan's byte floor over its device
time: every operand read once and every result written once, forward
and backward, no recomputation, in the configuration's 2-byte dtype
(`selective_scan_bytes_per_sample` in the configuration's model.py) at
the chip's HBM peak, over `selective_scan_device_ms`.  A byte floor,
since the op has no MXU work; numerator and denominator cover the same
passes whatever route runs.  The op is in fact bound by VPU issue along
a sequential dependence (16 multiply-adds and an exponential a channel,
state and step), the time holds the forward done again under remat and
the kernels' float32 relayouts, and the floor holds neither: a kernel at
the peak it can reach reads well under 100% (PERF.md section 3)."""
from harness import lookup, scope_time

CELL = "phi4_mini_flash_s16384"


def read(run):
    ms = scope_time.op_ms(run, "selective_scan")
    if not ms:
        return None
    cell = lookup.cell(CELL)
    nbytes = (cell.model.selective_scan_bytes_per_sample(
        cell.config, cell.traffic) * run["samples_per_step"])
    floor_s = nbytes / (run["chips"] * run["peak"].hbm_bytes_s)
    return 100.0 * floor_s / (ms / 1e3)
