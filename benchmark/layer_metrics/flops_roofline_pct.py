"""Layer: kernels.  The step's analytic FLOP floor (FLOPs per step over
chips x peak) over the device-busy time per step on chip 0 (as
layer_metrics/device_step_ms.py): the whole step program taken as one
kernel until named scopes exist inside the program."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    busy_s = trace.chips[0].busy_ns / trace.steps / 1e9
    floor_s = (run["flops_per_sample"] * run["samples_per_step"]
               / (run["chips"] * run["peak"].flops_bf16))
    return 100.0 * floor_s / busy_s
