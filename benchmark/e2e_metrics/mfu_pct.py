"""Model FLOP/s utilisation: throughput (as e2e_metrics/throughput.py)
x the configuration's analytic FLOPs per sample over chips x the chip's
published bf16 peak."""


def read(run):
    w = run["window"]
    samples_s = run["samples_per_step"] * w.completed / w.seconds
    return (100.0 * samples_s * run["flops_per_sample"]
            / (run["chips"] * run["peak"].flops_bf16))
