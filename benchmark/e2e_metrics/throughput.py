"""Samples (images or sequences; the configuration says which) completed
in the window over the window's wall time to the last block_until_ready,
all chips together."""


def read(run):
    w = run["window"]
    return run["samples_per_step"] * w.completed / w.seconds
