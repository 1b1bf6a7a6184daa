"""Layer: device.  Peak device memory on the fullest chip: the
allocator's peak_bytes_in_use plus the step program's temporaries, which
the allocator's peak leaves out (PR 21)."""


def read(run):
    return run["memory_peak_bytes"] / 2**30
