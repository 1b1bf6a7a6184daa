"""Layer: device.  Share of the traced window in which no op ran on
chip 0."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace.chips[0].busy_ns / trace.window_ns)
