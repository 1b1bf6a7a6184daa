"""Layer: models.  Device-busy time per step on chip 0: the union of the
intervals in which an XLA op ran, over the steps of the traced window."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return trace.chips[0].busy_ns / trace.steps / 1e6
