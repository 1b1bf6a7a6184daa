"""Layer: collectives.  The part of collective_ms per step during which
no other op ran on chip 0."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return trace.chips[0].collective_exposed_ns / trace.steps / 1e6
