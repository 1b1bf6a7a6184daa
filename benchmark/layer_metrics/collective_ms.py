"""Layer: collectives.  Device time per step inside collective ops on
chip 0, an asynchronous one counted from its -start to its -done."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return trace.chips[0].collective_ns / trace.steps / 1e6
