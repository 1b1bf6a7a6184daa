"""Layer: one-program step, host side.  Median time the host spends
inside one trainer.step call (the benchmark's bench.step_call span)."""
import statistics


def read(run):
    trace = run["trace"]
    if trace is None or not trace.spans.get("bench.step_call"):
        return None
    calls = trace.spans["bench.step_call"][-trace.steps:]
    return statistics.median(e - s for s, e in calls) / 1e6
