"""Layer: one-program step, host side.  Median `mx.step.dispatch` span
(SPMDTrainer.step: the executable call alone) over the counted steps;
host_step_ms less this is the host work before and after the call."""
import statistics

from harness import scope_time


def read(run):
    st = scope_time.read(run)
    if st is None or not st.host_spans.get("mx.step.dispatch"):
        return None
    return statistics.median(st.host_spans["mx.step.dispatch"]) / 1e6
