"""Layer: device.  Share of chip 0's busy time in the window that the
step program's table placed under no forward, backward or update scope:
copies, the ops of the tiny programs between two steps, anything traced
outside a scope.  The instrument's own health: the other scope metrics
describe the rest."""
from harness import scope_time


def read(run):
    st = scope_time.read(run)
    if st is None or not st.busy_ns:
        return None
    return 100.0 * st.phase_ns["other"] / st.busy_ns
