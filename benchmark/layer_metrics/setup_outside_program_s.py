"""Layer: outside the program.  `setup_s` less the program's top-level
set-up records: the road to the chip, the configuration's own draws, the
reference check, the warm-up steps' device time.  The instrument's own
health, as `scope_unattributed_pct` is for the device."""
from harness import startup_time


def read(run):
    st = startup_time.read(run)
    return None if st is None else run["setup_s"] - st.top_level_s
