"""Layer: compile cache.  Seconds of the three `mx.build.*` phases of
`SPMDTrainer.forward`'s program (`program` = `forward`), which the
reference check runs in set-up."""
from harness import startup_time


def read(run):
    return startup_time.phase_s(run, "mx.build", program="forward")
