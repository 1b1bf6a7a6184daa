"""Layer: compile cache.  Seconds of `mx.build.backend` of the step
program (`program` = `mx_train_step`): XLA's compile, or JAX's load from
its persistent cache; which of the two is the record's `origin` on the
`setup_by_phase` info line."""
from harness import startup_time


def read(run):
    return startup_time.phase_s(run, "mx.build.backend",
                                program="mx_train_step")
