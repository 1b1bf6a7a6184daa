"""Layer: compile cache.  Seconds of `mx.build.trace` of the step program
(`program` = `mx_train_step`): `jitted.trace(*args)`, the Python of the
whole model and its Pallas kernels, to a jaxpr."""
from harness import startup_time


def read(run):
    return startup_time.phase_s(run, "mx.build.trace",
                                program="mx_train_step")
