"""Layer: compile cache.  Seconds of `mx.build.lower` of the step program
(`program` = `mx_train_step`): the jaxpr to StableHLO."""
from harness import startup_time


def read(run):
    return startup_time.phase_s(run, "mx.build.lower",
                                program="mx_train_step")
