"""Layer: device.  Seconds of `mx.step.first_dispatch`: the first call of
the step executable that `_get_step` has just built or loaded; on the TPU
it holds the program's load onto the chip."""
from harness import startup_time


def read(run):
    return startup_time.phase_s(run, "mx.step.first_dispatch")
