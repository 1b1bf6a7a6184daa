"""Layer: models.  Seconds the program spent initialising parameters on
the host, `mx.setup.init` (`Parameter._finish_init`: the initializer's
draws and the write into the parameter, a model's parameters in one
record), and casting them, `mx.setup.cast` (`Block.cast`)."""
from harness import startup_time


def read(run):
    return startup_time.phase_s(run, "mx.setup.init", "mx.setup.cast")
