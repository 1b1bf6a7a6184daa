"""Layer: imports.  Seconds of `import mxnet_tpu`, first to last line of
its `__init__` (the program's `mx.setup.import` record), jax's own import
inside it (`mx.setup.import.jax`, on the info line) included."""
from harness import startup_time


def read(run):
    return startup_time.phase_s(run, "mx.setup.import")
