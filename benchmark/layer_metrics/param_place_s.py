"""Layer: one-program step, host side.  Seconds of `mx.setup.place`:
`SPMDTrainer.__init__` putting parameters and optimizer states on the mesh.
Nothing there waits for a transfer: one still in flight lands in the phase
that first needs its array."""
from harness import startup_time


def read(run):
    return startup_time.phase_s(run, "mx.setup.place")
