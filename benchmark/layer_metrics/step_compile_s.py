"""Layer: compile cache.  Seconds the program spent building or loading
its step executable during set-up
(parallel.spmd.step_compile_stats()["seconds_total"])."""


def read(run):
    return run["step_compile_s"]
