"""Process start to the first step of the window: imports, init,
placement, the reference check, compile or cache load, warm-up."""


def read(run):
    return run["setup_s"]
