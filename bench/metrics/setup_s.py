"""Set-up: from the start of the process to the start of the window (JAX
start-up, data, index build, compiles or cache loads, warm-up)."""


def read(run):
    return run.setup_s
