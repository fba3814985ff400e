"""Device self time of the Lloyd assignment kernels per job, in ms."""
from bench import kernel_names as kn


def read(run):
    t = run.trace.time(kernel=True, prefixes=kn.LLOYD)
    return t * 1e3 / run.win["units"] if t > 0 else None
