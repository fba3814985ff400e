"""Device self time of the seeding kernels per job, in ms."""
from bench import kernel_names as kn


def read(run):
    t = run.trace.time(kernel=True, prefixes=kn.SEEDING)
    return t * 1e3 / run.win["units"] if t > 0 else None
