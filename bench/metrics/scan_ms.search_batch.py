"""Device self time of the IVF scan kernel per query, in ms."""
from bench import kernel_names as kn


def read(run):
    t = run.trace.time(kernel=True, module=kn.SCAN_PROGRAM)
    return t * 1e3 / run.win["units"] if t > 0 else None
