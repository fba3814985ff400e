"""Device self time per query, in ms, of every operation other than the IVF
scan kernel: routing, tile compaction, id mapping."""
from bench import kernel_names as kn


def read(run):
    red = run.trace
    t = red.total_op_s - red.time(kernel=True, module=kn.SCAN_PROGRAM)
    return t * 1e3 / run.win["units"] if red.total_op_s > 0 else None
