"""Share of the traced window, in %, in which no operation ran on the
device (``trace_reduce.Reduced.idle_pct``)."""


def read(run):
    return run.trace.idle_pct
