"""Share of the traced window, in %, in which no operation ran on a chip,
the mean over the chips (``trace_reduce.Reduced.idle_pct``)."""


def read(run):
    return run.trace.idle_pct
