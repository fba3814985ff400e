"""Queries answered per second over the whole window."""


def read(run):
    w = run.win
    return w["units"] / (w["t_end"] - w["t_start"])
