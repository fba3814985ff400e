"""Seconds per clustering job: the whole window over the jobs it holds."""


def read(run):
    w = run.win
    return (w["t_end"] - w["t_start"]) / w["units"]
