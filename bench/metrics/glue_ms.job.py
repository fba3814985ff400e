"""Device self time per job, in ms, of every operation that is not a Pallas
kernel: sampler, centroid update, pads and relayouts, loop control."""


def read(run):
    red = run.trace
    t = red.total_op_s - red.time(kernel=True)
    return t * 1e3 / run.win["units"] if red.total_op_s > 0 else None
