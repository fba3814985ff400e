"""Device self time per job and per chip, in ms, of every operation that is
neither a seeding or Lloyd kernel nor a collective: sampler, centroid
update, the pad of the shard, loop control. Summed over the chips' device
planes, divided by the number of chips."""
import jax

from bench import kernel_names as kn
from bench.collective_names import COLLECTIVES


def read(run):
    red = run.trace
    if red.total_op_s <= 0:
        return None
    t = (red.total_op_s - red.time(kernel=True, prefixes=kn.SEEDING)
         - red.time(kernel=True, prefixes=kn.LLOYD)
         - red.time(prefixes=COLLECTIVES))
    return t * 1e3 / run.win["units"] / jax.device_count()
