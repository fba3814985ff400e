"""Device self time of the operations that cross chips
(``bench.collective_names``) per job and per chip, in ms: summed over the
chips' device planes, divided by the number of chips."""
import jax

from bench.collective_names import COLLECTIVES


def read(run):
    red = run.trace
    if red.total_op_s <= 0:
        return None
    t = red.time(prefixes=COLLECTIVES)
    return t * 1e3 / run.win["units"] / jax.device_count()
