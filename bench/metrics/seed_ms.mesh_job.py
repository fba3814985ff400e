"""Device self time of the seeding kernels per job and per chip, in ms:
summed over the chips' device planes, divided by the number of chips."""
import jax

from bench import kernel_names as kn


def read(run):
    t = run.trace.time(kernel=True, prefixes=kn.SEEDING)
    return t * 1e3 / run.win["units"] / jax.device_count() if t > 0 \
        else None
