"""Worker for ``test_mesh_cell.py``: the four-chip cell ``mnist8m_mesh_job``
at a test size on four fake CPU devices, in a SUBPROCESS (jax locks the
device count at its first use). Prints one JSON dict."""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import faults, harness  # noqa: E402
from bench.reference import checks, sharded  # noqa: E402
from bench.reference.data import generate  # noqa: E402
from bench.reference.generators import digits_like  # noqa: E402

# the configuration's widths (d=784) and data; n, k and the Lloyd cap cut so
# that Lloyd converges and interpret-mode kernels finish
SMALL = dict(n=4 * 2048, k=32, max_iters=200)
SEED = 2 ** 40 + 3
FAULTS = ("unchanged", "half", "altered", "iters=5")


def small_cell():
    cell = harness.load_cell("mnist8m_mesh_job")
    cell.cfg.update(SMALL)
    cell.cfg["data"] = dict(cell.cfg["data"], block_rows=512)
    return cell


def run(control=None):
    res = harness.run_cell(small_cell(), SEED, 0.5, False,
                           t_begin=time.perf_counter(), control=control)
    return {"correct": res["correct"], "checks": res["checks"]}


out = {"devices": jax.device_count()}

# the generator: the same rows for the same key, whatever the block size
cfg = small_cell().cfg
p = dict(cfg["data"])
del p["generator"]
rows = [np.asarray(digits_like.make(jax.random.PRNGKey(7), n=cfg["n"],
                                    d=cfg["d"], **dict(p, block_rows=b)))
        for b in (512, 2048, 300)]
out["generator_block_free"] = all(np.array_equal(rows[0], r)
                                  for r in rows[1:])
out["generator_zero_share"] = float((rows[0] == 0).mean())

# the sharded numbers against the gathered ones, on a sound answer and on
# one a few steps from a fixed point
data = generate(cfg, 11)
points = data["points"]
out["points_sharded"] = len(points.sharding.device_set)
host = np.asarray(points)
for name, iters in (("converged", 200), ("early", 2)):
    c, a, _ = sharded.kmeans(jax.random.PRNGKey(3), points, k=cfg["k"],
                             max_iters=iters)
    got = sharded.partition_numbers(points, c, a)
    want = checks.partition_numbers(jax.device_put(host), host, c, a)
    out[f"parity_{name}"] = {"sharded": got, "gathered": want}

out["program"] = run()
out["control_bf16"] = run(control="bf16")
for fault in FAULTS:
    with faults.planted(fault):
        out[f"fault_{fault}"] = run()

print(json.dumps(out))
