"""Worker for tests/test_mesh_kmeans.py: the one-program mesh ``kmeans`` on
four fake CPU devices, in a SUBPROCESS (jax locks the device count at its
first use). The shards run the Pallas kernels in interpret mode at d=784,
with a shard size that is not a tile multiple. Prints one JSON dict."""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ClusterEngine
from repro.core import engine as eng_mod
from test_engine import (_is_row_pad, _iter_eqns,  # this file's directory
                         _loop_bodies)

EPS32 = 2.0 ** -23
TIE_EPS = 32.0
D = 784
N_LOCAL = 4096 + 77     # the tile is 4096 rows for seeding alone, 2048 at
#                         k=256: neither divides the shard
out = {}
mesh = jax.make_mesh((4,), ("data",))
rows = NamedSharding(mesh, P("data"))


def blobs(key, n, d, k, spread):
    """Rows of k Gaussian blobs, and each row's blob."""
    kc, kl, kn = jax.random.split(key, 3)
    centres = jax.random.uniform(kc, (k, d), jnp.float32)
    labels = jax.random.randint(kl, (n,), 0, k)
    return centres[labels] + spread * jax.random.normal(kn, (n, d)), labels


def d2_64(x, c):
    """(n, k) float64 squared distances, difference form in blocks."""
    out = np.empty((x.shape[0], c.shape[0]))
    for j in range(c.shape[0]):
        diff = x - c[j]
        out[:, j] = np.einsum("ij,ij->i", diff, diff)
    return out


eng = ClusterEngine("mesh", mesh=mesh, local="pallas")

# ---------------------------------------------------------------------------
# 1. one program == mesh seed, then mesh fit, bitwise (k=256: the seeding
#    alone would take 4096-row tiles, the shared prologue takes 2048)
# ---------------------------------------------------------------------------
x = jax.device_put(jax.random.uniform(jax.random.PRNGKey(0),
                                      (4 * N_LOCAL, D)), rows)
key = jax.random.PRNGKey(5)
one = eng.kmeans(key, x, 256, max_iters=3)
two = eng.fit(x, eng.seed(key, x, 256).centroids, max_iters=3)
out["bitwise_ok"] = bool(
    np.array_equal(np.asarray(one.centroids), np.asarray(two.centroids))
    and np.array_equal(np.asarray(one.assignment),
                       np.asarray(two.assignment))
    and float(one.inertia) == float(two.inertia)
    and int(one.n_iters) == int(two.n_iters))
out["served_by"] = eng.last_backend.local.name

# the same on the fused (XLA) local backend, whose shard_maps keep JAX's
# varying-mesh-axes type check on (it is off only for interpreted Pallas)
fused = ClusterEngine("mesh", mesh=mesh, local="fused")
one_f = fused.kmeans(key, x, 256, max_iters=3)
two_f = fused.fit(x, fused.seed(key, x, 256).centroids, max_iters=3)
out["fused_bitwise_ok"] = bool(
    np.array_equal(np.asarray(one_f.centroids), np.asarray(two_f.centroids))
    and np.array_equal(np.asarray(one_f.assignment),
                       np.asarray(two_f.assignment))
    and float(one_f.inertia) == float(two_f.inertia)
    and int(one_f.n_iters) == int(two_f.n_iters))
out["fused_check_vma"] = [
    e.params["check_vma"] for e in _iter_eqns(jax.make_jaxpr(
        lambda kk, pp: eng_mod.kmeans_points(kk, pp, 256, None,
                                             fused.backend))(key, x).jaxpr)
    if e.primitive.name == "shard_map"]

# ---------------------------------------------------------------------------
# 2. the converged answer is a Lloyd fixed point in float64
# ---------------------------------------------------------------------------
K = 12
xb, labels = blobs(jax.random.PRNGKey(1), 4 * N_LOCAL, D, K, 0.05)
res = eng.kmeans(jax.random.PRNGKey(2), jax.device_put(xb, rows), K,
                 max_iters=100, tol=0.0)
x64 = np.asarray(xb, np.float64)
c64 = np.asarray(res.centroids, np.float64)
a = np.asarray(res.assignment)
counts = np.bincount(a, minlength=K)
full = counts > 0
means = np.stack([x64[a == j].mean(0) if counts[j] else c64[j]
                  for j in range(K)])
rms = np.sqrt((x64 ** 2).mean())
out["centroid_gap_eps"] = float(np.abs(c64 - means)[full].max()
                                / (rms * EPS32))
# no row nearer another centroid than its own beyond a tie: the kernels'
# fp32 matmul-form scores at d=784 sit up to ~13 eps of (|x| + |c|)^2 off
# the float64 distance, so two within TIE_EPS of each other are a tie
d2 = d2_64(x64, c64)
own = d2[np.arange(len(a)), a]
tie = TIE_EPS * EPS32 * (np.sqrt((x64 ** 2).sum(1))
                         + np.sqrt((c64[a] ** 2).sum(1))) ** 2
out["rows_nearer_elsewhere"] = int((d2.min(1) < own - tie).sum())
out["converged_iters"] = int(res.n_iters)

# ---------------------------------------------------------------------------
# 3. a mesh fit from fixed seeds agrees with the one-device reference fit
#    (one seed in each blob, so that no row lies near a tie)
# ---------------------------------------------------------------------------
seeds = xb[np.argmax(np.asarray(labels)[None, :] == np.arange(K)[:, None],
                     axis=1)]
mfit = eng.fit(jax.device_put(xb, rows), seeds, max_iters=3)
rfit = ClusterEngine("reference").fit(xb, seeds, max_iters=3)
out["fit_assign_match"] = bool(np.array_equal(np.asarray(mfit.assignment),
                                              np.asarray(rfit.assignment)))
out["fit_centroid_gap_eps"] = float(
    np.abs(np.asarray(mfit.centroids, np.float64)
           - np.asarray(rfit.centroids, np.float64)).max() / (rms * EPS32))
out["fit_iters"] = [int(mfit.n_iters), int(rfit.n_iters)]

# ---------------------------------------------------------------------------
# 4. jaxpr pin: one pad of each shard per call, none inside a loop body
# ---------------------------------------------------------------------------
with jax.set_mesh(mesh):
    jaxpr = jax.make_jaxpr(
        lambda kk, pp: eng_mod.kmeans_points(kk, pp, 256, None,
                                             eng.backend))(key, x).jaxpr
out["shard_maps"] = sum(e.primitive.name == "shard_map"
                        for e in _iter_eqns(jaxpr))
out["shard_pads"] = sum(_is_row_pad(e)
                        and e.invars[0].aval.shape == (N_LOCAL, D)
                        for e in _iter_eqns(jaxpr))
out["loop_pads"] = [str(e.invars[0].aval.shape)
                    for body in _loop_bodies(jaxpr)
                    for e in _iter_eqns(body) if _is_row_pad(e)]

print(json.dumps(out))
