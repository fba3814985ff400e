"""Worker for tests/test_distributed.py — runs under 8 fake CPU devices in a
SUBPROCESS (jax locks device count at init; the main pytest process keeps 1
device so smoke tests measure realistic single-device behaviour)."""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (dist_kmeans, dist_kmeanspp, dist_lloyd, kmeanspp,
                        lloyd, quality, ring_psum, take_global)
from repro.data.synthetic import blobs

out = {}
mesh = jax.make_mesh((4, 2), ("data", "model"))

# ---------------------------------------------------------------------------
# 1. distributed k-means++ is a valid, quality-preserving seeding
# ---------------------------------------------------------------------------
pts_np, _ = blobs(4096, 2, 16, seed=0)
pts = jnp.asarray(pts_np)
key = jax.random.PRNGKey(0)

res_d = dist_kmeanspp(key, pts, 16, mesh=mesh, axes=("data", "model"))
res_s = kmeanspp(key, pts, 16)
phi_d = float(quality.inertia(pts, res_d.centroids))
phi_s = float(quality.inertia(pts, res_s.centroids))
out["dist_seeds_are_points"] = bool(np.allclose(
    np.asarray(res_d.centroids),
    np.asarray(pts)[np.asarray(res_d.indices)], rtol=1e-5))
out["dist_phi"] = phi_d
out["serial_phi"] = phi_s
out["dist_quality_ok"] = phi_d < 3 * phi_s

# min_d2 parity: the returned min_d2 must equal the true potential terms
md = np.asarray(res_d.min_d2)
true_md = np.min(np.asarray(
    quality.pairwise_d2(pts, res_d.centroids)
    if hasattr(quality, "pairwise_d2") else
    __import__("repro.core.kmeanspp", fromlist=["pairwise_d2"])
    .pairwise_d2(pts, res_d.centroids)), axis=1)
out["dist_min_d2_ok"] = bool(np.allclose(md, true_md, rtol=1e-4, atol=1e-5))

# ---------------------------------------------------------------------------
# 2. distributed Lloyd == single-device Lloyd (same seeds)
# ---------------------------------------------------------------------------
seeds = res_s.centroids
r_d = dist_lloyd(pts, seeds, mesh=mesh, axes=("data", "model"), max_iters=10)
r_s = lloyd(pts, seeds, max_iters=10)
out["lloyd_inertia_match"] = bool(np.isclose(float(r_d.inertia),
                                             float(r_s.inertia), rtol=1e-4))
out["lloyd_assign_match"] = bool(
    (np.asarray(r_d.assignment) == np.asarray(r_s.assignment)).mean() > 0.999)

# ---------------------------------------------------------------------------
# 3. collective helpers: take_global, ring_psum
# ---------------------------------------------------------------------------
x = jnp.arange(64, dtype=jnp.float32).reshape(16, 4)


def tg(idx):
    f = jax.shard_map(
        lambda p: take_global(p, jnp.asarray(idx, jnp.int32),
                              ("data", "model")),
        mesh=mesh, in_specs=P(("data", "model")), out_specs=P())
    with jax.set_mesh(mesh):
        return f(x)


out["take_global_ok"] = all(
    np.allclose(np.asarray(tg(i)), np.asarray(x[i])) for i in (0, 7, 15))


def rp(v):
    # out_specs keeps the data axis: VMA can't statically prove a ppermute
    # ring is replicated, so each shard returns its copy and we check parity
    f = jax.shard_map(
        lambda p: ring_psum(jnp.sum(p, keepdims=True), "data"),
        mesh=mesh, in_specs=P(("data",)), out_specs=P(("data",)))
    with jax.set_mesh(mesh):
        return f(v)


v = jnp.arange(8, dtype=jnp.float32)[:, None]
out["ring_psum_ok"] = bool(np.allclose(np.asarray(rp(v)),
                                       float(jnp.sum(v))))

# ---------------------------------------------------------------------------
# 4. gumbel seeding distribution parity: distributed sampler ∝ D^2
# ---------------------------------------------------------------------------
small = jnp.asarray([[0.0, 0.0]] * 30 + [[10.0, 0.0]] * 10, jnp.float32)
# after choosing point 0 (say), D^2 mass is concentrated on the far cluster
counts = np.zeros(2)
for s in range(120):
    r = dist_kmeanspp(jax.random.PRNGKey(s), small, 2, mesh=mesh,
                      axes=("data", "model"))
    counts[int(np.asarray(r.indices)[1] >= 30)] += 1
# P(second seed in far cluster) should be ~ (10*100)/(10*100 + small)
out["gumbel_far_fraction"] = float(counts[1] / counts.sum())
out["gumbel_dist_ok"] = counts[1] / counts.sum() > 0.7

# ---------------------------------------------------------------------------
# 4b. rejection seeding on the mesh: shared-stream pin + exact min_d2
# ---------------------------------------------------------------------------
from repro.core.engine import ClusterEngine

eng_m = ClusterEngine("mesh", mesh=mesh, axes=("data", "model"))
t_m = eng_m.seed(key, pts, 16, sampler="tiled")
r_m1 = eng_m.seed(key, pts, 16, sampler="rejection", refresh_block=1)
r_m8 = eng_m.seed(key, pts, 16, sampler="rejection", refresh_block=8)
out["mesh_rejection_pin_ok"] = bool(
    np.array_equal(np.asarray(t_m.indices), np.asarray(r_m1.indices)))
d2_m = jnp.min(jnp.sum((pts[:, None, :] - r_m8.centroids[None]) ** 2, -1), 1)
out["mesh_rejection_min_d2_ok"] = bool(np.allclose(
    np.asarray(r_m8.min_d2), np.asarray(d2_m), rtol=2e-4, atol=1e-3))
props_m = np.asarray(r_m8.proposals)
accs_m = np.asarray(r_m8.accepts)
out["mesh_rejection_counters_ok"] = bool(
    props_m.shape == (16,) and props_m[0] == 0 and accs_m[0] == 0
    and (accs_m <= props_m).all() and (props_m[1:] >= 1).all())

# coarse-to-fine on the mesh (ISSUE 9): r_m1/r_m8 above already run the
# default proposal='hier'; pin that flat at refresh_block=1 telescopes to
# the same stream, and that the hier counters obey the contract at rb=8
f_m1 = eng_m.seed(key, pts, 16, sampler="rejection", refresh_block=1,
                  proposal="flat")
out["mesh_hier_flat_pin_ok"] = bool(
    np.array_equal(np.asarray(t_m.indices), np.asarray(f_m1.indices)))
tg_m = np.asarray(r_m8.tightened)
sp_m = np.asarray(r_m8.supers)
out["mesh_hier_counters_ok"] = bool(
    tg_m.shape == (16,) and sp_m.shape == (16,)
    and tg_m[0] == 0 and sp_m[0] == 0
    and (props_m <= sp_m).all() and (sp_m <= props_m + 1).all())
f_m8 = eng_m.seed(key, pts, 16, sampler="rejection", refresh_block=8,
                  proposal="flat")
out["mesh_flat_counters_zero_ok"] = bool(
    (np.asarray(f_m8.tightened) == 0).all()
    and (np.asarray(f_m8.supers) == 0).all())

# ---------------------------------------------------------------------------
# 4c. dist_gumbel_topl: exact distributed top-l == replicated gumbel_topk,
#     and the k-means|| mesh init built on it returns valid seeds
# ---------------------------------------------------------------------------
from repro.core import collectives, sampling
from repro.core.kmeans_parallel import kmeans_parallel_init

lw = sampling.safe_log(jnp.abs(jnp.asarray(
    np.random.default_rng(7).normal(size=4096), jnp.float32)) + 1e-3)
ktop = jax.random.PRNGKey(21)


def topl_dist(l):
    f = jax.shard_map(
        lambda w: collectives.dist_gumbel_topl(ktop, w, l,
                                               ("data", "model"))[0],
        mesh=mesh, in_specs=P(("data", "model")), out_specs=P())
    with jax.set_mesh(mesh):
        return f(lw)


# parity oracle: same per-shard fold_in key schedule, replicated
def topl_ref(l):
    S, n_loc = 8, 4096 // 8
    scores = []
    for s in range(S):
        g = lw[s * n_loc:(s + 1) * n_loc] + jax.random.gumbel(
            jax.random.fold_in(ktop, s), (n_loc,), jnp.float32)
        scores.append(g)
    allg = jnp.concatenate(scores)
    _, idx = jax.lax.top_k(allg, l)
    return idx


got = np.sort(np.asarray(topl_dist(32)))
want = np.sort(np.asarray(topl_ref(32)))
out["dist_gumbel_topl_ok"] = bool(np.array_equal(got, want))

kp = kmeans_parallel_init(jax.random.PRNGKey(22), pts, 16, rounds=3,
                          backend=eng_m.backend)
phi_p = float(np.sum(np.asarray(kp.min_d2)))
out["mesh_kmeans_parallel_phi"] = phi_p
out["mesh_kmeans_parallel_ok"] = bool(
    np.allclose(np.asarray(kp.centroids),
                np.asarray(pts)[np.asarray(kp.indices)], rtol=1e-5)
    and phi_p < 3 * phi_s)

# ---------------------------------------------------------------------------
# 5. checkpoint reshard restore (elasticity): save on (4,2), load on (2,4)
# ---------------------------------------------------------------------------
from repro.checkpoint.manager import CheckpointManager
import tempfile

with tempfile.TemporaryDirectory() as td:
    mgr = CheckpointManager(td, async_save=False)
    w = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(16, 4),
                       NamedSharding(mesh, P("data", "model")))
    mgr.save(1, {"w": w})
    mesh2 = jax.make_mesh((2, 4), ("data", "model"))
    _, got = mgr.restore({"w": jnp.zeros((16, 4))},
                         shardings={"w": NamedSharding(mesh2,
                                                       P("data", "model"))})
    out["reshard_values_ok"] = bool(np.allclose(np.asarray(got["w"]),
                                                np.asarray(w)))
    out["reshard_sharding_ok"] = got["w"].sharding.spec == P("data", "model")

# ---------------------------------------------------------------------------
# 6. sharded train step == single-device train step (tiny arch)
# ---------------------------------------------------------------------------
from repro.configs.registry import get_config
from repro.launch.mesh import make_test_mesh
from repro.launch.step import (init_train_state, make_train_step,
                               train_state_shardings)
from repro.models.sharding import use_mesh
from repro.optim import AdamWConfig

cfg = get_config("deepseek-7b", smoke=True)
opt = AdamWConfig(lr=1e-3, warmup_steps=1, decay_steps=10)
kb = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(kb, (8, 32), 0, cfg.vocab),
         "labels": jax.random.randint(kb, (8, 32), 0, cfg.vocab)}

state0 = init_train_state(cfg, jax.random.PRNGKey(0))
_, m_single = jax.jit(make_train_step(cfg, opt))(state0, batch)

lm_mesh = make_test_mesh()
with use_mesh(lm_mesh):
    ssh = train_state_shardings(lm_mesh, state0)
    state_sharded = jax.device_put(state0, ssh)
    bsh = {k: NamedSharding(lm_mesh, P(("data",), None)) for k in batch}
    jf = jax.jit(make_train_step(cfg, opt), in_shardings=(ssh, bsh),
                 out_shardings=(ssh, None))
    _, m_shard = jf(state_sharded, jax.device_put(batch, bsh))

out["sharded_loss"] = float(m_shard["loss"])
out["single_loss"] = float(m_single["loss"])
out["train_step_parity"] = bool(np.isclose(float(m_shard["loss"]),
                                           float(m_single["loss"]),
                                           rtol=2e-3, atol=2e-3))

print(json.dumps(out, default=lambda o: bool(o) if isinstance(o, np.bool_)
                 else float(o)))
sys.exit(0 if all(v for k, v in out.items()
                  if k.endswith("_ok") or k.endswith("parity")
                  or k.endswith("match")) else 1)
