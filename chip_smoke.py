#!/usr/bin/env python3
"""On-chip smoke test of the main path, through the entry points a user calls.

    python chip_smoke.py             # one chip: paper_job, ivf_search
    python chip_smoke.py --chips 4   # four chips: mesh_fit only

Phases (all data generated from ``--seed``):

* ``paper_job`` — the paper's job (``configs/kmeans_paper.FULL``: n=4,000,000,
  d=2, k=50 fp32 blobs): ``ClusterEngine("pallas").kmeans`` twice (cold, then
  warm), its k-means++ seeds checked to be rows of the data, and the same fit
  from those seeds on ``ClusterEngine("reference")``; both final inertias are
  recomputed in float64 on the host and must agree within ``INERTIA_RTOL``.
* ``ivf_search`` — a SIFT-shaped index (n=1,048,576, d=128 fp32, nlist=4096)
  built with ``engine=ClusterEngine("pallas")`` and searched for k=10 with
  1,000 held-out queries at nprobe=64 and nprobe=nlist, against each query's
  exact top-10 computed in float64 on the host; at nprobe=nlist every
  returned neighbour must be one of them up to distance ties within the
  fp32 matmul form's rounding (``TIE_EPS``): recall@10 = 1.0.
* ``mesh_fit`` (``--chips 4``) — ``ClusterEngine("mesh", local="pallas")`` on
  a ``jax.make_mesh((4,), ("data",))`` mesh with the points row-sharded by a
  ``NamedSharding`` (n=16,777,216, d=2, k=50), compared with the same fit
  from the same centroids on one of the four chips.

Every phase also checks that no kernel fell back to XLA, that the Pallas
backend served the call, and that the lowered program holds the compiled
kernels (``tpu_custom_call``). Earlier lines of stdout report each phase; the
times are smoke timings (cold includes compilation), not benchmark numbers.
The last line is one JSON object: ``{"ok": true, "device": {...}}``. Without a
TPU, or with any check failing, the script exits non-zero and prints no such
line. It runs in one process and starts no other.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

INERTIA_RTOL = 1e-3      # pallas vs reference final inertia (float64 on host)
MESH_INERTIA_RTOL = 1e-4  # mesh vs one-chip fit (tests/distributed_worker.py)
MESH_ASSIGN_MATCH = 0.999
# full-probe ties, in fp32 machine epsilons (2^-23) of (|x| + |q|)^2. One
# fp32 matmul-form score at d=128 was off its exact value by at most 3.2 eps
# in a CPU sample of 50,000 near-neighbour scores; two rows swap only when
# both scores err, so a tie is two 4-eps errors.
EPS32 = 2.0 ** -23
TIE_EPS = 8 * EPS32
IVF = dict(n=1_048_576, d=128, nlist=4096, queries=1_000, k=10, nprobe=64)
MESH = dict(n=16_777_216, d=2, k=50, max_iters=25)


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def timed(fn, *arrays_of):
    """(result, seconds) of ``fn()``, waiting for the device arrays that
    ``arrays_of(result)`` names."""
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready([a(out) for a in arrays_of])
    return out, time.perf_counter() - t0


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def inertia64(points, centroids, chunk: int = 1 << 20) -> float:
    """Sum over points of the squared distance to the nearest centroid, in
    float64 on the host."""
    import numpy as np
    c = np.asarray(centroids, np.float64)
    cn = (c * c).sum(1)
    total = 0.0
    for lo in range(0, points.shape[0], chunk):
        x = np.asarray(points[lo:lo + chunk], np.float64)
        d2 = (x * x).sum(1)[:, None] - 2.0 * x @ c.T + cn[None, :]
        total += float(np.maximum(d2.min(1), 0.0).sum())
    return total


def check_engine(eng, local_name: str = "pallas") -> None:
    be = eng.last_backend
    served = be.local.name if be.distributed else be.name
    require(eng.fallback_events == [],
            f"kernel fallback happened: {eng.fallback_events}")
    require(served == local_name, f"backend {served!r} served, not pallas")


def check_kernels(lowered, what: str) -> None:
    require("tpu_custom_call" in lowered.as_text(),
            f"{what}: no compiled Pallas kernel in the lowered program")


def lower_scan(idx, queries, k: int):
    """The scan launch ``idx.search`` makes (``kernels.ops.ivf_scan`` with
    the index's arrays and the search defaults), lowered."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    nq = queries.shape[0]
    scan = jax.jit(ops.ivf_scan, static_argnames=("k", "block_n", "gate"))
    return scan.lower(
        queries, idx.points, idx.norms, idx.centers, idx.radii,
        jax.ShapeDtypeStruct((nq, idx.n_tiles), jnp.int32),
        jax.ShapeDtypeStruct((nq,), jnp.int32), k=min(k, idx.n),
        block_n=idx.block_n, gate=True)


def exact_topk(points, queries, k: int, chunk: int = 1 << 14):
    """Each query's k nearest rows, in float64 on the host: the matmul form
    picks 2k candidates per query, the difference form orders them. Returns
    (rows (Q, k), squared distances (Q, k))."""
    import numpy as np
    q = np.asarray(queries, np.float64)
    qn = (q * q).sum(1)[:, None]
    cand_d, cand_i = [], []
    for lo in range(0, points.shape[0], chunk):
        x = np.asarray(points[lo:lo + chunk], np.float64)
        d2 = (x * x).sum(1)[None, :] - 2.0 * q @ x.T + qn
        m = min(2 * k, d2.shape[1])
        part = np.argpartition(d2, m - 1, axis=1)[:, :m]
        cand_d.append(np.take_along_axis(d2, part, 1))
        cand_i.append(part + lo)
    cand_d, cand_i = np.concatenate(cand_d, 1), np.concatenate(cand_i, 1)
    best = np.argsort(cand_d, axis=1)[:, :2 * k]
    rows = np.take_along_axis(cand_i, best, 1)
    exact = ((np.asarray(points[rows.ravel()], np.float64).reshape(
        rows.shape + (-1,)) - q[:, None]) ** 2).sum(2)
    order = np.lexsort((rows, exact), axis=1)[:, :k]
    return (np.take_along_axis(rows, order, 1),
            np.take_along_axis(exact, order, 1))


def paper_job(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs.kmeans_paper import FULL as cfg
    from repro.core import ClusterEngine
    from repro.data.synthetic import blobs

    pts_np, _ = blobs(cfg.n_points, cfg.dim, cfg.k, seed=seed)
    pts = jax.device_put(pts_np)
    key = jax.random.PRNGKey(seed)
    eng = ClusterEngine("pallas")

    def run():
        return eng.kmeans(key, pts, cfg.k, max_iters=cfg.max_iters)

    res, cold = timed(run, lambda r: r.centroids, lambda r: r.assignment)
    res, warm = timed(run, lambda r: r.centroids, lambda r: r.assignment)
    check_engine(eng)
    check_kernels(eng.lowered_last_call(), "paper_job kmeans")

    # the seeds kmeans drew: same key, same k-means++ seeding
    seeds = eng.seed(key, pts, cfg.k)
    idx = np.asarray(seeds.indices)
    require(np.array_equal(np.asarray(seeds.centroids), pts_np[idx]),
            "a k-means++ seed is not a row of the data")
    ref_eng = ClusterEngine("reference")
    ref = ref_eng.fit(pts, seeds.centroids, max_iters=cfg.max_iters)
    ref_idx = np.asarray(ref_eng.seed(key, pts, cfg.k).indices)
    phi = inertia64(pts_np, res.centroids)
    phi_ref = inertia64(pts_np, ref.centroids)
    gap = abs(phi - phi_ref) / phi_ref
    # the CPU suite pins fused == pallas bitwise; report whether the chip's
    # XLA and Mosaic arithmetic keep that pin
    fused = ClusterEngine("fused")
    fused_idx = np.asarray(fused.seed(key, pts, cfg.k).indices)
    # one seeding round against the same first centroid: the arithmetic gap
    md_f = np.asarray(fused.seed(key, pts, 1).min_d2, np.float64)
    md_p = np.asarray(eng.seed(key, pts, 1).min_d2, np.float64)
    fused_fit = fused.fit(pts, seeds.centroids, max_iters=cfg.max_iters)
    pallas_fit = eng.fit(pts, seeds.centroids, max_iters=cfg.max_iters)
    check_engine(eng)
    fused_parity = dict(
        same_seed_indices=bool(np.array_equal(idx, fused_idx)),
        round1_min_d2_bitwise=bool(np.array_equal(md_f, md_p)),
        round1_min_d2_max_abs_diff=float(np.abs(md_f - md_p).max()),
        round1_min_d2_max=float(md_p.max()),
        same_fit_centroids=bool(np.array_equal(
            np.asarray(fused_fit.centroids),
            np.asarray(pallas_fit.centroids))),
        same_fit_assignment=float(np.mean(
            np.asarray(fused_fit.assignment)
            == np.asarray(pallas_fit.assignment))),
        fit_inertia_rel_gap=abs(float(fused_fit.inertia)
                                - float(pallas_fit.inertia))
        / float(pallas_fit.inertia))
    report("paper_job", n=cfg.n_points, d=cfg.dim, k=cfg.k,
           cold_s=cold, warm_s=warm, n_iters=int(res.n_iters),
           inertia=phi, reference_inertia=phi_ref, inertia_rel_gap=gap,
           inertia_rtol=INERTIA_RTOL,
           reference_same_seed_indices=bool(np.array_equal(idx, ref_idx)),
           fused_vs_pallas=fused_parity,
           peak_bytes_in_use=peak_bytes(jax.devices()[0]))
    require(gap <= INERTIA_RTOL,
            f"pallas inertia {phi} vs reference {phi_ref}: rel gap {gap}")


def recall(found, truth) -> float:
    """Mean fraction of each query's true neighbours that were found."""
    hits = [len(set(f) & set(t)) for f, t in zip(found.tolist(),
                                                 truth.tolist())]
    return sum(hits) / truth.size


def ivf_search(seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import ClusterEngine
    from repro.data.synthetic import blobs
    from repro.serve import IvfIndex

    n, d, nlist, nq, k = (IVF[f] for f in ("n", "d", "nlist", "queries",
                                           "k"))
    data, _ = blobs(n + nq, d, nlist, seed=seed)
    pts_np, q_np = data[:n], data[n:]
    pts, queries = jax.device_put(pts_np), jax.device_put(q_np)
    key = jax.random.PRNGKey(seed)
    eng = ClusterEngine("pallas")

    def build():
        return IvfIndex.build(pts, nlist, engine=eng, key=key)

    idx, build_cold = timed(build, lambda i: i.points, lambda i: i.perm)
    idx, build_warm = timed(build, lambda i: i.points, lambda i: i.perm)
    check_engine(eng)
    check_kernels(eng.lowered_last_call(), "ivf build kmeans")

    out = {}
    for nprobe in (IVF["nprobe"], nlist):
        def search():
            return idx.search(queries, k, nprobe=nprobe)
        res, cold = timed(search, lambda r: r.indices)
        res, warm = timed(search, lambda r: r.indices)
        require(res.backend == "pallas",
                f"the scan at nprobe={nprobe} fell back to {res.backend!r}")
        out[nprobe] = (res, cold, warm)
    check_kernels(lower_scan(idx, queries, k), "ivf scan")

    truth, truth_d2 = exact_topk(pts_np, q_np, k)
    # ties: the scan ranks by the fp32 matmul form, whose rounding error is
    # absolute, of the order of eps * (|x| + |q|)^2; two rows whose exact
    # distances lie closer than that may come out in either order. A
    # returned row counts as correct when its exact squared distance
    # exceeds the exact 10th neighbour's by at most TIE_EPS * (|x| + |q|)^2.
    q64 = q_np.astype(np.float64)
    rows = pts_np[np.asarray(out[nlist][0].indices)].astype(np.float64)
    got = ((rows - q64[:, None]) ** 2).sum(2)
    scale = (np.sqrt((rows ** 2).sum(2))
             + np.sqrt((q64 ** 2).sum(1))[:, None]) ** 2
    excess = got - truth_d2[:, -1:]
    recall_ties = float((excess <= TIE_EPS * scale).mean())
    outside = excess > 0
    fields = {}
    for nprobe, (res, cold, warm) in out.items():
        fields[f"nprobe_{nprobe}"] = dict(
            cold_s=cold, warm_s=warm,
            recall_at_10=recall(np.asarray(res.indices), truth),
            probed_tiles_mean=float(np.asarray(res.probed_tiles).mean()))
    report("ivf_search", n=n, d=d, nlist=nlist, queries=nq, k=k,
           block_n=idx.block_n, build_cold_s=build_cold,
           build_warm_s=build_warm, full_probe_recall_up_to_ties=recall_ties,
           full_probe_beyond_exact_order=int(outside.sum()),
           full_probe_max_excess_in_eps=float((excess / scale).max()
                                              / EPS32),
           tie_eps=TIE_EPS / EPS32,
           peak_bytes_in_use=peak_bytes(jax.devices()[0]), **fields)
    require(recall_ties == 1.0,
            f"full-probe recall@10 up to ties is {recall_ties}, not 1.0")


def mesh_fit(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import ClusterEngine
    from repro.data.synthetic import blobs

    devices = jax.devices()
    require(len(devices) == 4, f"--chips 4 needs 4 devices, found "
                               f"{len(devices)}")
    n, d, k, iters = (MESH[f] for f in ("n", "d", "k", "max_iters"))
    pts_np, _ = blobs(n, d, k, seed=seed)
    mesh = jax.make_mesh((4,), ("data",))
    pts = jax.device_put(pts_np, NamedSharding(mesh, P("data")))
    rows = sorted(s.data.shape[0] for s in pts.addressable_shards)
    require(rows == [n // 4] * 4, f"shard rows {rows}, want 4 x {n // 4}")

    eng = ClusterEngine("mesh", mesh=mesh, local="pallas")
    seeds = np.asarray(eng.seed(jax.random.PRNGKey(seed), pts, k).centroids)
    init = jax.device_put(seeds, NamedSharding(mesh, P()))

    def fit():
        return eng.fit(pts, init, max_iters=iters)

    res, cold = timed(fit, lambda r: r.centroids, lambda r: r.assignment)
    res, warm = timed(fit, lambda r: r.centroids, lambda r: r.assignment)
    check_engine(eng)
    check_kernels(eng.lowered_last_call(), "mesh fit")

    one = ClusterEngine("pallas")
    pts1 = jax.device_put(pts_np, devices[0])
    single, single_s = timed(
        lambda: one.fit(pts1, jax.device_put(seeds, devices[0]),
                        max_iters=iters),
        lambda r: r.centroids, lambda r: r.assignment)
    check_engine(one)
    match = float((np.asarray(res.assignment)
                   == np.asarray(single.assignment)).mean())
    phi, phi1 = float(res.inertia), float(single.inertia)
    report("mesh_fit", n=n, d=d, k=k, chips=4, shard_rows=rows,
           cold_s=cold, warm_s=warm, one_chip_s=single_s,
           n_iters=int(res.n_iters), one_chip_n_iters=int(single.n_iters),
           inertia=phi, one_chip_inertia=phi1, assign_match=match,
           peak_bytes_in_use=[peak_bytes(dv) for dv in devices])
    require(np.isclose(phi, phi1, rtol=MESH_INERTIA_RTOL),
            f"mesh inertia {phi} vs one chip {phi1}")
    require(match > MESH_ASSIGN_MATCH, f"assignments agree on {match}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    report("start", compile_cache=cache, device_kind=devices[0].device_kind,
           device_count=len(devices))
    t0 = time.perf_counter()
    phases = [mesh_fit] if args.chips == 4 else [paper_job, ivf_search]
    for phase in phases:
        phase(args.seed)
    report("done", total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
