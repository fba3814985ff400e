"""Plain references that decide ``correct``.

Nothing here imports the program. Each function takes the cell's data (made
by :mod:`bench.reference.data` from the seed) and what the timed path
returned, and gives the numbers that are compared with the configuration's
``limits``:

* :func:`partition_numbers` — a clustering job's answer, centroids ``c`` and
  assignment ``a``: ``centroid_gap_eps``, the largest distance of a centroid
  from the float64 mean of the rows assigned to it (the Lloyd update),
  ``assign_gap``, the share of ``sum_i d(x_i, c[a_i])`` above
  ``sum_i min_j d(x_i, c_j)`` in float64 (the Lloyd assignment).
* :func:`layout_errors` — an IVF build's label-sorted layout: rows that are
  not the caller's rows in ``perm`` order, labels out of order, lists whose
  offsets disagree with the labels.
* :func:`search_numbers` — search answers against each query's exact
  float64 top-k: ``recall_at_k`` and ``dist_err_eps``, the largest gap
  between a returned distance and the exact distance of the returned row.
* :func:`probe_rank_errors` — search answers against the exact float64
  top-k over the rows of each query's ``nprobe`` nearest lists, the
  definition of an IVF search at a fixed ``nprobe`` on a given index.

Distances on the device run at ``Precision.HIGHEST`` in float32 to choose
candidates; every compared number is then recomputed in float64 on the host.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EPS32 = 2.0 ** -23
# Two squared distances within this many fp32 eps of (|x| + |q|)^2 are a
# tie: one fp32 score at d=128 is off by up to 3.2 eps (chip_smoke.py's
# TIE_EPS), and a swap needs two errors.
TIE_EPS = 8.0
_HOST_ROWS = 1 << 17


def _rows_per_block(width: int, budget: int = 1 << 26) -> int:
    """Rows of a (rows, width) float32 distance block within ``budget``
    elements, a power of two."""
    return 1 << max(8, (budget // max(width, 1)).bit_length() - 1)


_DOTS = {"highest": (jnp.float32, jax.lax.Precision.HIGHEST),
         "high": (jnp.float32, jax.lax.Precision.HIGH),
         "bf16": (jnp.bfloat16, jax.lax.Precision.HIGHEST)}


@partial(jax.jit, static_argnames=("m", "rows", "precision"))
def _nearest_m(points, targets, *, m, rows, precision):
    """Indices and distances of each row's ``m`` nearest ``targets`` by the
    matmul form ``|x|^2 - 2 x.t + |t|^2``, in blocks of ``rows`` rows."""
    dtype, hp = _DOTS[precision]
    n, d = points.shape
    pad = -n % rows
    x = jnp.pad(points, ((0, pad), (0, 0))).reshape(-1, rows, d)
    t = targets.astype(dtype)
    tn = jnp.sum(targets.astype(jnp.float32) ** 2, axis=1)

    def block(xb):
        xn = jnp.sum(xb.astype(jnp.float32) ** 2, axis=1)
        dots = jnp.matmul(xb.astype(dtype), t.T, precision=hp,
                          preferred_element_type=jnp.float32)
        d2 = xn[:, None] - 2.0 * dots + tn[None, :]
        neg, idx = jax.lax.top_k(-d2, m)
        return idx, -neg

    idx, d2 = jax.lax.map(block, x)
    return idx.reshape(-1, m)[:n], d2.reshape(-1, m)[:n]


def nearest(points, targets, m: int, precision: str = "highest"):
    """(indices (n, m), float32 distances (n, m)) of each of ``points``'
    ``m`` nearest ``targets``, on the device. ``precision`` is that of the
    dot products: ``"highest"`` (float32, the reference), or ``"high"`` or
    ``"bf16"`` for a control."""
    rows = min(_rows_per_block(targets.shape[0]),
               1 << max(8, (points.shape[0] - 1).bit_length()))
    return _nearest_m(points, jnp.asarray(targets), m=m, rows=rows,
                      precision=precision)


def _d2_64(x64: np.ndarray, c64: np.ndarray) -> np.ndarray:
    """Row-wise squared distances in float64, difference form."""
    diff = x64 - c64
    return np.einsum("ij,ij->i", diff, diff)


def partition_numbers(points, host_points, centroids, assignment) -> dict:
    """``centroid_gap_eps`` and ``assign_gap`` of one clustering answer (clusters with no rows keep their centroid and are
    not compared). ``points`` is the
    device array the job ran on and ``host_points`` its copy on the host;
    ``centroids`` (k, d) and ``assignment`` (n,) are the job's answer."""
    c = np.asarray(centroids, np.float64)
    a = np.asarray(assignment).astype(np.int64)
    k, d = c.shape
    n = points.shape[0]
    if a.shape != (n,) or a.min() < 0 or a.max() >= k:
        return {"centroid_gap_eps": float("inf"), "assign_gap": float("inf")}
    cand, _ = nearest(points, jnp.asarray(c, jnp.float32), min(2, k))
    cand = np.asarray(cand)
    sums = np.zeros((k, d))
    counts = np.bincount(a, minlength=k).astype(np.float64)
    sq = 0.0
    got = best = 0.0
    for lo in range(0, n, _HOST_ROWS):
        x = host_points[lo:lo + _HOST_ROWS].astype(np.float64)
        ab = a[lo:lo + _HOST_ROWS]
        for j in range(d):
            sums[:, j] += np.bincount(ab, weights=x[:, j], minlength=k)
        sq += float(np.einsum("ij,ij->", x, x))
        da = _d2_64(x, c[ab])
        dmin = da
        for col in range(cand.shape[1]):
            dmin = np.minimum(dmin, _d2_64(x, c[cand[lo:lo + _HOST_ROWS,
                                                     col]]))
        got += float(da.sum())
        best += float(dmin.sum())
    full = counts > 0
    means = sums[full] / counts[full, None]
    rms = np.sqrt(sq / (n * d))
    gap = float(np.abs(c[full] - means).max()) / (rms * EPS32)
    return {"centroid_gap_eps": gap, "assign_gap": (got - best) / best}


def layout_errors(points, sorted_points, perm, labels, starts, counts) -> int:
    """Faults of a label-sorted layout: rows of ``sorted_points`` that are
    not ``points[perm]`` bit for bit, ``perm`` entries that break the
    permutation, labels out of order and lists whose offsets disagree."""
    n = points.shape[0]
    perm_h = np.asarray(perm).astype(np.int64)
    if perm_h.shape != (n,) or perm_h.min() < 0 or perm_h.max() >= n:
        return n
    errors = int(np.abs(np.bincount(perm_h, minlength=n) - 1).sum())
    rows_off = jnp.sum(jnp.any(sorted_points != points[jnp.asarray(perm_h)],
                               axis=1))
    errors += int(rows_off)
    lab = np.asarray(labels).astype(np.int64)
    nlist = np.asarray(counts).shape[0]
    errors += int((np.diff(lab) < 0).sum())
    want = np.bincount(lab, minlength=nlist)
    errors += int((np.asarray(counts) != want[:nlist]).sum())
    errors += int((np.asarray(starts) != np.cumsum(want) - want).sum())
    return errors


def exact_topk(points, host_points, queries,
               k: int) -> tuple[np.ndarray, np.ndarray]:
    """Each query's ``k`` nearest rows and their squared distances in
    float64: float32 candidates on the device (2k per query), ordered in
    float64 on the host by (distance, row)."""
    q = jnp.asarray(queries, jnp.float32)
    cand, _ = nearest(q, points, 2 * k)
    cand = np.asarray(cand)
    q64 = np.asarray(q, np.float64)
    rows = host_points[cand.ravel()].astype(np.float64)
    d2 = ((rows.reshape(cand.shape + (-1,)) - q64[:, None]) ** 2).sum(2)
    order = np.lexsort((cand, d2), axis=1)[:, :k]
    return (np.take_along_axis(cand, order, 1),
            np.take_along_axis(d2, order, 1))


def search_numbers(points, host_points, queries, ids, dists,
                   k: int) -> dict:
    """``recall_at_k`` and ``dist_err_eps`` of search answers: ``queries``
    (Q, d) were answered with ``ids`` (Q, k) and ``dists`` (Q, k)."""
    ids = np.asarray(ids).astype(np.int64)
    dists = np.asarray(dists, np.float64)
    n = points.shape[0]
    truth, _ = exact_topk(points, host_points, queries, k)
    hits = sum(len(set(f) & set(t)) for f, t in zip(ids.tolist(),
                                                    truth.tolist()))
    valid = (ids >= 0) & (ids < n)
    safe = np.where(valid, ids, 0)
    q64 = np.asarray(queries, np.float64)
    rows = host_points[safe.ravel()].astype(np.float64)
    rows = rows.reshape(ids.shape + (-1,))
    exact = ((rows - q64[:, None]) ** 2).sum(2)
    scale = (np.sqrt((rows ** 2).sum(2))
             + np.sqrt((q64 ** 2).sum(1))[:, None]) ** 2
    err = np.where(valid, np.abs(dists - exact) / (scale * EPS32), np.inf)
    return {"recall_at_k": hits / truth.size,
            "dist_err_eps": float(np.nan_to_num(err, nan=np.inf).max())}


@partial(jax.jit, static_argnames=("m", "rows"))
def _nearest_in_lists(queries, points, row_list, allowed, *, m, rows):
    """Each query's ``m`` nearest rows among those whose list
    ``row_list[row]`` it may probe (``allowed`` (Q, nlist)), in blocks of
    ``rows`` queries, float32 at HIGHEST."""
    q_n, d = queries.shape
    pad = -q_n % rows
    qb = jnp.pad(queries, ((0, pad), (0, 0))).reshape(-1, rows, d)
    ab = jnp.pad(allowed, ((0, pad), (0, 0))).reshape(-1, rows,
                                                       allowed.shape[1])
    pn = jnp.sum(points * points, axis=1)

    def block(args):
        q, al = args
        dots = jnp.matmul(q, points.T, precision=jax.lax.Precision.HIGHEST)
        d2 = jnp.sum(q * q, axis=1)[:, None] - 2.0 * dots + pn[None, :]
        d2 = jnp.where(al[:, row_list], d2, jnp.inf)
        neg, idx = jax.lax.top_k(-d2, m)
        return idx, -neg

    idx, d2 = jax.lax.map(block, (qb, ab))
    return idx.reshape(-1, m)[:q_n], d2.reshape(-1, m)[:q_n]


def probe_topk(points, host_points, queries, centroids, row_list,
               nprobe: int, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Each query's ``k`` nearest rows and their float64 squared distances,
    among the rows of its ``nprobe`` nearest lists: lists ranked by float64
    distance to ``centroids`` (nlist, d); row ``i`` belongs to list
    ``row_list[i]``. Candidates (2k per query) are chosen in float32 on the
    device."""
    q64 = np.asarray(queries, np.float64)
    c64 = np.asarray(centroids, np.float64)
    cd = ((q64 ** 2).sum(1)[:, None] - 2.0 * q64 @ c64.T
          + (c64 ** 2).sum(1)[None, :])
    lists = np.argsort(cd, axis=1, kind="stable")[:, :nprobe]
    allowed = np.zeros(cd.shape, bool)
    np.put_along_axis(allowed, lists, True, axis=1)
    n = points.shape[0]
    cand, d2_32 = _nearest_in_lists(
        jnp.asarray(queries, jnp.float32), points,
        jnp.asarray(row_list, jnp.int32), jnp.asarray(allowed),
        m=2 * k, rows=max(8, min(256, (1 << 26) // n)))
    cand = np.asarray(cand)
    rows = host_points[cand.ravel()].astype(np.float64)
    d2 = ((rows.reshape(cand.shape + (-1,)) - q64[:, None]) ** 2).sum(2)
    d2 = np.where(np.isfinite(np.asarray(d2_32)), d2, np.inf)  # not probed
    order = np.lexsort((cand, d2), axis=1)[:, :k]
    return (np.take_along_axis(cand, order, 1),
            np.take_along_axis(d2, order, 1))


def probe_rank_errors(points, host_points, queries, ids, centroids,
                      row_list, nprobe: int, k: int) -> int:
    """Ranks, over all queries, at which the returned rows ``ids`` (Q, k),
    ordered by their float64 distance, lie farther than the exact top-k over
    the query's ``nprobe`` nearest lists (:func:`probe_topk`) by more than a
    tie (``TIE_EPS``). A list or tile the search should have scanned and
    did not, a row lost in the merge, a wrong id: each puts a farther row
    in some rank."""
    truth, t_d2 = probe_topk(points, host_points, queries, centroids,
                             row_list, nprobe, k)
    ids = np.asarray(ids).astype(np.int64)
    n = points.shape[0]
    valid = (ids >= 0) & (ids < n)
    q64 = np.asarray(queries, np.float64)
    rows = host_points[np.where(valid, ids, 0).ravel()].astype(np.float64)
    got = ((rows.reshape(ids.shape + (-1,)) - q64[:, None]) ** 2).sum(2)
    got = np.sort(np.where(valid, got, np.inf), axis=1)
    t_rows = host_points[truth.ravel()].astype(np.float64)
    scale = (np.sqrt((t_rows ** 2).sum(1)).reshape(truth.shape)
             + np.sqrt((q64 ** 2).sum(1))[:, None]) ** 2
    return int((got > t_d2 + TIE_EPS * EPS32 * scale).sum())
