"""Plain references for a row-sharded dataset, worked shard by shard: no
step gathers the rows onto one chip or the host. It imports nothing of the
program.

* :func:`partition_numbers` — the numbers of ``checks.partition_numbers``
  (``centroid_gap_eps``, ``assign_gap``) for points sharded by row over a
  mesh. Each chip reduces its own rows in blocks: the squared distances of
  each row to every centroid in float32 at ``Precision.HIGHEST`` (the
  distance to its assigned centroid, the least one, and their difference),
  and the per-cluster sums and counts of its rows. The sums are exact: each
  coordinate splits into a multiple of a power-of-two unit, summed as an
  integer, and a remainder below that unit, summed with compensation. The
  host combines the shards in float64.
* :func:`kmeans` — plain k-means++ seeding and Lloyd iterations on the
  sharded points, the control of a sharded clustering cell, with the
  semantics of ``kmeans.kmeans``: each shard streams its own rows in blocks,
  k-means++ draws each seed by a Gumbel-max over all rows (each shard its
  own Gumbel noise, the largest score across shards wins), the drawn row
  reaches every shard by a sum, and the cluster sums, counts and potential
  are summed across shards.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from bench.reference.checks import EPS32

_PRECISION = {"bf16": jax.lax.Precision.HIGHEST,
              "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}
_DEVICE_ROWS = 8192       # rows of a block of the control's k-means
_CHECK_ROWS = 4096        # rows of a block of the check
_UNIT_BITS = 8            # a coordinate's integer part: at most 2**8 units,
#                           so a shard's sums stay exact in int32 up to 2**23
#                           rows a cluster


def _mesh_axis(points):
    """The mesh the rows are sharded over, and its axis."""
    return points.sharding.mesh, points.sharding.spec[0]


def _blocks(n_local: int, rows: int, fn, init):
    """``fori_loop`` over the shard's row blocks of ``rows`` rows: ``fn(b,
    start, valid, carry)``. The last block starts early enough to end at
    the shard's end; ``valid`` marks its rows that no earlier block had."""
    def body(b, carry):
        start = jnp.minimum(b * rows, n_local - rows)
        valid = start + jnp.arange(rows) >= b * rows
        return fn(b, start, valid, carry)
    return jax.lax.fori_loop(0, -(-n_local // rows), body, init)


def _varying(x, axis):
    return jax.lax.pcast(x, axis, to="varying")


def _partition_local(x, c, a, *, rows, axis):
    """One shard's reductions (each with a leading axis of 1 to stack the
    shards on): integer and remainder parts of the cluster sums, counts, the
    unit, and ``sum d(x, c[a])``, ``sum (d(x, c[a]) - min_j d(x, c_j))``,
    ``sum |x|^2`` in float32."""
    n_local, d = x.shape
    k = c.shape[0]
    hp = jax.lax.Precision.HIGHEST
    cn = jnp.sum(c * c, axis=1)
    top = jax.lax.pmax(jnp.max(jnp.abs(x)), axis)
    unit = jnp.ldexp(jnp.float32(1.0), jnp.frexp(top)[1] - _UNIT_BITS)

    def fn(b, start, valid, carry):
        hi, lo, comp, cnt, got, excess, sq = carry
        xs = jax.lax.dynamic_slice(x, (start, 0), (rows, d))
        ab = jax.lax.dynamic_slice(a, (start,), (rows,))
        xn = jnp.sum(xs * xs, axis=1)
        d2 = xn[:, None] - 2.0 * jnp.matmul(xs, c.T, precision=hp) \
            + cn[None, :]
        da = jnp.take_along_axis(d2, ab[:, None], axis=1)[:, 0]
        onehot = jax.nn.one_hot(ab, k, dtype=jnp.float32) * valid[:, None]
        q = jnp.round(xs / unit)        # exact: |q| <= 2**_UNIT_BITS
        hi = hi + jnp.matmul(onehot.T, q, precision=hp).astype(jnp.int32)
        y = jnp.matmul(onehot.T, xs - q * unit, precision=hp) - comp
        t = lo + y                       # Kahan: lo + comp is the sum
        comp = (t - lo) - y
        return (hi, t, comp, cnt + jnp.sum(onehot, axis=0).astype(jnp.int32),
                got + jnp.sum(jnp.where(valid, da, 0.0)),
                excess + jnp.sum(jnp.where(valid, da - jnp.min(d2, axis=1),
                                           0.0)),
                sq + jnp.sum(jnp.where(valid, xn, 0.0)))

    zero = lambda shape, dt=jnp.float32: _varying(  # noqa: E731
        jnp.zeros(shape, dt), axis)
    out = _blocks(n_local, rows, fn,
                  (zero((k, d), jnp.int32), zero((k, d)), zero((k, d)),
                   zero((k,), jnp.int32), zero(()), zero(()), zero(())))
    return tuple(v[None] for v in out[:2] + (-out[2],) + out[3:]) \
        + (unit[None],)


@partial(jax.jit, static_argnames=("rows", "mesh", "axis"))
def _partition(points, centroids, assignment, *, rows, mesh, axis):
    return jax.shard_map(partial(_partition_local, rows=rows, axis=axis),
                         mesh=mesh, in_specs=(P(axis), P(), P(axis)),
                         out_specs=P(axis))(points, centroids, assignment)


def partition_numbers(points, centroids, assignment) -> dict:
    """``centroid_gap_eps`` and ``assign_gap`` of one clustering answer on
    row-sharded ``points`` (clusters with no rows keep their centroid and
    are not compared): ``centroids`` (k, d) and ``assignment`` (n,) are the
    job's answer."""
    c = np.asarray(centroids, np.float64)
    k, d = c.shape
    n = points.shape[0]
    if assignment.shape != (n,) or int(jnp.min(assignment)) < 0 \
            or int(jnp.max(assignment)) >= k:
        return {"centroid_gap_eps": float("inf"), "assign_gap": float("inf")}
    mesh, axis = _mesh_axis(points)
    n_local = points.addressable_shards[0].data.shape[0]
    hi, lo, comp, cnt, got, excess, sq, unit = jax.device_get(_partition(
        points, jnp.asarray(c, jnp.float32),
        jax.device_put(assignment, points.sharding),
        rows=min(_CHECK_ROWS, n_local), mesh=mesh, axis=axis))
    f64 = partial(np.asarray, dtype=np.float64)
    sums = (f64(hi) * f64(unit)[:, None, None] + f64(lo) + f64(comp)).sum(0)
    counts = f64(cnt).sum(0)
    full = counts > 0
    means = sums[full] / counts[full, None]
    rms = np.sqrt(f64(sq).sum() / (n * d))
    gap = float(np.abs(c[full] - means).max()) / (rms * EPS32)
    got, excess = f64(got).sum(), f64(excess).sum()
    return {"centroid_gap_eps": gap, "assign_gap": excess / (got - excess)}


_to_bf16 = jax.jit(lambda p: p.astype(jnp.bfloat16))


def kmeans(key, points, *, k, max_iters, tol=1e-6, precision="highest"):
    """(centroids (k, d) float32, assignment (n,) int32 sharded like the
    rows, Lloyd iterations run () int32) on row-sharded ``points``.
    ``precision`` as in ``kmeans.kmeans``: ``"bf16"`` rounds the points to
    bfloat16 once, in a program of its own, and works on them throughout."""
    x = _to_bf16(points) if precision == "bf16" else points
    n_local = points.addressable_shards[0].data.shape[0]
    mesh, axis = _mesh_axis(points)
    return _kmeans(key, x, k=k, max_iters=max_iters, tol=tol,
                   precision=precision, rows=min(_DEVICE_ROWS, n_local),
                   mesh=mesh, axis=axis)


@partial(jax.jit, static_argnames=("k", "max_iters", "tol", "precision",
                                   "rows", "mesh", "axis"))
def _kmeans(key, x, *, k, max_iters, tol, precision, rows, mesh, axis):
    body = partial(_kmeans_local, k=k, max_iters=max_iters, tol=tol,
                   precision=precision, rows=rows, axis=axis)
    return jax.shard_map(body, mesh=mesh, in_specs=(P(), P(axis)),
                         out_specs=(P(), P(axis), P()))(key, x)


def _kmeans_local(key, x, *, k, max_iters, tol, precision, rows, axis):
    hp = _PRECISION[precision]
    n_local, d = x.shape
    me = jax.lax.axis_index(axis)
    n = n_local * jax.lax.axis_size(axis)

    def block(start):
        return jax.lax.dynamic_slice(x, (start, 0), (rows, d))

    def norms(b, start, valid, out):
        xs = block(start).astype(jnp.float32)
        return jax.lax.dynamic_update_slice(out, jnp.sum(xs * xs, axis=1),
                                            (start,))

    xn = _blocks(n_local, rows, norms,
                 _varying(jnp.zeros((n_local,), jnp.float32), axis))

    def d2_to(c):
        cc = jnp.sum(c * c)

        def fn(b, start, valid, out):
            dot = jnp.matmul(block(start), c.astype(x.dtype), precision=hp,
                             preferred_element_type=jnp.float32)
            xb = jax.lax.dynamic_slice(xn, (start,), (rows,))
            return jax.lax.dynamic_update_slice(
                out, jnp.maximum(xb - 2.0 * dot + cc, 0.0), (start,))
        return _blocks(n_local, rows, fn,
                       _varying(jnp.zeros((n_local,), jnp.float32), axis))

    def take(g):
        """Global row ``g`` on every shard, from the shard that holds it."""
        i = jnp.clip(g - me * n_local, 0, n_local - 1)
        row = jax.lax.dynamic_index_in_dim(x, i, keepdims=False)
        return jax.lax.psum(jnp.where(g // n_local == me,
                                      row.astype(jnp.float32), 0.0), axis)

    k0, key = jax.random.split(key)
    c0 = take(jax.random.randint(k0, (), 0, n))
    cents = jnp.zeros((k, d), jnp.float32).at[0].set(c0)

    def seed_round(j, carry):
        cents, md, key = carry
        key, sk = jax.random.split(key)
        score = jnp.log(md) + jax.random.gumbel(jax.random.fold_in(sk, me),
                                                (n_local,))
        i = jnp.argmax(score)
        vals = jax.lax.all_gather(score[i], axis)
        idxs = jax.lax.all_gather(i + me * n_local, axis)
        c = take(idxs[jnp.argmax(vals)])
        return cents.at[j].set(c), jnp.minimum(md, d2_to(c)), key

    cents, _, _ = jax.lax.fori_loop(1, k, seed_round,
                                    (cents, d2_to(c0), key))

    def assign(c):
        cn = jnp.sum(c * c, axis=1)
        cl = c.astype(x.dtype)

        def fn(b, start, valid, carry):
            a, sums, cnt, pot = carry
            xs = block(start)
            dot = jnp.matmul(xs, cl.T, precision=hp,
                             preferred_element_type=jnp.float32)
            xb = jax.lax.dynamic_slice(xn, (start,), (rows,))
            d2 = jnp.maximum(xb[:, None] - 2.0 * dot + cn[None, :], 0.0)
            ab = jnp.argmin(d2, axis=1).astype(jnp.int32)
            onehot = jax.nn.one_hot(ab, k, dtype=jnp.float32) \
                * valid[:, None]
            sums = sums + jnp.matmul(onehot.T, xs.astype(jnp.float32),
                                     precision=jax.lax.Precision.HIGHEST)
            return (jax.lax.dynamic_update_slice(a, ab, (start,)), sums,
                    cnt + jnp.sum(onehot, axis=0),
                    pot + jnp.sum(jnp.where(valid, jnp.min(d2, axis=1),
                                            0.0)))

        init = (_varying(jnp.zeros((n_local,), jnp.int32), axis),
                _varying(jnp.zeros((k, d), jnp.float32), axis),
                _varying(jnp.zeros((k,), jnp.float32), axis),
                _varying(jnp.zeros((), jnp.float32), axis))
        a, sums, cnt, pot = _blocks(n_local, rows, fn, init)
        return (a, jax.lax.psum(sums, axis), jax.lax.psum(cnt, axis),
                jax.lax.psum(pot, axis))

    def cond(s):
        i, _, prev, cur, _ = s
        rel = (prev - cur) / jnp.maximum(prev, 1e-30)
        return (i < max_iters) & ((i < 2) | (rel > tol))

    def body(s):
        i, c, _, cur, _ = s
        a, sums, cnt, pot = assign(c)
        new = jnp.where(cnt[:, None] > 0,
                        sums / jnp.maximum(cnt, 1)[:, None], c)
        return i + 1, new, cur, pot, a

    iters, cents, _, _, a = jax.lax.while_loop(
        cond, body, (jnp.int32(0), cents, jnp.float32(jnp.inf),
                     jnp.float32(jnp.inf),
                     _varying(jnp.zeros((n_local,), jnp.int32), axis)))
    return cents, a, iters
