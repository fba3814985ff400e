"""Plain k-means++ seeding and Lloyd iterations, and the label-sorted IVF
layout built from them: the reference put in the program's place for the
controls of the clustering cells. It imports nothing of the program.

``precision`` is where the control's arithmetic sits below the
configuration's float32: ``"bf16"`` rounds the points to bfloat16 once and
works on them throughout (dot products, norms, sums accumulate in float32),
``"high"`` keeps float32 points and runs the dot products at
``Precision.HIGH`` (three bfloat16 passes), ``"highest"`` is the float32
reference itself. The loop mirrors what a Lloyd job returns: the centroids
after the last update and the assignment that update was made from; it
stops at ``max_iters`` or when the relative drop of the potential is at most
``tol`` after the second iteration.
"""
from __future__ import annotations

from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp

_PRECISION = {"bf16": jax.lax.Precision.HIGHEST,
              "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}


def _rows(k: int) -> int:
    return 1 << max(8, ((1 << 26) // k).bit_length() - 1)


_to_bf16 = jax.jit(lambda p: p.astype(jnp.bfloat16))


def kmeans(key, points, *, k, max_iters, tol=1e-6, precision="highest"):
    """(centroids (k, d) float32, assignment (n,) int32, Lloyd iterations
    run () int32). Under ``"bf16"``
    the points are rounded in a program of their own, so that the rounding
    is real: inside one program XLA may keep the float32 value of a
    float32 -> bfloat16 -> float32 round trip."""
    x = _to_bf16(points) if precision == "bf16" else points
    return _kmeans(key, x, k=k, max_iters=max_iters, tol=tol,
                   precision=precision)


@partial(jax.jit, static_argnames=("k", "max_iters", "tol", "precision"))
def _kmeans(key, x, *, k, max_iters, tol, precision):
    hp = _PRECISION[precision]
    xf = x.astype(jnp.float32)
    n, d = x.shape
    xn = jnp.sum(xf * xf, axis=1)

    def d2_one(c):
        dot = jnp.matmul(x, c.astype(x.dtype), precision=hp,
                         preferred_element_type=jnp.float32)
        return jnp.maximum(xn - 2.0 * dot + jnp.sum(c * c), 0.0)

    k0, key = jax.random.split(key)
    c0 = xf[jax.random.randint(k0, (), 0, n)]
    cents = jnp.zeros((k, d), jnp.float32).at[0].set(c0)

    def seed_round(j, carry):
        cents, md, key = carry
        key, sk = jax.random.split(key)
        i = jax.random.categorical(sk, jnp.log(md))
        c = xf[i]
        return cents.at[j].set(c), jnp.minimum(md, d2_one(c)), key

    cents, _, _ = jax.lax.fori_loop(1, k, seed_round,
                                    (cents, d2_one(c0), key))

    rows = min(_rows(k), 1 << max(8, (n - 1).bit_length()))
    pad = -n % rows
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, rows, d)
    xnb = jnp.pad(xn, (0, pad)).reshape(-1, rows)

    def assign(c):
        cn = jnp.sum(c * c, axis=1)
        cl = c.astype(x.dtype)

        def block(args):
            xs, ns = args
            dot = jnp.matmul(xs, cl.T, precision=hp,
                             preferred_element_type=jnp.float32)
            d2 = jnp.maximum(ns[:, None] - 2.0 * dot + cn[None, :], 0.0)
            return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, 1)

        a, md = jax.lax.map(block, (xb, xnb))
        return a.reshape(-1)[:n], md.reshape(-1)[:n]

    def cond(s):
        i, _, prev, cur, _ = s
        rel = (prev - cur) / jnp.maximum(prev, 1e-30)
        return (i < max_iters) & ((i < 2) | (rel > tol))

    def body(s):
        i, c, _, cur, _ = s
        a, md = assign(c)
        sums = jax.ops.segment_sum(xf, a, num_segments=k)
        cnt = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), a,
                                  num_segments=k)
        new = jnp.where(cnt[:, None] > 0, sums / jnp.maximum(cnt, 1)[:, None],
                        c)
        return i + 1, new, cur, jnp.sum(md), a

    iters, cents, _, _, a = jax.lax.while_loop(
        cond, body, (jnp.int32(0), cents, jnp.inf, jnp.inf,
                     jnp.zeros((n,), jnp.int32)))
    return cents, a, iters


def ivf_layout(points, centroids, assignment):
    """The label-sorted layout of an IVF index: rows grouped by list in a
    stable order, with each list's offset and size."""
    nlist = centroids.shape[0]
    perm = jnp.argsort(assignment, stable=True).astype(jnp.int32)
    labels = assignment[perm]
    counts = jnp.bincount(assignment, length=nlist).astype(jnp.int32)
    return SimpleNamespace(points=points[perm], perm=perm, labels=labels,
                           starts=jnp.cumsum(counts) - counts, counts=counts,
                           centroids=centroids)
