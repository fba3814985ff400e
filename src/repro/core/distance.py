"""The per-tile distance math the fused backend and the Pallas kernels share.

One point tile is scored against a centroid block in the matmul form
``||x||^2 - 2 x.c + ||c||^2`` with the cached fp32 ``||x||^2``: the fused
(XLA) backend maps these functions over tiles, and the kernels in
``repro.kernels`` call them on their VMEM blocks, which is what makes the
fused and Pallas results bitwise comparable on one compiler.

The layout is the kernels' (see ``kernels.kmeans_distance``): a tile's
distances are ``(k, block_n)`` — centroids on sublanes, points on lanes —
so every per-point reduction over centroids yields a ``(1, block_n)`` row.
A point tile comes as ``(block_n, d)`` rows or, in the small-d seeding
rounds, coordinate-major as ``(d, block_n)`` (`tile_d2_min_cols`).
The dots run at ``Precision.HIGHEST``: the TPU's default fp32 matmul rounds
its operands to bf16, whose error the cancellation in the matmul form would
amplify into wrong assignments.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def tile_d2(x_raw, c_raw, xn, *, cols: bool = False):
    """(k, block_n) matmul-form D^2 of one point tile.

    ``x_raw`` (block_n, d) / ``c_raw`` (k, d) keep their input dtype into the
    MXU (bf16 streams at half width) with fp32 accumulation; ``xn`` is the
    cached fp32 ``||x||^2`` row (1, block_n). ``cols=True`` takes the tile
    coordinate-major, ``x_raw`` (d, block_n) with the points on lanes: the
    dot then contracts d on the sublane axis."""
    cf = c_raw.astype(jnp.float32)
    cn = jnp.sum(cf * cf, axis=1, keepdims=True)        # (k, 1)
    dots = jax.lax.dot_general(c_raw, x_raw,
                               (((1,), (0 if cols else 1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=HIGHEST)       # (k, block_n)
    return jnp.maximum(xn - 2.0 * dots + cn, 0.0)


def tile_d2_min(x_raw, c_raw, xn):
    """(1, block_n) min over the centroid block of `tile_d2` (the seeding
    round's fold)."""
    return jnp.min(tile_d2(x_raw, c_raw, xn), axis=0, keepdims=True)


def tile_d2_min_cols(xt_raw, c_raw, xn):
    """`tile_d2_min` of a coordinate-major tile ``xt_raw`` (d, block_n): the
    same formula, precision and work; only the operand's layout differs (the
    small-d seeding rounds stream the points this way, see
    ``kernels.kmeans_distance``)."""
    return jnp.min(tile_d2(xt_raw, c_raw, xn, cols=True), axis=0,
                   keepdims=True)


def nearest(d2):
    """(k, block_n) distances -> (labels (1, block_n) int32, min (1, block_n)):
    ``jnp.argmin``/``jnp.min`` over the centroid axis, written with the
    reductions Mosaic lowers. The label is the FIRST index attaining the min
    (the first NaN when a column holds one, as ``jnp.argmin``), which every
    assignment path shares for deterministic ties."""
    k = d2.shape[0]
    m = jnp.min(d2, axis=0, keepdims=True)
    hit = (d2 == m) | (d2 != d2)
    slot = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    return jnp.min(jnp.where(hit, slot, k), axis=0, keepdims=True), m
