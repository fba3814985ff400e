"""PQ flash-decode (Pallas TPU): decode attention over a PRODUCT-QUANTIZED
KV cache — the paper's k-means++ applied to the serving hot path.

Long-context decode is HBM-bound on KV-cache streaming (roofline §C:
codeqwen decode_32k reads a 2.2 TB bf16 cache per step). serve/kvquant.py
builds k-means++-seeded codebooks; this kernel computes attention DIRECTLY
over the uint8 codes, so HBM traffic per step is

    codes:  S * KH * n_sub        bytes   (vs  S * KH * hd * 2  for bf16)
    + the codebooks (n_sub, 256, dsub) — VMEM-RESIDENT across the whole
      grid: the paper's constant-memory insight a third time.

Reconstruction inside VMEM uses one-hot matmuls (codes -> one-hot(256) ->
@ codebook, one sub-space at a time, codes on the lanes), the TPU-idiomatic
replacement for a gather: the MXU does the lookup. head_dim 128 / n_sub 16
=> 16x less cache traffic.

Layout: grid (B, KH, nk); VMEM scratch carries (m, l, acc) for the G query
heads of one kv head across kv blocks (sequential innermost grid dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _reconstruct_t(codes, cb_t):
    """codes (n_sub, block_k) uint8 + cb_t (n_sub, hd, 256) -> (hd, block_k),
    the block's rows transposed. One one-hot matmul per sub-space (MXU
    lookup, no gather): ``cb_t[s]`` holds sub-space s's codebook transposed
    into rows ``s*dsub:(s+1)*dsub`` and zeros elsewhere, so each output
    element sums one codebook entry with zeros — an exact lookup."""
    n_sub, block_k = codes.shape
    code_iota = jax.lax.broadcasted_iota(jnp.int32, (cb_t.shape[2], 1), 0)
    out = jnp.zeros((cb_t.shape[1], block_k), jnp.float32)
    for s in range(n_sub):
        onehot = (code_iota == codes[s:s + 1, :].astype(jnp.int32))
        out = out + jax.lax.dot_general(
            cb_t[s], onehot.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    return out


def _kernel(len_ref, q_ref, kc_ref, vc_ref, kcb_ref, vcb_ref, o_ref,
            m_scr, l_scr, acc_scr, *, block_k: int, scale: float):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cache_len = len_ref[0]
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    mask = k_pos < cache_len                                # (1, block_k)

    @pl.when(jnp.any(mask))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (G, hd)
        k_t = _reconstruct_t(kc_ref[0, 0], kcb_ref[0])      # (hd, bk)
        v_t = _reconstruct_t(vc_ref[0, 0], vcb_ref[0])
        s = jax.lax.dot_general(q, k_t, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, _NEG_INF)                    # (G, bk)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _codebook_t(cb: jax.Array) -> jax.Array:
    """(KH, n_sub, 256, dsub) codebooks -> (KH, n_sub, hd, 256): sub-space
    s transposed into rows ``s*dsub:(s+1)*dsub``, zeros elsewhere."""
    kh, n_sub, n_codes, dsub = cb.shape
    hd = n_sub * dsub
    col = jnp.arange(hd)
    rep = cb[..., col % dsub]                            # (KH, n_sub, 256, hd)
    own = (col // dsub)[None, :] == jnp.arange(n_sub)[:, None]
    return jnp.where(own[None, :, None, :], rep, 0.0).transpose(0, 1, 3, 2)


@functools.partial(jax.jit,
                   static_argnames=("block_k", "interpret"))
def pq_decode_attention(q: jax.Array, k_codes: jax.Array, v_codes: jax.Array,
                        k_cb: jax.Array, v_cb: jax.Array,
                        cache_len: jax.Array, *, block_k: int = 512,
                        interpret: bool | None = None) -> jax.Array:
    """Single-token decode attention over PQ codes.

    q        (B, 1, H, hd)       — current query
    k_codes  (B, S, KH, n_sub) uint8 ; v_codes same
    k_cb     (KH, n_sub, 256, dsub)  ; v_cb same (per-kv-head codebooks)
    cache_len () int32           — valid positions
    Returns (B, 1, H, hd).
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    B, _, H, hd = q.shape
    S, KH = k_codes.shape[1], k_codes.shape[2]
    n_sub = k_codes.shape[3]
    G = H // KH
    scale = hd ** -0.5
    pad = (-S) % block_k
    # codes ride the lanes: (B, KH, n_sub, S)
    kc = jnp.pad(k_codes, ((0, 0), (0, pad), (0, 0), (0, 0))) \
        .transpose(0, 2, 3, 1)
    vc = jnp.pad(v_codes, ((0, 0), (0, pad), (0, 0), (0, 0))) \
        .transpose(0, 2, 3, 1)
    qh = q.reshape(B, 1, KH, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KH, G, hd)
    nk = kc.shape[3] // block_k
    len_arr = jnp.asarray([cache_len], jnp.int32)
    n_codes = k_cb.shape[2]

    out = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, scale=scale),
        grid=(B, KH, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, ik: (0,)),
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, n_sub, block_k),
                         lambda b, h, ik: (b, h, 0, ik)),
            pl.BlockSpec((1, 1, n_sub, block_k),
                         lambda b, h, ik: (b, h, 0, ik)),
            # codebooks: VMEM-RESIDENT across the grid (constant-memory
            # analogue — index_map pins the block)
            pl.BlockSpec((1, n_sub, hd, n_codes),
                         lambda b, h, ik: (h, 0, 0, 0)),
            pl.BlockSpec((1, n_sub, hd, n_codes),
                         lambda b, h, ik: (h, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(len_arr, qh, kc, vc, _codebook_t(k_cb.astype(jnp.float32)),
      _codebook_t(v_cb.astype(jnp.float32)))
    return out.reshape(B, 1, H, hd)      # (B, KH, G, hd): H = kh*G + g


def hbm_bytes_model(B: int, S: int, KH: int, hd: int, n_sub: int) -> dict:
    """Per-step cache traffic: PQ codes vs bf16 KV (for §Perf C)."""
    bf16 = 2 * B * S * KH * hd * 2
    pq = 2 * B * S * KH * n_sub + 2 * KH * n_sub * 256 * (hd // n_sub) * 4
    return {"bf16_cache_bytes": bf16, "pq_bytes": pq,
            "compression": bf16 / pq}
