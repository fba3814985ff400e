"""Mixture-of-Experts FFN with capacity-based top-k dispatch (GShard-style,
static shapes — pjit/EP friendly) + k-means++ router initialization (the
paper's technique as a first-class training feature).

Dispatch is gather/scatter by expert slot (not the (S, E, C) one-hot einsum,
whose mask alone is O(S*E*C) memory): a cumsum over the top-k one-hot gives
each token its position-in-expert; tokens beyond capacity are dropped
(standard GShard behaviour, capacity_factor controls the slack).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.common import ArchConfig
from repro.models.layers import dense_init, mlp_init, mlp_apply


def moe_init(key, cfg: ArchConfig):
    d = cfg.d_model
    fe = cfg.moe_d_ff or cfg.d_ff
    E = cfg.n_experts
    Ep = cfg.padded_experts          # sharding-friendly (pads get no tokens)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E)),
        "experts_wi": dense_init(ks[1], (Ep, d, fe)),
        "experts_wg": dense_init(ks[2], (Ep, d, fe)),
        "experts_wo": dense_init(ks[3], (Ep, fe, d),
                                 scale=1.0 / (2 * max(cfg.n_layers, 1)) ** 0.5),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, fe * cfg.n_shared_experts,
                               cfg.n_layers)
    return p


def _capacity(cfg: ArchConfig, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * n_tokens * cfg.n_experts_per_tok
            / cfg.n_experts)
    return max(8, ((c + 7) // 8) * 8)          # pad to 8 for TPU lanes


def moe_apply(p, x, cfg: ArchConfig):
    """x (B, S, d) -> (y (B, S, d), aux with load-balance stats).

    Dispatch is CHUNKED along the SEQUENCE dim (cfg.moe_chunk ~= tokens per
    chunk): each chunk is dispatched and combined independently, so the
    cross-shard token gather GSPMD emits for expert parallelism is bounded by
    chunk*d bytes instead of the whole batch (1M tokens x 4k d_model would
    otherwise all-gather GBs per layer). Chunking along S — NOT along the
    flattened token dim — keeps the lax.map axis unsharded while the batch
    dim stays data-parallel inside every chunk (a scan over a sharded dim
    would make GSPMD replicate the expert compute on every data shard).
    Capacity is per-chunk — the standard grouped-dispatch approximation."""
    B, S, d = x.shape
    target = max((cfg.moe_chunk // max(B, 1)) if cfg.moe_chunk else S, 1)
    chunk_s = S
    if target < S:  # largest divisor of S that is <= target
        for c in range(min(target, S), 0, -1):
            if S % c == 0:
                chunk_s = c
                break
    nc = S // chunk_s
    if nc == 1:
        y, aux = _moe_chunk(p, x.reshape(B * S, d), cfg)
    else:
        xs = x.reshape(B, nc, chunk_s, d).swapaxes(0, 1)   # (nc, B, cs, d)
        ys, auxs = jax.lax.map(
            lambda xc: _moe_chunk(p, xc.reshape(B * chunk_s, d), cfg), xs)
        y = ys.reshape(nc, B, chunk_s, d).swapaxes(0, 1)
        aux = jax.tree.map(jnp.mean, auxs)
    return y.reshape(B, S, d), aux


def _route(p, xf, cfg: ArchConfig, C: int):
    """Top-k routing + slotting for a token block xf (n, d).

    Returns (gate_vals (n,K) f32, keep (n,K) bool, slot_e, slot_c (n*K,),
    slot_src (Ep, C) int32, probs, gate_idx). The router matmul is fp32
    (standard practice): top-k sits on a decision boundary, bf16 reduction
    noise flips experts between batched-forward and single-token-decode paths.
    """
    n, _ = xf.shape
    E, K = cfg.n_experts, cfg.n_experts_per_tok
    Ep = cfg.padded_experts
    logits = xf.astype(jnp.float32) @ p["router"]                 # (n, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)                 # (n, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each (token, k) inside its expert queue (gate_idx < E <= Ep,
    # so pad experts never receive a token)
    onehot = jax.nn.one_hot(gate_idx, Ep, dtype=jnp.int32)        # (n, K, Ep)
    flat = onehot.reshape(n * K, Ep)
    pos = jnp.cumsum(flat, axis=0) - flat                         # exclusive
    pos_in_e = jnp.sum(pos * flat, axis=-1).reshape(n, K)
    keep = pos_in_e < C

    slot_e = gate_idx.reshape(-1)                                  # (n*K,)
    slot_c = jnp.where(keep.reshape(-1), pos_in_e.reshape(-1), C)  # C = drop
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32), K)
    slot_src = jnp.full((Ep, C + 1), n, jnp.int32)                 # n = pad row
    slot_src = slot_src.at[slot_e, slot_c].set(src)[:, :C]         # (Ep, C)
    return gate_vals, keep, slot_e, slot_c, slot_src, probs, gate_idx


def _experts_ffn(expert_in, wi, wg, wo, dt):
    h = jnp.einsum("ecd,edf->ecf", expert_in, wi.astype(dt))
    g = jnp.einsum("ecd,edf->ecf", expert_in, wg.astype(dt))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * h
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))


def _combine(expert_out, gate_vals, keep, slot_e, slot_c, n, C, dt):
    Ep = expert_out.shape[0]
    d = expert_out.shape[-1]
    out_pad = jnp.concatenate(
        [expert_out.reshape(Ep * C, d), jnp.zeros((1, d), dt)], axis=0)
    flat_slot = jnp.where(keep.reshape(-1),
                          slot_e * C + slot_c, Ep * C)             # (n*K,)
    K = gate_vals.shape[1]
    per_k = out_pad[flat_slot].reshape(n, K, d)
    return jnp.sum(per_k * gate_vals[..., None].astype(dt), axis=1)


def _aux_stats(cfg, probs, gate_idx, keep, *, psum_axes=None):
    """Switch-style load balance. lb = E * sum(me * ce) is NONLINEAR in the
    per-token means, so under shard_map `me`/`ce` are psum-averaged across
    shards BEFORE the product — bitwise-matching the global (gather) stats."""
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    if psum_axes is not None:
        n_dev = jax.lax.psum(1, psum_axes)
        me = jax.lax.psum(me, psum_axes) / n_dev
        ce = jax.lax.psum(ce, psum_axes) / n_dev
        dropped = jax.lax.psum(dropped, psum_axes) / n_dev
    return {"lb_loss": E * jnp.sum(me * ce), "dropped_frac": dropped}


def _moe_chunk(p, xf, cfg: ArchConfig):
    """One dispatch chunk: xf (n, d) -> (y (n, d), aux)."""
    from repro.models.sharding import current_mesh

    mesh = current_mesh()
    use_a2a = cfg.moe_dispatch == "a2a" and mesh is not None
    if use_a2a:
        n_dev = 1
        for s in mesh.shape.values():
            n_dev *= s
        use_a2a = xf.shape[0] % n_dev == 0   # decode batches < devices: gather
    if use_a2a:
        y, aux = _moe_chunk_a2a(p, xf, cfg)
    else:
        y, aux = _moe_chunk_gather(p, xf, cfg)
    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], xf)
    return y, aux


def _moe_chunk_gather(p, xf, cfg: ArchConfig):
    """GSPMD gather-based dispatch (baseline). The compiler all-gathers the
    chunk's tokens over the data axes to build the expert buffers — simple
    and correct, but moves every token to every device (§Perf hillclimb A
    replaces this with the a2a path below)."""
    from repro.models.sharding import constrain

    n, d = xf.shape
    dt = cfg.compute_dtype
    C = _capacity(cfg, n)
    gate_vals, keep, slot_e, slot_c, slot_src, probs, gate_idx = \
        _route(p, xf, cfg, C)

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), dt)], axis=0)
    expert_in = xpad[slot_src]                                     # (Ep, C, d)
    # EP anchor: experts over "model", capacity slots over the batch axes —
    # without the second axis every data shard would redundantly compute ALL
    # of each expert's slots (16x wasted FLOPs on a 16x16 mesh).
    expert_in = constrain(expert_in, "model", "batch", None)
    expert_out = _experts_ffn(expert_in, p["experts_wi"], p["experts_wg"],
                              p["experts_wo"], dt)
    y = _combine(expert_out, gate_vals, keep, slot_e, slot_c, n, C, dt)
    return y, _aux_stats(cfg, probs, gate_idx, keep)


def _moe_chunk_a2a(p, xf, cfg: ArchConfig):
    """shard_map all-to-all dispatch (§Perf hillclimb A).

    Tokens stay on their home shard; each device routes its n/devices tokens
    locally, builds an (Ep, C_loc, d) buffer, and ONE all_to_all over the
    model axis delivers each expert's slots to the device holding that
    expert's weights (a second a2a returns the outputs). Wire per device per
    chunk = 2 * Ep*C_loc*d*2B ~= 2 * (K * capacity_factor) * token bytes —
    vs the gather baseline's all-gather of ALL tokens to ALL devices plus a
    model-axis gather of every expert buffer (measured ~10x more).
    """
    from repro.launch.mesh import batch_axes
    from repro.models.sharding import current_mesh
    from jax.sharding import PartitionSpec as P

    mesh = current_mesh()
    n, d = xf.shape
    dt = cfg.compute_dtype
    tok_axes = tuple(batch_axes(mesh)) + ("model",)
    n_dev = 1
    for a in tok_axes:
        n_dev *= mesh.shape[a]
    model_n = mesh.shape["model"]
    Ep = cfg.padded_experts
    assert n % n_dev == 0, (n, n_dev)
    n_loc = n // n_dev
    C_loc = _capacity(cfg, n_loc)

    def local_fn(xf_loc, router, wi, wg, wo):
        gate_vals, keep, slot_e, slot_c, slot_src, probs, gate_idx = \
            _route({"router": router}, xf_loc, cfg, C_loc)
        xpad = jnp.concatenate([xf_loc, jnp.zeros((1, d), dt)], axis=0)
        expert_in = xpad[slot_src]                         # (Ep, C_loc, d)
        # deliver slots to the expert owners: (Ep/m, m*C_loc, d) per device
        expert_in = jax.lax.all_to_all(expert_in, "model", split_axis=0,
                                       concat_axis=1, tiled=True)
        expert_out = _experts_ffn(expert_in, wi, wg, wo, dt)
        # return outputs to the token owners
        expert_out = jax.lax.all_to_all(expert_out, "model", split_axis=1,
                                        concat_axis=0, tiled=True)
        y = _combine(expert_out, gate_vals, keep, slot_e, slot_c,
                     n_loc, C_loc, dt)
        aux = _aux_stats(cfg, probs, gate_idx, keep, psum_axes=tok_axes)
        return y, aux

    mapped = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(tok_axes), P(), P("model"), P("model"), P("model")),
        out_specs=(P(tok_axes), P()))
    return mapped(xf, p["router"], p["experts_wi"], p["experts_wg"],
                  p["experts_wo"])


def kmeans_router_init(key, p_moe, token_embeds, cfg: ArchConfig, *,
                       variant: str = "fused"):
    """Initialize router weights from k-means++ centroids of token embeddings
    (paper integration #2): router logit_e = x . c_e gives balanced early
    routing. token_embeds (N, d) — typically one batch of embedded tokens."""
    from repro.core import kmeanspp
    res = kmeanspp(key, token_embeds.astype(jnp.float32), cfg.n_experts,
                   variant=variant)
    cents = res.centroids / (jnp.linalg.norm(res.centroids, axis=1,
                                             keepdims=True) + 1e-6)
    return {**p_moe, "router": cents.T.astype(jnp.float32)}
