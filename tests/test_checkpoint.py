"""Checkpoint manager: atomic commit, async save, GC, dtype fidelity."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 4)),
                   "b16": jax.random.normal(k, (4,), jnp.bfloat16)},
        "opt": {"m": jnp.zeros((8, 4)), "step": jnp.asarray(7, jnp.int32)},
        "rng": jax.random.PRNGKey(3),
    }


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=False)
    state = _state()
    mgr.save(5, state)
    step, got = mgr.restore(jax.tree.map(jnp.zeros_like, state))
    assert step == 5
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(state)[0],
            jax.tree_util.tree_flatten_with_path(got)[0]):
        assert a.dtype == b.dtype, pa
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_async_save_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=True)
    s = _state()
    mgr.save(1, s)
    mgr.save(2, s)
    mgr.wait()
    assert mgr.latest_step() == 2


def test_gc_keeps_newest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_save=False)
    s = _state()
    for step in (1, 2, 3, 4):
        mgr.save(step, s)
    assert mgr.all_steps() == [3, 4]


def test_no_partial_checkpoint_visible(tmp_path):
    """tmp dirs are never listed as restorable steps."""
    mgr = CheckpointManager(tmp_path, async_save=False)
    (tmp_path / "step_00000009.tmp").mkdir()
    s = _state()
    mgr.save(1, s)
    assert mgr.all_steps() == [1]


def test_restore_specific_step(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=0, async_save=False)
    s1, s2 = _state(1), _state(2)
    mgr.save(1, s1)
    mgr.save(2, s2)
    _, got = mgr.restore(jax.tree.map(jnp.zeros_like, s1), step=1)
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                  np.asarray(s1["params"]["w"]))


def test_missing_checkpoint_raises(tmp_path):
    mgr = CheckpointManager(tmp_path)
    with pytest.raises(FileNotFoundError):
        mgr.restore({"x": jnp.zeros(())})


# ---------------------------------------------------------------------------
# manifest meta + bound-state geometry stamps (ISSUE 7: checkpointed bound
# state must never restore onto a mismatched shard/tile geometry)
# ---------------------------------------------------------------------------


def test_manifest_meta_roundtrip(tmp_path):
    from repro.checkpoint.manager import CheckpointManager as M
    mgr = M(tmp_path, async_save=False)
    mgr.save(3, _state(), meta={"kind": "seed", "k": 7})
    man = mgr.read_manifest(3)
    assert man["meta"] == {"kind": "seed", "k": 7}
    assert man["step"] == 3 and "shapes" in man
    # meta-less saves stay readable (back-compat)
    mgr.save(4, _state())
    assert mgr.read_manifest(4).get("meta") is None
    assert mgr.read_manifest()["step"] == 4          # default: latest


def _bound_state(n_tiles, seed=0):
    import jax.numpy as jnp
    from repro.core.bounds import BoundState
    k = jax.random.PRNGKey(seed)
    return BoundState(jax.random.uniform(k, (n_tiles,)),
                      jax.random.uniform(jax.random.fold_in(k, 1),
                                         (n_tiles,)) + 1.0)


@pytest.mark.parametrize("shards", [8, 4, 1])
def test_bound_state_same_geometry_roundtrips_bitwise(tmp_path, shards):
    from repro.checkpoint import restore_bound_state, save_bound_state
    st = _bound_state(128 // max(shards, 1))
    save_bound_state(tmp_path, 1, st, shards=shards, tile=128)
    got = restore_bound_state(tmp_path, jax.tree.map(jnp.zeros_like, st),
                              shards=shards, tile=128)
    assert got is not None
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bound_state_reshard_invalidates(tmp_path):
    """8 -> 4 -> 1 shards: the shard-local tile layout no longer matches, so
    restore returns None and the caller rebuilds with one ungated round —
    never a silently-interleaved (wrong) bound state."""
    from repro.checkpoint import restore_bound_state, save_bound_state
    st = _bound_state(16)
    save_bound_state(tmp_path, 1, st, shards=8, tile=128)
    like = jax.tree.map(jnp.zeros_like, st)
    for shards in (4, 1):
        assert restore_bound_state(tmp_path, like, shards=shards,
                                   tile=128) is None
    # a tile-height change invalidates the same way
    assert restore_bound_state(tmp_path, like, shards=8, tile=256) is None


def test_bound_state_restore_errors_are_typed(tmp_path):
    from repro.checkpoint import restore_bound_state, save_bound_state
    from repro.core.guards import CheckpointError, ClusteringError
    st = _bound_state(8)
    like = jax.tree.map(jnp.zeros_like, st)
    with pytest.raises(CheckpointError, match="no bound-state checkpoint"):
        restore_bound_state(tmp_path / "empty", like, shards=1, tile=128)
    # a foreign (non-bound-state) checkpoint is refused, not misread
    from repro.checkpoint.manager import CheckpointManager as M
    M(tmp_path, async_save=False).save(1, _state(), meta={"kind": "train"})
    with pytest.raises(CheckpointError, match="not a bound-state"):
        restore_bound_state(tmp_path, like, shards=1, tile=128)
    assert issubclass(CheckpointError, ClusteringError)


# ---------------------------------------------------------------------------
# engine-level checkpointed seeding: chunked driver == one-shot, resume
# bitwise, meta compatibility enforced
# ---------------------------------------------------------------------------


def _seed_problem(n=4096):
    from repro.data.synthetic import blobs
    pts = jnp.asarray(blobs(n, 2, 6, seed=3, spread=0.05)[0])
    return pts, jax.random.PRNGKey(4)


# the pallas case's n is not a tile multiple: the chunked driver's jitted
# prologue pads the points once, like the one-shot seed's
@pytest.mark.parametrize("backend,n", [("fused", 4096), ("pallas", 4096 + 77)])
def test_checkpointed_seed_matches_plain_and_resumes(tmp_path, backend, n):
    import shutil
    from repro.core.engine import ClusterEngine
    from repro.checkpoint.manager import CheckpointManager as M
    pts, key = _seed_problem(n)
    eng = ClusterEngine(backend)
    plain = eng.seed(key, pts, 6)
    ck = eng.seed(key, pts, 6, checkpoint_dir=tmp_path, checkpoint_every=2)
    np.testing.assert_array_equal(np.asarray(plain.centroids),
                                  np.asarray(ck.centroids))
    np.testing.assert_array_equal(np.asarray(plain.indices),
                                  np.asarray(ck.indices))
    np.testing.assert_array_equal(np.asarray(plain.min_d2),
                                  np.asarray(ck.min_d2))
    mgr = M(tmp_path)
    assert mgr.latest_step() == 6
    assert mgr.read_manifest()["meta"]["kind"] == "seed"
    # crash simulation: drop the newest checkpoints, rerun -> bitwise
    for step in mgr.all_steps()[-2:]:
        shutil.rmtree(tmp_path / f"step_{step:08d}")
    res = eng.seed(key, pts, 6, checkpoint_dir=tmp_path, checkpoint_every=2)
    np.testing.assert_array_equal(np.asarray(plain.centroids),
                                  np.asarray(res.centroids))
    np.testing.assert_array_equal(np.asarray(plain.min_d2),
                                  np.asarray(res.min_d2))


def test_checkpointed_seed_refuses_mismatched_run(tmp_path):
    from repro.core.engine import ClusterEngine
    from repro.core.guards import CheckpointError
    pts, key = _seed_problem()
    eng = ClusterEngine("fused")
    eng.seed(key, pts, 6, checkpoint_dir=tmp_path, checkpoint_every=2)
    with pytest.raises(CheckpointError, match="meta"):
        eng.seed(key, pts, 5, checkpoint_dir=tmp_path, checkpoint_every=2)


def test_checkpointed_seed_rejects_unsupported_modes(tmp_path):
    from repro.core.engine import ClusterEngine, MeshBackend
    from repro.core.guards import CheckpointError
    pts, key = _seed_problem()
    with pytest.raises(CheckpointError, match="rejection"):
        ClusterEngine("fused").seed(key, pts, 6, sampler="rejection",
                                    checkpoint_dir=tmp_path)
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(CheckpointError, match="local"):
        ClusterEngine(MeshBackend(mesh=mesh, axes=("data",))).seed(
            key, pts, 6, checkpoint_dir=tmp_path)
