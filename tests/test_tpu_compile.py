"""TPU-compiler rehearsals of the main-path Pallas kernels at the widths the
chip smoke runs (``chip_smoke.py``): each kernel is lowered and compiled by
the TPU compiler for one chip of a described v5e, with no chip attached. A
kernel that interprets fine on the CPU can still be refused by Mosaic (an
unaligned block, a shape cast it cannot lay out, more VMEM or SMEM than a
core has) — this file catches that before any chip time is spent.

Widths: the paper's job (n=4,000,000, d=2, k=50), the IVF build's Lloyd
kernels (n=1,048,576, d=128, k=4096), one chip's shard of the four-chip
MNIST8m job (n=2,025,000, d=784, k=256; 784 is no multiple of 128 lanes)
and the IVF scan at Q=1,000 over that index (k=10, the build's 128-row
tiles). Tile heights come from the same pickers the engine and the index
builder use."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro.core import bounds
from repro.kernels import ops
from repro.kernels.ivf_scan import ivf_scan_pallas
from repro.kernels.kmeans_distance import (distance_min_update_gated_pallas,
                                           distance_min_update_pallas,
                                           seed_prologue_pallas)
from repro.kernels.lloyd_assign import (lloyd_assign_gated_pallas,
                                        lloyd_assign_tiled_pallas)

PAPER = (4_000_000, 2, 50)
IVF_BUILD = (1_048_576, 128, 4096)
MNIST8M = (8_100_000, 784, 256)      # the whole job, over four chips
MNIST8M_SHARD = (MNIST8M[0] // 4, 784, 256)
WIDTHS = dict(argvalues=[PAPER, IVF_BUILD, MNIST8M_SHARD],
              ids=["paper", "ivf", "mnist8m"])
IVF_QUERIES, IVF_K, IVF_BLOCK = 1_000, 10, 128


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 (four chips) — the compile target."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One chip of the described v5e."""
    return SingleDeviceSharding(topo.devices[0])


def _compile(chip, fn, shapes, *, width=None, rows=None, **static):
    """Compile ``fn`` for one chip on operands of ``shapes``, each in the
    chip's default layout, but for the MNIST8m shard's once-padded rows
    (``_stream``; ``rows`` is the prologue's keyword for them): those are
    row-major, as the engine's prologue copies them for the kernels."""
    def sds(shape, dt):
        if width != MNIST8M_SHARD or shape != _stream(width):
            return jax.ShapeDtypeStruct(shape, dt, sharding=chip)
        fmt = Format(Layout(major_to_minor=(0, 1)), chip)
        return jax.ShapeDtypeStruct(shape, dt, sharding=fmt)

    args = [sds(s, dt) for s, dt in shapes]
    if rows is not None:
        static["rows"] = sds(*rows)
    text = fn.lower(*args, interpret=False, **static).compile().as_text()
    assert "tpu_custom_call" in text


def _tile(n, d, k):
    """The engine's tile height for a kmeans call (`Backend.seed_tile`)."""
    return ops.choose_block_n(n, d, k, batched=True)


def _stream(width):
    """The (rows, d) operand the kernels stream: at the MNIST8m shard the
    engine's once-padded rows (the wrapper's own pad of a 7.26 GB shard
    beside it would not fit a chip); elsewhere the (n, d) points, which the
    wrapper pads."""
    n, d, k = width
    if width != MNIST8M_SHARD:
        return (n, d)
    bn = _tile(n, d, k)
    return (-(-n // bn) * bn, d)


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("width", **WIDTHS)
def test_prologue_compiles(chip, width):
    n, d, k = width
    _compile(chip, seed_prologue_pallas, [((n, d), F32)], width=width,
             rows=(_stream(width), F32) if width == MNIST8M_SHARD else None,
             block_n=_tile(n, d, k))


@pytest.mark.parametrize("width", **WIDTHS)
def test_round_compiles(chip, width):
    n, d, k = width
    _compile(chip, distance_min_update_pallas,
             [(_stream(width), F32), ((n,), F32), ((1, d), F32),
              ((n,), F32)],
             width=width, block_n=_tile(n, d, k), resident=True)


@pytest.mark.parametrize("width", **WIDTHS)
def test_round_gated_compiles(chip, width):
    n, d, k = width
    bn = _tile(n, d, k)
    t = -(-n // bn)
    _compile(chip, distance_min_update_gated_pallas,
             [(_stream(width), F32), ((n,), F32), ((1, d), F32),
              ((n,), F32), ((n,), F32), ((t,), F32), ((t,), F32),
              ((t,), F32), ((t,), F32), ((t,), I32), ((2,), I32)],
             width=width, block_n=bn, resident=True)


@pytest.mark.parametrize("width", **WIDTHS)
def test_assign_tiled_compiles(chip, width):
    n, d, k = width
    bn = _tile(n, d, k)
    _compile(chip, lloyd_assign_tiled_pallas,
             [(_stream(width), F32), ((n,), F32), ((k, d), F32)],
             width=width, block_n=bn,
             tps=bounds.tiles_per_super(-(-n // bn)))


@pytest.mark.parametrize("width", **WIDTHS)
def test_assign_gated_compiles(chip, width):
    n, d, k = width
    bn = _tile(n, d, k)
    t = -(-n // bn)
    tps = bounds.tiles_per_super(t)
    s = -(-t // tps)
    _compile(chip, lloyd_assign_gated_pallas,
             [(_stream(width), F32), ((n,), F32), ((k, d), F32),
              ((k,), F32),
              ((t,), F32), ((t,), F32), ((n,), I32), ((n,), F32),
              ((n,), F32), ((t,), F32), ((t,), F32), ((s, k, d), F32),
              ((s, k), F32), ((t,), I32), ((2,), I32)],
             width=width, block_n=bn, tps=tps)


def test_ivf_scan_compiles(chip):
    n, d, _ = IVF_BUILD
    t = -(-n // IVF_BLOCK)
    q = IVF_QUERIES
    _compile(chip, ivf_scan_pallas,
             [((q, d), F32), ((n, d), F32), ((n,), F32), ((t, d), F32),
              ((t,), F32), ((q, t), I32), ((q,), I32)],
             k=IVF_K, block_n=IVF_BLOCK, gate=True)


def test_mesh_fit_compiles_on_four_chips(topo, monkeypatch):
    """The mesh backend over the Pallas local backend, rows sharded over the
    four described chips at the four-chip smoke width (n=16,777,216, d=2,
    k=50): the kernels must compile inside shard_map, whose output types
    carry the operands' varying mesh axes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import engine

    # the engine picks interpret mode from this process's (CPU) backend;
    # the described chips need the compiled kernels
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    mesh = Mesh(topo.devices, ("data",))
    backend = engine.make_backend("mesh", mesh=mesh, local="pallas")
    pts = jax.ShapeDtypeStruct((16_777_216, 2), F32,
                               sharding=NamedSharding(mesh, P("data")))
    init = jax.ShapeDtypeStruct((50, 2), F32,
                                sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        lowered = engine._fit_jit.lower(
            pts, init, None, backend=backend, max_iters=25, tol=1e-6,
            empty="keep", precision="fp32", bound_gate=True, guard=True)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("row_major", [False, True],
                         ids=["mnist8m", "mnist8m-row-major"])
def test_mesh_kmeans_compiles_on_four_chips(topo, monkeypatch, row_major):
    """The one-program mesh ``kmeans`` (seeding and Lloyd in one
    ``shard_map``) over the Pallas local backend, rows sharded over the
    four described chips at MNIST8m's size: it compiles with the kernels,
    and one chip's arguments and temporaries fit its 16 GiB. The rows come
    in the chip's default layout, as ``device_put`` makes them, which for
    a (2,025,000, 784) shard is column-major, or row-major: either way
    each chip holds its shard and one padded row-major copy, and no whole
    relayout of the shard (a plain pad of the column-major shard needs
    19.45 GB)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import engine

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    n, d, k = MNIST8M
    mesh = Mesh(topo.devices, ("data",))
    backend = engine.make_backend("mesh", mesh=mesh, local="pallas")
    rows = NamedSharding(mesh, P("data"))
    if row_major:
        rows = Format(Layout(major_to_minor=(0, 1)), rows)
    pts = jax.ShapeDtypeStruct((n, d), F32, sharding=rows)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        compiled = engine._kmeans_jit.lower(
            key, pts, None, k=k, backend=backend, sampler="cdf",
            max_iters=20, tol=1e-6, empty="keep", precision="fp32",
            bound_gate=True, refresh_block=8, guard=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    per_chip = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert per_chip < 16 * 2 ** 30, per_chip
