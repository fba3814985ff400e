import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes with ShapeDtypeStruct inputs (nothing allocated), and
record memory_analysis / cost_analysis / the collective schedule to
artifacts/dryrun/<arch>_<shape>_<mesh>.json for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh pod      # single-pod only
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.configs.registry import ARCH_NAMES, get_config, get_shape, supported_shapes
from repro.launch.mesh import make_production_mesh
from repro.launch.step import jitted_cell
from repro.models.sharding import use_mesh
from repro.roofline.hlo import analyze

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _parse_val(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    if v in ("true", "false", "True", "False"):
        return v.lower() == "true"
    return v


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             save: bool = True, verbose: bool = True,
             overrides: dict | None = None, tag: str = "") -> dict:
    import dataclasses
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    t0 = time.time()
    with use_mesh(mesh):
        jf, args = jitted_cell(cfg, shape, mesh)
        lowered = jf.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    mem_d = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            mem_d[k] = int(v)
    cost_d = {k: float(v) for k, v in (cost or {}).items()
              if isinstance(v, (int, float))}

    hlo = compiled.as_text()
    an = analyze(hlo, n_devices=int(mesh.devices.size))
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_devices": int(mesh.devices.size),
        "kind": shape.kind, "tag": tag, "overrides": overrides or {},
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "memory_analysis": mem_d,
        "cost_analysis": cost_d,            # XLA raw (scan bodies counted 1x)
        "hlo_flops_per_device": an["flops"],        # scan-aware (ours)
        "hlo_bytes_per_device": an["bytes"],
        "collectives": an["collectives"],
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"flops/dev={an['flops']:.3e} "
              f"bytes/dev={an['bytes']:.3e} "
              f"coll/dev={an['collectives']['total_bytes']:.3e}B "
              f"temp_mem/dev={mem_d.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        print("  memory_analysis:", mem_d)
    if save:
        ART_DIR.mkdir(parents=True, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        out = ART_DIR / (f"{arch.replace('/', '_')}_{shape_name}"
                         f"_{mesh_name}{suffix}.json")
        out.write_text(json.dumps(rec, indent=1))
    return rec


def all_cells():
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for shape_name in supported_shapes(cfg):
            yield arch, shape_name


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="ArchConfig override, e.g. --set moe_dispatch=a2a")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    args = ap.parse_args()
    overrides = {kv.split("=", 1)[0]: _parse_val(kv.split("=", 1)[1])
                 for kv in args.set}

    meshes = {"pod": ["pod"], "multipod": ["multipod"],
              "both": ["pod", "multipod"]}[args.mesh]
    cells = [(a, s) for a, s in all_cells()
             if (args.arch in (None, a)) and (args.shape in (None, s))]
    failures = []
    for arch, shape_name in cells:
        for mesh_name in meshes:
            out = ART_DIR / f"{arch}_{shape_name}_{mesh_name}.json"
            if args.skip_existing and out.exists():
                print(f"[dryrun] skip existing {out.name}")
                continue
            try:
                run_cell(arch, shape_name, mesh_name,
                         overrides=overrides, tag=args.tag)
            except Exception as e:
                failures.append((arch, shape_name, mesh_name, repr(e)))
                print(f"[dryrun] FAIL {arch} x {shape_name} x {mesh_name}: {e}")
                traceback.print_exc()
    print(f"[dryrun] done: {len(cells) * len(meshes) - len(failures)} ok, "
          f"{len(failures)} failed")
    for f in failures:
        print("  FAILED:", *f)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
