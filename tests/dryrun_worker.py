"""Worker for test_dryrun_small.py: exercises the jitted_cell + analyzer
machinery on an 8-device mesh with SMOKE configs (subprocess — device count
is locked at jax init)."""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import dataclasses
import json
import sys

from repro.configs.common import ShapeConfig
from repro.configs.registry import get_config
from repro.launch.mesh import make_test_mesh
from repro.launch.step import jitted_cell
from repro.models.sharding import use_mesh
from repro.roofline.hlo import analyze

out = {}
mesh = make_test_mesh()
TINY_TRAIN = ShapeConfig("t", 128, 8, "train")
TINY_DECODE = ShapeConfig("d", 256, 8, "decode")


def compile_cell(cfg, shape):
    with use_mesh(mesh):
        jf, args = jitted_cell(cfg, shape, mesh)
        return jf.lower(*args).compile()


# 1. dense train cell: compiles, analyzer sees flops + collectives,
#    scan trip count (2 layers) is applied
cfg = get_config("deepseek-7b", smoke=True)
compiled = compile_cell(cfg, TINY_TRAIN)
r = analyze(compiled.as_text())
out["train_flops_positive"] = r["flops"] > 1e6
out["train_has_allreduce"] = r["collectives"]["by_kind"].get("all-reduce", 0) > 0
out["mem_analysis_present"] = compiled.memory_analysis() is not None
out["cost_analysis_present"] = "flops" in compiled.cost_analysis()

# 2. MoE a2a variant compiles and has all-to-all in the schedule
cfg_moe = dataclasses.replace(get_config("qwen2-moe-a2.7b", smoke=True),
                              moe_dispatch="a2a", moe_chunk=0)
compiled2 = compile_cell(cfg_moe, TINY_TRAIN)
r2 = analyze(compiled2.as_text())
out["a2a_in_schedule"] = r2["collectives"]["by_kind"].get("all-to-all", 0) > 0

# 3. gather baseline moves MORE collective bytes than a2a (the hillclimb)
cfg_g = dataclasses.replace(get_config("qwen2-moe-a2.7b", smoke=True),
                            moe_dispatch="gather")
r3 = analyze(compile_cell(cfg_g, TINY_TRAIN).as_text())
out["a2a_less_wire"] = (r2["collectives"]["total_bytes"]
                        < r3["collectives"]["total_bytes"])
out["a2a_bytes"] = r2["collectives"]["total_bytes"]
out["gather_bytes"] = r3["collectives"]["total_bytes"]

# 4. decode cell with bf16 serving params: argument bytes halve vs fp32
cfg_d = get_config("deepseek-7b", smoke=True)
m_f32 = compile_cell(cfg_d, TINY_DECODE).memory_analysis()
cfg_bf = dataclasses.replace(cfg_d, serve_dtype="bfloat16")
m_bf16 = compile_cell(cfg_bf, TINY_DECODE).memory_analysis()
out["bf16_args_smaller"] = (m_bf16.argument_size_in_bytes
                            < m_f32.argument_size_in_bytes)

# 5. seq_shard variant compiles
cfg_sp = dataclasses.replace(get_config("deepseek-7b", smoke=True),
                             seq_shard=True)
compile_cell(cfg_sp, TINY_TRAIN)
out["sp_compiles"] = True

print(json.dumps(out))
sys.exit(0 if all(v for k, v in out.items() if isinstance(v, bool)) else 1)
