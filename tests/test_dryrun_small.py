"""Dry-run machinery on a small in-process mesh (the 256/512-chip production
runs live in experiments/dryrun; this guards the mechanics in CI). Runs in a
subprocess so the 8-device XLA flag never leaks into other tests.

Uses the `reduced()` (tiny-dims, same-family) variant of olmo-1b with short
sequences so the lower+compile fits the tier-1 time budget — the mechanics
under test (SPMD sharding, collectives in the compiled HLO, roofline
decomposition) are dimension-independent."""
import json
import subprocess
import sys

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # never probe for TPU in the subprocess
import json, jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec
from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.models import build
from repro.launch.steps import (batch_shardings, build_shardings,
                                cache_shardings, make_serve_step,
                                make_train_step, opt_state_struct_and_sharding)
from repro.launch import roofline as rl
from repro.launch.decompose import decompose_cell
from repro.parallel.sharding import default_rules

cfg = get_arch("olmo-1b").reduced()
model = build(cfg)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
rules = default_rules()
out = {}

# train lower+compile
shape = ShapeConfig("t", 512, 8, "train")
p_struct, p_shard, _ = build_shardings(model, mesh, rules)
b_struct, b_shard = batch_shardings(model, shape, mesh, rules)
step_fn, _ = make_train_step(model, shape, mesh, rules)
o_struct, o_shard = opt_state_struct_and_sharding(model, mesh, p_shard,
                                                  p_struct, jnp.bfloat16)
sc = NamedSharding(mesh, PartitionSpec())
comp = jax.jit(step_fn, in_shardings=(p_shard, o_shard, b_shard, sc),
               out_shardings=(p_shard, o_shard, sc, sc),
               donate_argnums=(0, 1)).lower(
    p_struct, o_struct, b_struct,
    jax.ShapeDtypeStruct((), jnp.int32)).compile()
out["train_flops"] = float(comp.cost_analysis().get("flops", 0))
out["train_coll"] = rl.collective_bytes(comp.as_text())["total"]

# decode lower+compile
shape_d = ShapeConfig("d", 256, 8, "decode")
c_struct, c_shard = cache_shardings(model, shape_d, mesh, rules)
b_struct, b_shard = batch_shardings(model, shape_d, mesh, rules)
serve = make_serve_step(model)
comp_d = jax.jit(serve, in_shardings=(p_shard, c_shard, b_shard["tokens"]),
                 donate_argnums=(1,)).lower(
    p_struct, c_struct, b_struct["tokens"]).compile()
out["decode_ok"] = 1

# decomposition
dec = decompose_cell(model, shape, mesh, rules)
out["roofline"] = dec["roofline"]
print(json.dumps(out))
"""


def test_dryrun_small_mesh():
    res = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["train_flops"] > 0
    assert out["train_coll"] > 0                # SPMD => real collectives
    assert out["decode_ok"] == 1
    r = out["roofline"]
    assert r["dominant"] in ("compute", "memory", "collective")
    # tiny dims pad heavily on TPU-tile granularity, so the useful-flops
    # ratio sits far below the production configs' band — it just has to
    # be a sane positive fraction here.
    assert 0.0 < r["useful_flops_ratio"] < 1.5
    assert r["t_compute"] > 0 and r["t_memory"] > 0


def test_collective_parser_on_synthetic_hlo():
    from repro.launch.roofline import collective_bytes
    hlo = """
  %all-reduce.1 = f32[64,512]{1,0} all-reduce(%x), channel_id=1
  %ag = bf16[128,256]{1,0} all-gather(%y), dimensions={0}
  %t = (f32[8]{0}, f32[8]{0}) all-reduce(%a, %b), channel_id=2
  %ar-start = f32[16]{0} all-reduce-start(%c)
  %ar-done = f32[16]{0} all-reduce-done(%ar-start)
  %other = f32[4]{0} add(%p, %q)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 64 * 512 * 4 + 2 * 8 * 4 + 16 * 4
    assert out["all-gather"] == 128 * 256 * 2
    assert out["count"] == 4
