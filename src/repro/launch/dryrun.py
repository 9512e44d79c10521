import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This proves the distribution config is coherent without hardware:
`jax.jit(step, in_shardings=…).lower(**ShapeDtypeStructs).compile()` must
succeed on the (16,16) single-pod mesh AND the (2,16,16) multi-pod mesh for
every assigned architecture and input shape. memory_analysis() proves the
step fits 16 GB/chip; cost_analysis() + the optimized HLO feed §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k \
      --mesh single --out experiments/dryrun
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec

from repro.configs import ARCHS, SHAPES, get_arch
from repro.configs.base import SUBQUADRATIC, skipped_cells
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (batch_shardings, build_shardings,
                                cache_shardings, choose_microbatch,
                                make_prefill_step, make_serve_step,
                                make_train_step, opt_state_struct_and_sharding)
from repro.models import build
from repro.parallel.sharding import (rules_for, set_activation_sharding,
                                     spec_for)


def _mesh_for(kind: str):
    if kind == "single":
        devs = jax.devices()[:256]
        return jax.make_mesh((16, 16), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2, devices=devs)
    return make_production_mesh(multi_pod=True)


def run_cell(arch: str, shape_name: str, mesh_kind: str, rules=None,
             verbose: bool = True) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    model = build(cfg)
    mesh = _mesh_for(mesh_kind)
    chips = mesh.size
    rules = rules or rules_for(cfg, mesh)
    set_activation_sharding(rules, mesh)   # model-code logical constraints
    dtype = jnp.bfloat16
    t0 = time.time()

    p_struct, p_shard, _ = build_shardings(model, mesh, rules, dtype)
    b_struct, b_shard = batch_shardings(model, shape, mesh, rules, dtype)
    total, active = model.param_counts()

    if shape.kind == "train":
        step_fn, _ = make_train_step(model, shape, mesh, rules)
        o_struct, o_shard = opt_state_struct_and_sharding(
            model, mesh, p_shard, p_struct, dtype)
        scalar_sh = NamedSharding(mesh, PartitionSpec())
        jitted = jax.jit(
            step_fn,
            in_shardings=(p_shard, o_shard, b_shard, scalar_sh),
            out_shardings=(p_shard, o_shard, scalar_sh, scalar_sh),
            donate_argnums=(0, 1))
        lowered = jitted.lower(p_struct, o_struct, b_struct,
                               jax.ShapeDtypeStruct((), jnp.int32))
        tokens = shape.global_batch * shape.seq_len
        mflops = rl.model_flops(total, active, "train", tokens)
        extra = {"accum": step_fn.accum}
    elif shape.kind == "prefill":
        prefill_fn = make_prefill_step(model, max_len=shape.seq_len)
        c_struct, c_shard = cache_shardings(model, shape, mesh, rules, dtype)
        lg_spec = spec_for(("batch", "vocab"),
                           (shape.global_batch, cfg.vocab), rules, mesh)
        out_sh = (NamedSharding(mesh, lg_spec), c_shard) \
            if cfg.family != "encdec" else None
        jitted = jax.jit(prefill_fn, in_shardings=(p_shard, b_shard),
                         out_shardings=out_sh)
        lowered = jitted.lower(p_struct, b_struct)
        tokens = shape.global_batch * shape.seq_len
        mflops = rl.model_flops(total, active, "prefill", tokens)
        extra = {}
    else:  # decode
        serve_fn = make_serve_step(model)
        c_struct, c_shard = cache_shardings(model, shape, mesh, rules, dtype)
        tok_sh = {k: v for k, v in b_shard.items()}
        lg_spec = spec_for(("batch", "vocab"),
                           (shape.global_batch, cfg.vocab), rules, mesh)
        jitted = jax.jit(serve_fn,
                         in_shardings=(p_shard, c_shard, tok_sh["tokens"]),
                         out_shardings=(NamedSharding(mesh, lg_spec), c_shard),
                         donate_argnums=(1,))
        lowered = jitted.lower(p_struct, c_struct, b_struct["tokens"])
        tokens = shape.global_batch  # one new token per sequence
        mflops = rl.model_flops(total, active, "decode", tokens)
        extra = {}

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_rec = {
        "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_size_bytes":
            getattr(mem, "generated_code_size_in_bytes", None),
        "alias_size_bytes": getattr(mem, "alias_size_in_bytes", None),
    }
    # Raw full-step numbers (while bodies counted once — see decompose.py).
    roof_raw = rl.build(compiled, chips, mflops)
    coll = rl.collective_bytes(compiled.as_text())
    # Corrected roofline via piece-wise decomposition with trip counts.
    from repro.launch.decompose import decompose_cell
    t2 = time.time()
    dec = decompose_cell(model, shape, mesh, rules)
    t_decompose = time.time() - t2
    roof = dec["roofline"]
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "chips": chips,
        "status": "ok", "params_total": total, "params_active": active,
        "tokens_per_step": tokens, "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "decompose_s": round(t_decompose, 1), "memory": mem_rec,
        "collectives_full_step_raw": coll,
        "roofline_full_step_raw": roof_raw.to_dict(),
        "roofline": roof, "pieces": {
            k: {kk: vv for kk, vv in v.items() if kk != "coll_by_kind"}
            for k, v in dec["pieces"].items()}, **extra,
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {mesh_kind}: OK "
              f"(compile {t_compile:.0f}s, dominant={roof['dominant']}, "
              f"roofline={roof['roofline_fraction']:.3f}, "
              f"useful={roof['useful_flops_ratio']:.3f})")
        print("  memory_analysis:", {k: v for k, v in mem_rec.items()
                                     if v is not None})
        print("  terms(s): compute=%.4f memory=%.4f collective=%.4f"
              % (roof["t_compute"], roof["t_memory"], roof["t_collective"]))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--missing", action="store_true",
                    help="run only cells without an ok record yet")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all or args.missing:
        for a in ARCHS:
            for s in SHAPES:
                if s == "long_500k" and a not in SUBQUADRATIC:
                    continue
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        for mk in meshes:
            path = os.path.join(args.out, f"{arch}__{shape}__{mk}.json")
            if args.missing and os.path.exists(path):
                try:
                    if json.load(open(path)).get("status") == "ok":
                        continue
                except Exception:  # noqa: BLE001
                    pass
            try:
                rec = run_cell(arch, shape, mk)
            except Exception as e:  # noqa: BLE001 — record and continue
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "mesh": mk,
                       "status": "fail", "error": repr(e)}
                failures += 1
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            sys.stdout.flush()
    # Record the documented skips so the table is complete.
    for a in ARCHS:
        for (aa, ss, why) in skipped_cells(a):
            path = os.path.join(args.out, f"{aa}__{ss}__skip.json")
            with open(path, "w") as f:
                json.dump({"arch": aa, "shape": ss, "status": "skipped",
                           "reason": why}, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
