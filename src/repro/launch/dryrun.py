import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init).  512 placeholder host devices back the production
meshes: 16×16 (one v5e pod) and 2×16×16 (two pods).

Per cell this script:
  1. builds the model and ``ShapeDtypeStruct`` input specs (no allocation),
  2. jits the right step (train_step / prefill / decode) with in/out
     shardings from parallel/sharding.py,
  3. ``.lower().compile()`` — any sharding mismatch, OOM-at-compile or
     unsupported collective fails the cell,
  4. records memory_analysis / cost_analysis / per-class collective wire
     bytes into a JSON file consumed by the roofline benchmarks.

Usage:
  python -m repro.launch.dryrun --arch qwen3-moe-30b-a3b --shape train_4k \
      --mesh single --out results/
  python -m repro.launch.dryrun --all --mesh both --out results/
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cell_is_runnable, get_config, list_archs
from repro.core import hloscan
from repro.launch.mesh import auto_mesh, make_production_mesh
from repro.models import build_model
from repro.optim import AdamWConfig, adamw_init
from repro.parallel.sharding import ShardingRules, choose_mode
from repro.train.step import make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               mode: str = "auto", opt_dtype: str = "float32",
               microbatches: int = 1, collect_hlo: bool = True,
               save_hlo_path=None, cfg_overrides=None, mesh_shape=None):
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.with_overrides(**cfg_overrides)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    if mesh_shape is not None:
        # per-arch logical remapping of the same physical chips (§Perf):
        # the topology is fixed, the (data, model) factorization is not.
        axes = (("pod", "data", "model") if len(mesh_shape) == 3
                else ("data", "model"))
        mesh = auto_mesh(mesh_shape, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    if mode == "auto":
        mode = choose_mode(cfg, mesh)
    rules = ShardingRules(cfg, mesh, mode=mode)

    specs = model.input_specs(shape)
    params_abs = model.init_abstract()
    p_spec = rules.params_spec(params_abs)
    p_shard = rules.to_sharding(p_spec)

    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            opt_cfg = AdamWConfig(state_dtype=opt_dtype)
            opt_abs = jax.eval_shape(
                lambda p: adamw_init(p, opt_cfg), params_abs)
            o_shard = rules.to_sharding(rules.opt_spec(opt_abs, p_spec))
            b_shard = rules.to_sharding(rules.batch_spec(specs["batch"]))
            step = make_train_step(model, opt_cfg,
                                   microbatches=microbatches)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, specs["batch"])
        elif shape.kind == "prefill":
            b_shard = rules.to_sharding(rules.batch_spec(specs["batch"]))
            jitted = jax.jit(lambda p, b: model.prefill(p, b),
                             in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(params_abs, specs["batch"])
        else:  # decode
            c_shard = rules.to_sharding(rules.cache_spec(specs["cache"]))
            t_shard = rules.to_sharding(rules.batch_spec(
                {"token": specs["token"]}))["token"]
            pos_shard = NamedSharding(mesh, P())
            jitted = jax.jit(
                lambda p, c, t, i: model.decode_step(p, c, t, i),
                in_shardings=(p_shard, c_shard, t_shard, pos_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(1,))
            lowered = jitted.lower(params_abs, specs["cache"],
                                   specs["token"], jnp.int32(0))
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    n_chips = mesh.size
    mem = hloscan.memory_summary(compiled)
    cost = hloscan.cost_summary(compiled)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok", "mode": mode, "opt_dtype": opt_dtype,
        "microbatches": microbatches,
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem, "cost": cost,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    if collect_hlo:
        try:
            text = compiled.as_text()
            if save_hlo_path is not None:
                import gzip
                with gzip.open(save_hlo_path, "wt") as fh:
                    fh.write(text)
            # trip-count-aware analyzer (cost_analysis counts while bodies
            # once — see core/hloscan.py)
            result["hlo"] = hloscan.analyze_hlo(text)
            result["collectives"] = hloscan.collective_bytes(text)
        except Exception as e:  # pragma: no cover
            result["hlo"] = {"error": str(e)}
    print(f"[dryrun] {arch} × {shape_name} × "
          f"{'multi' if multi_pod else 'single'}: OK "
          f"(mode={mode}, compile {t_compile:.0f}s, "
          f"temp/dev {mem.get('temp_size_in_bytes', 0)/2**30:.2f} GiB, "
          f"args/dev {mem.get('argument_size_in_bytes', 0)/2**30:.2f} GiB)")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "tp", "fsdp"])
    ap.add_argument("--opt-dtype", default="float32",
                    choices=["float32", "int8"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default="results")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--attn-batch-shard", action="store_true",
                    help="§Perf: shard attention batch over (data, model)")
    ap.add_argument("--attn-bf16-logits", action="store_true",
                    help="§Perf: bf16 attention logits/probs")
    args = ap.parse_args()
    overrides = {}
    if args.attn_batch_shard:
        overrides["attn_batch_shard"] = True
    if args.attn_bf16_logits:
        overrides["attn_logits_bf16"] = True

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    cells = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    archs = [a for a in archs if a != "paper-conv-sweep"]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    n_fail = 0
    for arch, shape, mp in cells:
        mesh_name = "multi" if mp else "single"
        fname = outdir / f"{args.tag}__{arch}__{shape}__{mesh_name}.json"
        if fname.exists():
            print(f"[dryrun] {fname.name} exists, skipping")
            continue
        try:
            hlo_path = (outdir / (fname.stem + ".hlo.gz")
                        if args.save_hlo else None)
            result = lower_cell(arch, shape, multi_pod=mp, mode=args.mode,
                                opt_dtype=args.opt_dtype,
                                microbatches=args.microbatches,
                                save_hlo_path=hlo_path,
                                cfg_overrides=overrides or None)
        except Exception as e:
            n_fail += 1
            result = {"arch": arch, "shape": shape, "mesh": mesh_name,
                      "status": "error", "error": str(e),
                      "traceback": traceback.format_exc()[-4000:]}
            print(f"[dryrun] {arch} × {shape} × {mesh_name}: "
                  f"FAIL — {type(e).__name__}: {str(e)[:200]}")
        fname.write_text(json.dumps(result, indent=1))
    print(f"[dryrun] done; {n_fail} failures")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
