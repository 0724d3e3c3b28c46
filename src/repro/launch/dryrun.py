import os

os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

For each combination this builds the appropriate step function
(train_step / prefill_step / decode_step), lowers it under the production
mesh with full sharding specs, compiles, and records:

  * memory_analysis (bytes per device — proves the program fits)
  * cost_analysis   (FLOPs / bytes — §Roofline numerators)
  * collective bytes parsed from the partitioned HLO
  * the derived roofline terms (single-pod mesh only, per spec)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs import ARCHITECTURES, INPUT_SHAPES, get_config
from repro.distributed.hlo_analysis import roofline
from repro.distributed.hlo_cost import analyze_hlo
from repro.distributed.sharding import DEFAULT_RULES, AxisRules, axis_rules_context
from repro.distributed.specs import (
    batch_specs,
    cache_specs,
    opt_state_specs,
    param_specs,
    tree_shardings,
)
from repro.launch.input_specs import (
    abstract_cache,
    abstract_params,
    decode_window_for,
    input_specs,
)
from repro.launch.mesh import make_production_mesh
from repro.models import Model, make_decode_step, make_prefill_step, make_train_step
from repro.optim import adamw, linear_warmup_cosine


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train, 2·N·D prefill, 2·N_active·B decode."""
    n_act = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_act * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_act * shape.global_batch * shape.seq_len
    return 2.0 * n_act * shape.global_batch  # one decode step


def run_one(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    overrides: Optional[Dict[str, Any]] = None,
    tag: str = "",
) -> Dict[str, Any]:
    cfg = get_config(arch)
    if overrides:
        plain = {k: v for k, v in overrides.items() if "." not in k}
        nested = {k: v for k, v in overrides.items() if "." in k}
        if plain:
            cfg = dataclasses.replace(cfg, **plain)
        for k, v in nested.items():
            field, sub = k.split(".", 1)
            inner = getattr(cfg, field)
            if inner is not None:
                cfg = dataclasses.replace(
                    cfg, **{field: dataclasses.replace(inner, **{sub: v})}
                )
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.size
    rules = AxisRules(DEFAULT_RULES, mesh)
    model = Model(cfg)
    t0 = time.time()

    with mesh, axis_rules_context(rules):
        params_shape = abstract_params(model)
        pspecs = param_specs(params_shape, rules)
        p_shard = tree_shardings(mesh, pspecs)
        batch = input_specs(cfg, shape)
        b_shard = tree_shardings(mesh, batch_specs(batch, rules))

        if shape.kind == "train":
            opt = adamw(linear_warmup_cosine(3e-4, 200, 10_000), weight_decay=0.1)
            opt_shape = jax.eval_shape(opt.init, params_shape)
            ospecs = opt_state_specs(opt_shape, pspecs, rules)
            o_shard = tree_shardings(mesh, ospecs)
            step = make_train_step(model, opt)
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
            ).lower(params_shape, opt_shape, batch)
        elif shape.kind == "prefill":
            window = 0
            cache_shape = abstract_cache(model, shape.global_batch, shape.seq_len)
            c_shard = tree_shardings(mesh, cache_specs(cache_shape, rules))
            step = make_prefill_step(model, window=0)
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, b_shard),
                out_shardings=(None, c_shard),
            ).lower(params_shape, batch)
        else:  # decode
            window = decode_window_for(cfg, shape)
            cache_shape = abstract_cache(model, shape.global_batch, window)
            c_shard = tree_shardings(mesh, cache_specs(cache_shape, rules))
            step = make_decode_step(model)
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, c_shard, b_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(1,),  # in-place ring-buffer update
            ).lower(params_shape, cache_shape, batch)

        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        raw_cost = compiled.cost_analysis() or {}
        # Trip-count-aware per-device analysis (raw cost_analysis counts
        # while bodies once; our models are scans over blocks).
        walker = analyze_hlo(compiled.as_text())

    bytes_per_dev = None
    try:
        bytes_per_dev = (
            mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
        ) / 1.0
    except Exception:
        pass

    # Walker numbers are per-device (SPMD module); globalize for the table.
    cost = {
        "flops": walker.flops * chips,
        "bytes accessed": walker.hbm_bytes * chips,
    }
    coll = {"total": walker.coll_bytes * chips}
    coll.update({k: v * chips for k, v in walker.coll_by_kind.items()})
    rl = roofline(
        arch,
        shape_name,
        mesh_name,
        chips,
        cost,
        coll,
        model_flops(cfg, shape),
        bytes_per_device=bytes_per_dev,
    )
    row = rl.row()
    row.update(
        {
            "tag": tag,
            "ok": True,
            "compile_s": round(time.time() - t0, 1),
            "memory_analysis": str(mem),
            "collectives": {k: v for k, v in coll.items()},
            "raw_cost_flops": float(raw_cost.get("flops", 0.0)),
            "unknown_trip_counts": walker.unknown_trip_counts,
        }
    )
    print(
        f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
        f"compile={row['compile_s']}s flops={row['hlo_flops']:.3e} "
        f"coll={row['coll_bytes']:.3e}B dominant={row['dominant']}"
    )
    print(f"  memory_analysis: {mem}")
    print(f"  cost_analysis: flops={cost.get('flops')} bytes={cost.get('bytes accessed')}")
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="benchmarks/results/dryrun.jsonl")
    ap.add_argument("--tag", default="")
    ap.add_argument(
        "--override",
        action="append",
        default=[],
        help="cfg overrides, e.g. --override shard_residuals=False",
    )
    args = ap.parse_args()
    overrides: Dict[str, Any] = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        overrides[k] = json.loads(v.lower()) if v.lower() in ("true", "false") else (
            int(v) if v.lstrip("-").isdigit() else v
        )

    archs = list(ARCHITECTURES) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    failures = 0
    with open(args.out, "a") as f:
        for arch in archs:
            for shape in shapes:
                for mp in meshes:
                    try:
                        row = run_one(arch, shape, mp, overrides=overrides, tag=args.tag)
                    except Exception as e:
                        failures += 1
                        row = {
                            "arch": arch,
                            "shape": shape,
                            "mesh": "2x16x16" if mp else "16x16",
                            "ok": False,
                            "error": f"{type(e).__name__}: {e}",
                        }
                        print(f"[dryrun] {arch} x {shape}: FAIL {e}", file=sys.stderr)
                        traceback.print_exc()
                    f.write(json.dumps(row) + "\n")
                    f.flush()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
