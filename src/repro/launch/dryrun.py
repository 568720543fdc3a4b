"""Multi-pod dry-run: lower + compile EVERY assigned (arch x shape) cell
on the production meshes, prove it fits, and extract roofline terms.

For each cell:
  * the scan-over-layers program is lowered with full parameter/optimizer
    /batch shardings and compiled -> ``memory_analysis()`` proves the
    per-chip footprint fits HBM; ``cost_analysis()`` + the trip-count-
    aware HLO parser (hloparse.py) give FLOPs and collective traffic;
  * roofline terms (seconds):
        compute    = HLO_FLOPs / (peak_FLOPs_bf16 * mxu_eff ... reported
                     raw: / peak)      [per chip — the parsed module IS
                     the per-device program]
        memory     = HLO_bytes / HBM_bw   (XLA 'bytes accessed', scaled
                     by the parsed/reported FLOP ratio to undo XLA's
                     count-loop-once behavior)
        collective = ring-adjusted collective bytes / ICI_bw
  * MODEL_FLOPS = 6*N*D (train) or 2*N*D (prefill/decode), N = active
    params, D = tokens — the useful-compute yardstick.

Usage:
  python -m repro.launch.dryrun                       # full sweep, both meshes
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --mesh multi --strategy fsdp
Artifacts append to artifacts/dryrun.json (resumable; done cells skip).
Run as a program, it compiles against 512 placeholder host devices,
which cover both the single-pod (16x16) and multi-pod (2x16x16) meshes;
importing the module sets nothing.
"""
import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs import SHAPES, all_archs, cells_for, get_arch
from repro.launch import specs as sp
from repro.launch.hloparse import analyze
from repro.launch.mesh import data_axes, make_production_mesh, mesh_chips
from repro.optim import adamw
from repro.runtime.sharding import ShardingStrategy
from repro.runtime import spmd
from repro.utils.hw import V5E


def model_flops(arch, shape) -> float:
    n = arch.active_params()
    toks = shape.tokens_per_step()
    mult = 6.0 if shape.is_training else 2.0
    return mult * n * toks


def run_cell(arch_name: str, shape_name: str, mesh_kind: str,
             strategy_name: str, loss_chunk: int = 512,
             remat_policy: str = "full", moe_impl: Optional[str] = None,
             serve_bf16: bool = False, gather_dtype: Optional[str] = None,
             variant: str = "") -> Dict[str, Any]:
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh_chips(mesh)
    strategy = ShardingStrategy(strategy=strategy_name,
                                data_axes=data_axes(multi),
                                gather_dtype=gather_dtype)
    t0 = time.time()
    import jax.numpy as jnp
    model = spmd.build_model(
        arch, strategy, mesh, shape.global_batch,
        # optimized serving holds bf16 weights (--serve-bf16); the
        # baseline keeps fp32 for strict comparability with training
        param_dtype=(jnp.bfloat16 if serve_bf16 and not shape.is_training
                     else jnp.float32),
        moe_impl=moe_impl or ("capacity" if shape.kind != "decode"
                              else "grouped"))
    model = dataclasses.replace(model, loss_chunk=loss_chunk,
                                remat_policy=remat_policy)
    pshape = sp.params_shape(model)
    with mesh:
        if shape.kind == "train":
            oshape = sp.opt_shape(model, pshape)
            bundle = spmd.train_bundle(model, adamw.AdamWConfig(), strategy,
                                       mesh, pshape, oshape, shape)
            # donate params+opt: outputs alias inputs (production setup)
            lowered = bundle.jit(donate=(0, 1)).lower(
                pshape, oshape, sp.batch_specs(arch, shape))
        elif shape.kind == "prefill":
            bundle = spmd.prefill_bundle(model, strategy, mesh, pshape, shape)
            lowered = bundle.jit().lower(pshape, sp.prefill_specs(arch, shape))
        else:
            tok, cache, pos = sp.decode_specs(arch, shape, model)
            bundle = spmd.decode_bundle(model, strategy, mesh, pshape, cache,
                                        shape)
            # donate the KV/SSM cache: updated in place when serving
            lowered = bundle.jit(donate=(2,)).lower(pshape, tok, cache, pos)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    stats = analyze(text, default_group=mesh.shape[strategy.model_axis])

    xla_flops = float(ca.get("flops", 0.0)) or 1.0
    xla_bytes = float(ca.get("bytes accessed", 0.0))
    # undo XLA's loop-counted-once on bytes via the FLOP expansion ratio
    expansion = max(stats.dot_flops / xla_flops, 1.0)
    hbm_bytes = xla_bytes * expansion

    compute_s = stats.dot_flops / V5E.peak_flops_bf16
    memory_s = hbm_bytes / V5E.hbm_bandwidth
    collective_s = stats.collective_bytes / V5E.ici_bandwidth
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=terms.get).replace("_s", "")
    mf = model_flops(arch, shape)
    global_flops = stats.dot_flops * chips

    per_dev_bytes = {
        "args_gb": ma.argument_size_in_bytes / 1e9,
        "temps_gb": ma.temp_size_in_bytes / 1e9,
        "output_gb": ma.output_size_in_bytes / 1e9,
        "alias_gb": ma.alias_size_in_bytes / 1e9,
    }
    # donated buffers alias outputs: count them once
    fits = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes
            - ma.alias_size_in_bytes) <= V5E.hbm_capacity

    suffix = f"/{variant}" if variant else ""
    return {
        "key": f"{arch_name}/{shape_name}/{mesh_kind}/{strategy_name}{suffix}",
        "arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
        "strategy": strategy_name, "variant": variant, "chips": chips,
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "compile_us": (t_lower + t_compile) * 1e6,
        "memory": per_dev_bytes, "fits_hbm": bool(fits),
        "hlo": {
            "xla_flops_per_dev": xla_flops,
            "parsed_flops_per_dev": stats.dot_flops,
            "xla_bytes_per_dev": xla_bytes,
            "dot_bytes_per_dev": stats.dot_bytes,
            "memory_s_dots": stats.dot_bytes / V5E.hbm_bandwidth,
            "collective_bytes_per_dev": stats.collective_bytes,
            "collective_counts": stats.collective_counts,
            "num_whiles": stats.num_whiles,
        },
        "roofline": {
            **{k: round(v, 6) for k, v in terms.items()},
            "bottleneck": bottleneck,
            "model_flops": mf,
            "hlo_flops_global": global_flops,
            "model_flops_ratio": mf / max(global_flops, 1.0),
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--strategy", default="fsdp", choices=["fsdp", "tp"])
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "dots"])
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, "dense", "grouped", "capacity",
                             "capacity_vec"])
    ap.add_argument("--loss-chunk", type=int, default=512)
    ap.add_argument("--serve-bf16", action="store_true")
    ap.add_argument("--gather-dtype", default=None,
                    choices=[None, "bfloat16"])
    ap.add_argument("--variant", default="",
                    help="label for perf-iteration runs (artifact key suffix)")
    ap.add_argument("--out", default="artifacts/dryrun.json")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    cells = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            cells = json.load(f).get("cells", [])
    done = {c["key"] for c in cells if c.get("status") == "ok"}

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    work = []
    for arch in all_archs():
        if args.arch and arch.name != args.arch.replace("-", "_").replace(".", "_"):
            continue
        for shape in cells_for(arch):
            if args.shape and shape.name != args.shape:
                continue
            for mesh_kind in meshes:
                work.append((arch.name, shape.name, mesh_kind))

    suffix = f"/{args.variant}" if args.variant else ""
    for arch_name, shape_name, mesh_kind in work:
        key = f"{arch_name}/{shape_name}/{mesh_kind}/{args.strategy}{suffix}"
        if key in done and not args.force:
            print(f"SKIP {key}", flush=True)
            continue
        print(f"RUN  {key}", flush=True)
        try:
            cell = run_cell(arch_name, shape_name, mesh_kind, args.strategy,
                            loss_chunk=args.loss_chunk,
                            remat_policy=args.remat_policy,
                            moe_impl=args.moe_impl,
                            serve_bf16=args.serve_bf16,
                            gather_dtype=args.gather_dtype,
                            variant=args.variant)
            r = cell["roofline"]
            print(f"  ok: compile {cell['compile_s']}s "
                  f"mem {cell['memory']['args_gb']:.1f}+{cell['memory']['temps_gb']:.1f}GB "
                  f"fits={cell['fits_hbm']} bottleneck={r['bottleneck']} "
                  f"terms=({r['compute_s']:.4f},{r['memory_s']:.4f},"
                  f"{r['collective_s']:.4f})s useful={r['model_flops_ratio']:.2f}",
                  flush=True)
        except Exception as e:
            traceback.print_exc()
            cell = {"key": key, "arch": arch_name, "shape": shape_name,
                    "mesh": mesh_kind, "strategy": args.strategy,
                    "status": f"error: {type(e).__name__}: {e}"}
        cells = [c for c in cells if c["key"] != key] + [cell]
        with open(args.out, "w") as f:
            json.dump({"cells": cells}, f, indent=1)


if __name__ == "__main__":
    # before jax initializes its backend: the device count is fixed then
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
