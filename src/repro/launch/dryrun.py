import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we jit the right step function (train_step for train shapes,
prefill_step for prefill, serve_step for decode/long) against the production
mesh with full in/out shardings, ``.lower().compile()`` it on 512 host
placeholder devices, and record:

  * memory_analysis()  — proves the step fits per-chip HBM,
  * cost_analysis()    — FLOPs / bytes for the §Roofline terms,
  * collective bytes   — parsed from the optimized HLO (scan-weighted),
  * the roofline report (compute/memory/collective seconds, dominant term).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_0_5b \
      --shape train_4k [--multi-pod] [--out runs/dryrun]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_arch
from repro.configs.base import SHAPES
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build_model, input_specs
from repro.models.sharding import MeshCtx
from repro.roofline.analysis import V5E, roofline_report
from repro.roofline.hlo_parse import analyze as analyze_hlo
from repro.train.steps import (
    batch_shardings,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    training_state_shapes,
    training_state_specs,
)


def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return {"status": "skipped", "reason": "pure full-attention arch (DESIGN.md §4)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = MeshCtx(mesh)
    model = build_model(cfg, max_pos=shape.seq_len)
    ispecs = input_specs(cfg, shape)
    bshard = batch_shardings(cfg, shape, ctx)
    t0 = time.time()
    if shape.kind == "train":
        pshapes, oshapes = training_state_shapes(model)
        pspecs, ospecs = training_state_specs(model, ctx)
        step = make_train_step(model, ctx)
        jitted = jax.jit(
            step,
            in_shardings=(pspecs, ospecs, bshard),
            out_shardings=(pspecs, ospecs, ctx.replicated()),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(pshapes, oshapes, ispecs)
    elif shape.kind == "prefill":
        pshapes = model.param_shapes()
        pspecs = model.param_specs(ctx, serve=True)
        step = make_prefill_step(model, ctx)
        jitted = jax.jit(
            step,
            in_shardings=(pspecs, bshard),
            out_shardings=ctx.ns(*(ctx.token_spec(shape.global_batch)[0:1]), None)
            if shape.global_batch % ctx.n_batch == 0
            else ctx.replicated(),
        )
        lowered = jitted.lower(pshapes, ispecs)
    else:  # decode
        pshapes = model.param_shapes()
        pspecs = model.param_specs(ctx, serve=True)
        B, S = shape.global_batch, shape.seq_len
        ctmpl = model.cache_template(B, S)
        cshapes = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in ctmpl.items()}
        cspecs = model.cache_specs(B, S, ctx)
        step = make_serve_step(model, ctx)
        logits_spec = (
            ctx.ns(ctx.batch_axes, None)
            if B % ctx.n_batch == 0 and B >= ctx.n_batch
            else ctx.replicated()
        )
        jitted = jax.jit(
            step,
            in_shardings=(pspecs, cspecs, bshard),
            out_shardings=(logits_spec, cspecs),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(pshapes, cshapes, ispecs)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # ---- analyses --------------------------------------------------------
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            )
            if hasattr(mem, k)
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": str(e)}
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # cost_analysis counts while bodies once; use the scan-weighted HLO
    # analysis for the roofline terms (see roofline/hlo_parse.py).
    weighted = analyze_hlo(hlo)
    flops = float(weighted["flops"])
    bytes_accessed = float(weighted["hbm_bytes"])
    coll = weighted["collective_bytes"]
    coll_total = float(weighted["collective_bytes_total"])
    n_chips = int(np.prod(list(mesh.shape.values())))
    nmodel = model.n_active_params()
    # MODEL_FLOPS: 6·N·D tokens for train; 2·N·D for forward-only
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    factor = 6 if shape.kind == "train" else 2
    model_flops = factor * nmodel * tokens
    report = roofline_report(
        flops=flops, bytes_accessed=bytes_accessed, collective_bytes=coll_total,
        n_chips=n_chips, model_flops=model_flops,
    )
    per_chip_hbm = (
        mem_d.get("argument_size_in_bytes", 0)
        + mem_d.get("temp_size_in_bytes", 0)
        + mem_d.get("output_size_in_bytes", 0)
        - mem_d.get("alias_size_in_bytes", 0)
    )
    return {
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_d,
        "per_chip_live_bytes": int(per_chip_hbm),
        "fits_hbm": bool(per_chip_hbm <= V5E.hbm_bytes),
        "flops_per_chip": flops,
        "bytes_per_chip": bytes_accessed,
        "collective_bytes": coll,
        "collective_bytes_total": coll_total,
        "cost_analysis_raw": {
            "flops_unweighted": float(cost.get("flops", 0.0)),
            "bytes_unweighted": float(cost.get("bytes accessed", 0.0)),
        },
        "unknown_trip_whiles": weighted["unknown_trip_whiles"],
        "model_flops": model_flops,
        "n_active_params": nmodel,
        "roofline": report,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    args = ap.parse_args()
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    mesh_tag = "pod2" if args.multi_pod else "pod1"
    path = outdir / f"{args.arch}__{args.shape}__{mesh_tag}.json"
    try:
        res = lower_cell(args.arch, args.shape, args.multi_pod)
    except Exception as e:
        res = {
            "status": "error",
            "arch": args.arch,
            "shape": args.shape,
            "mesh": mesh_tag,
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    path.write_text(json.dumps(res, indent=2, default=str))
    ok = res["status"]
    print(f"[{ok}] {args.arch} {args.shape} {mesh_tag}")
    if ok == "ok":
        print(json.dumps({k: res[k] for k in ("per_chip_live_bytes", "fits_hbm",
                                              "flops_per_chip", "collective_bytes_total")},
                         indent=2))
        print("memory_analysis:", json.dumps(res["memory"]))
        print("roofline:", json.dumps(res["roofline"]))
    elif ok == "error":
        print(res["error"])
        print(res["traceback"][-1500:])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
