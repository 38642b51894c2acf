import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, without allocating a single parameter.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
        --shape train_4k --mesh both

For each cell it records: compile OK, per-device memory analysis, HLO
FLOPs/bytes (cost_analysis), and collective traffic parsed from the
partitioned module — the §Roofline inputs.  Artifacts land in
artifacts/dryrun/<arch>__<shape>__<mesh>.json.

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count on first init); do not set it globally.
"""

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES, applicable_shapes,  # noqa: E402
                           get_config, input_specs, train_config)
from repro.launch import hlo_analysis, mesh as mesh_lib  # noqa: E402
from repro.launch.steps import (batch_shardings, make_serve_step,  # noqa: E402
                                make_train_step)

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def lower_cell(arch: str, shape_name: str, mesh, *, mesh_name: str,
               overrides: dict = None, microbatches: int = 0):
    """Lower + compile one (arch, shape, mesh) cell; return metrics dict.

    ``overrides``: ModelConfig.replace kwargs (§Perf knobs: flash_threshold,
    parallelism, moe_group_size, remat_policy, ...).
    """
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            tc = train_config(arch)
            ts = make_train_step(cfg, mesh,
                                 num_microbatches=(microbatches or
                                                   tc["num_microbatches"]),
                                 optimizer=tc["optimizer"])
            specs = input_specs(cfg, shape, ts.model)
            params_abs = ts.model.abstract()
            opt_abs = jax.eval_shape(ts.opt.init, params_abs)
            fn = ts.jit(specs, donate=False)
            lowered = fn.lower(params_abs, opt_abs, specs)
        elif shape.kind == "prefill":
            ss = make_serve_step(cfg, mesh)
            specs = input_specs(cfg, shape, ss.model)
            params_abs = ss.model.abstract()
            fn = ss.jit_prefill(specs)
            lowered = fn.lower(params_abs, specs)
        else:  # decode
            ss = make_serve_step(cfg, mesh)
            specs = input_specs(cfg, shape, ss.model)
            params_abs = ss.model.abstract()
            fn = ss.jit_decode(specs["cache"], donate=False)
            lowered = fn.lower(params_abs, specs["cache"], specs["tokens"],
                               specs["pos"])
        compiled = lowered.compile()
    t_compile = time.time() - t0

    chips = mesh_lib.mesh_chips(mesh)
    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0)),
        }
    except Exception as e:  # backend without memory analysis
        mem_info = {"error": str(e)}
    hlo = compiled.as_text()
    # per-device numbers from the partitioned module, with while-loop
    # trip multipliers (cost_analysis counts scan bodies ONCE — useless
    # for scanned models; recorded for reference only)
    summary = hlo_analysis.analyze(hlo)
    flops = summary.flops * chips              # global
    bytes_hbm = summary.mem_bytes * chips
    coll_total = summary.coll_total * chips
    terms = hlo_analysis.roofline_terms(
        flops, bytes_hbm, coll_total, chips=chips,
        peak_flops=mesh_lib.PEAK_FLOPS_BF16, hbm_bw=mesh_lib.HBM_BW,
        ici_bw=mesh_lib.ICI_BW)
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": chips, "ok": True, "compile_seconds": round(t_compile, 1),
        "hlo_flops": flops, "hlo_bytes": bytes_hbm,
        "collective_bytes": {k: float(v) * chips
                             for k, v in summary.coll_bytes.items()},
        "collective_counts": summary.coll_counts,
        "collective_bytes_total": coll_total,
        "xla_cost_analysis": {
            "flops_per_device_unrolled_once": float(cost.get("flops", 0.0)),
            "bytes_per_device_unrolled_once":
                float(cost.get("bytes accessed", 0.0)),
        },
        "memory": mem_info,
        "roofline": terms,
        "dominant": hlo_analysis.dominant_term(terms),
        "hlo_chars": len(hlo),
    }


def run(archs, shapes, meshes, out_dir: Path, *, overrides=None,
        microbatches=0, tag_suffix=""):
    out_dir.mkdir(parents=True, exist_ok=True)
    results = []
    for arch in archs:
        cfg = get_config(arch)
        app = applicable_shapes(cfg)
        for shape_name in shapes:
            if shape_name not in app:
                rec = {"arch": arch, "shape": shape_name, "ok": None,
                       "skip": "N/A-by-design (needs sub-quadratic attn)"}
                print(f"[skip] {arch} x {shape_name}: {rec['skip']}")
                results.append(rec)
                continue
            for mesh_name in meshes:
                mesh = mesh_lib.make_production_mesh(
                    multi_pod=(mesh_name == "multi"))
                tag = f"{arch}__{shape_name}__{mesh_name}{tag_suffix}"
                try:
                    rec = lower_cell(arch, shape_name, mesh,
                                     mesh_name=mesh_name,
                                     overrides=overrides,
                                     microbatches=microbatches)
                    t = rec["roofline"]
                    print(f"[ok]   {tag}: compile={rec['compile_seconds']}s "
                          f"flops={rec['hlo_flops']:.3e} "
                          f"coll={rec['collective_bytes_total']:.3e}B "
                          f"dom={rec['dominant']} "
                          f"t=({t['t_compute']:.4f},{t['t_memory']:.4f},"
                          f"{t['t_collective']:.4f})s")
                except Exception as e:
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "ok": False,
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    print(f"[FAIL] {tag}: {rec['error']}")
                (out_dir / f"{tag}.json").write_text(json.dumps(rec,
                                                                indent=2))
                results.append(rec)
    n_fail = sum(1 for r in results if r.get("ok") is False)
    print(f"\n{len(results)} cells, {n_fail} failures")
    return results, n_fail


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id | comma list | all")
    ap.add_argument("--shape", default="all",
                    help="shape name | comma list | all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=str(ARTIFACTS))
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    # §Perf knobs
    ap.add_argument("--flash-threshold", type=int, default=0)
    ap.add_argument("--causal-skip", action="store_true")
    ap.add_argument("--attn-bf16", action="store_true")
    ap.add_argument("--parallelism", default="")
    ap.add_argument("--moe-group", type=int, default=0)
    ap.add_argument("--remat", default="")
    ap.add_argument("--microbatches", type=int, default=0)
    args = ap.parse_args()
    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    overrides = {}
    if args.flash_threshold:
        overrides["flash_threshold"] = args.flash_threshold
    if args.causal_skip:
        overrides["flash_causal_skip"] = True
    if args.attn_bf16:
        overrides["attn_scores_bf16"] = True
    if args.parallelism:
        overrides["parallelism"] = args.parallelism
    if args.moe_group:
        overrides["moe_group_size"] = args.moe_group
    if args.remat:
        overrides["remat_policy"] = args.remat
    _, n_fail = run(archs, shapes, meshes, Path(args.out),
                    overrides=overrides or None,
                    microbatches=args.microbatches,
                    tag_suffix=(f"__{args.tag}" if args.tag else ""))
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
