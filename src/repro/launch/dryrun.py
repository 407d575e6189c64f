import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh).

NOTE: the two ``os.environ`` lines above MUST stay the first statements —
jax locks the device count at first init.

For each cell, on the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh:
  * jit(step).lower(**abstract inputs) -> .compile()  (sharding must be
    coherent; failures here are bugs),
  * print compiled.memory_analysis()  (per-chip HBM proof),
  * print compiled.cost_analysis() flops (XLA's, loop-UNAWARE — recorded for
    reference) and the loop-aware HLO analysis (FLOPs / HBM bytes /
    collective bytes) that feeds EXPERIMENTS.md §Roofline,
  * dump a JSON record per cell under results/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
"""

import argparse
import json
import time
import traceback


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str = "results/dryrun") -> dict:
    import jax

    from repro.configs import get_config
    from repro.distributed import hlo_analysis as ha
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES, cell_supported
    from repro.launch.steps import lower_cell

    cfg = get_config(arch)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "ok"}
    ok, reason = cell_supported(cfg, shape_name)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    try:
        # explicit interval timestamps: t_lower must not fold mesh
        # construction in, and t_compile must not fold t_lower in — the
        # old running-subtraction form made both easy to get wrong
        t0 = time.time()
        lowered, _ = lower_cell(cfg, shape_name, mesh)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        t_lower, t_compile = t1 - t0, t2 - t1

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        txt = compiled.as_text()
        analysis = ha.analyze(txt)
        terms = ha.roofline_terms(analysis, ha.V5E_KIND)  # target chip

        counts = cfg.param_counts()
        cell = SHAPES[shape_name]
        rec.update({
            "chips": n_chips,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "per_chip_total_gb": round(
                    (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
                    / 2**30, 3),
            },
            "xla_cost_flops_loop_unaware": cost.get("flops", -1.0),
            "hlo": {
                "flops_per_chip": analysis.flops,
                "hbm_bytes_per_chip": analysis.hbm_bytes,
                "collective_operand_bytes": analysis.collective_operand_bytes,
                "collective_wire_bytes": analysis.collective_wire_bytes,
                "collectives": {
                    k: {"count": v.count, "operand_bytes": v.operand_bytes,
                        "wire_bytes": v.wire_bytes}
                    for k, v in analysis.collectives.items()},
            },
            "roofline": terms,
            "params_total": counts["total"],
            "params_active": counts["active"],
            "tokens_per_step": cell.global_batch * (
                cell.seq_len if cell.kind == "train" else 1),
        })
    except Exception as e:  # a failure here is a sharding bug — surface it
        rec.update(status="failed", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    import os as _os
    _os.makedirs(out_dir, exist_ok=True)
    path = f"{out_dir}/{arch}__{shape_name}__{mesh_name}.json"
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out-dir", default="results/dryrun")
    args = ap.parse_args()

    from repro.configs import ARCH_IDS
    from repro.launch.shapes import SHAPES

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        cells.append((args.arch, args.shape))

    meshes = [False] if args.single_pod_only else (
        [True] if args.multi_pod else [False, True])

    for arch, shape in cells:
        for mp in meshes:
            rec = run_cell(arch, shape, multi_pod=mp, out_dir=args.out_dir)
            status = rec["status"]
            extra = ""
            if status == "ok":
                extra = (f" mem/chip={rec['memory']['per_chip_total_gb']}GB"
                         f" flops/chip={rec['hlo']['flops_per_chip']:.3g}"
                         f" coll_wire={rec['hlo']['collective_wire_bytes']:.3g}B"
                         f" compile={rec['compile_s']}s")
            elif status == "failed":
                extra = " " + rec["error"][:160]
            elif status == "skipped":
                extra = " " + rec["reason"][:80]
            print(f"[{rec['mesh']}] {arch} x {shape}: {status}{extra}",
                  flush=True)


if __name__ == "__main__":
    main()
