import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Perf-iteration harness (§Perf): re-lower a cell with a named variant and
report the roofline-term deltas vs baseline.

  PYTHONPATH=src python -m repro.launch.perf --arch qwen3-32b \
      --shape train_4k --variants baseline,mb1,dots

Variants compose config/step overrides; every run writes
results/perf/<arch>__<shape>__<variant>.json.
"""

import argparse
import json


VARIANTS = {
    "baseline": {},
    "mb1": {"microbatches": 1},
    "mb2": {"microbatches": 2},
    "mb4": {"microbatches": 4},
    "mb8": {"microbatches": 8},
    "mb16": {"microbatches": 16},
    "dots": {"remat_policy": "dots"},          # save dot outputs in remat
    "nothing": {"remat_policy": "nothing"},
    "noremat": {"remat": False},
    "mb1_dots": {"microbatches": 1, "remat_policy": "dots"},
    "mb2_dots": {"microbatches": 2, "remat_policy": "dots"},
    "f32opt_off": {"opt_memory_mode": "bf16"},
    "nosp": {"no_seq_sp": True},
    "mb1_nosp": {"microbatches": 1, "no_seq_sp": True},
}


def run_variant(arch: str, shape: str, variant: str, *, multi_pod: bool,
                out_dir: str = "results/perf") -> dict:
    import jax

    from repro.configs import get_config
    from repro.distributed import hlo_analysis as ha
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import lower_cell

    ov = dict(VARIANTS[variant])
    cfg = get_config(arch)
    cfg_kw = {k: v for k, v in ov.items()
              if k in ("remat", "remat_policy", "opt_memory_mode")}
    if cfg_kw:
        cfg = cfg.replace(**cfg_kw)
    mb = ov.get("microbatches")
    from repro.models import layers as _layers
    _layers.DISABLE_SEQ_SP = bool(ov.get("no_seq_sp", False))

    mesh = make_production_mesh(multi_pod=multi_pod)
    lowered, _ = lower_cell(cfg, shape, mesh, microbatches=mb)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    a = ha.analyze(compiled.as_text())
    terms = ha.roofline_terms(a, ha.V5E_KIND)    # the pods' chip
    rec = {
        "arch": arch, "shape": shape, "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mem_gb": round((mem.argument_size_in_bytes + mem.temp_size_in_bytes
                         + mem.output_size_in_bytes
                         - mem.alias_size_in_bytes) / 2**30, 3),
        "flops_per_chip": a.flops,
        "hbm_bytes_per_chip": a.hbm_bytes,
        "collective_wire_bytes": a.collective_wire_bytes,
        "collectives": {k: {"count": v.count, "wire": v.wire_bytes}
                        for k, v in a.collectives.items()},
        "roofline": terms,
        "bound": max(terms, key=terms.get).replace("_s", ""),
        "step_time_overlap_s": max(terms.values()),
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(f"{out_dir}/{arch}__{shape}__{rec['mesh']}__{variant}.json",
              "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variants", default="baseline")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    base = None
    for v in args.variants.split(","):
        r = run_variant(args.arch, args.shape, v, multi_pod=args.multi_pod)
        t = r["roofline"]
        line = (f"{v:12s} mem={r['mem_gb']:8.2f}GB "
                f"comp={t['compute_s']:7.2f}s mem_t={t['memory_s']:7.2f}s "
                f"coll={t['collective_s']:7.2f}s bound={r['bound']:10s} "
                f"overlap_step={r['step_time_overlap_s']:7.2f}s")
        if base is None:
            base = r
        else:
            d = r["step_time_overlap_s"] / base["step_time_overlap_s"] - 1
            line += f"  vs-base {100*d:+.1f}%"
        print(line, flush=True)


if __name__ == "__main__":
    main()
