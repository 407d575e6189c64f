"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``BENCH_<rev>.json`` — the
per-kernel wall times, the fused/unfused and tuned/default ratio tables,
and the calibrated cycles->us prediction-error report
(``repro.core.calibrate``) — is written by default in ``--smoke`` mode and
under ``--emit-json`` otherwise, so the perf trajectory is machine-tracked
from the blocking tier-1 CI job (``benchmarks/perf_gate.py`` fails the
build on drift against the committed baseline; the non-blocking slow job
emits the full-size variant).  ``--no-json`` suppresses the file.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time


def _git_rev() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              capture_output=True, text=True, check=True,
                              timeout=10).stdout.strip()
    except Exception:
        return "unknown"


def _ratios(rows: list[tuple]) -> dict:
    """Pull the ``key=value`` ratio annotations out of the derived column."""
    out: dict[str, dict[str, float]] = {"fused_unfused": {}, "tuned_default": {}}
    for name, _, derived in rows:
        for part in str(derived).split(","):
            if "=" not in part:
                continue
            k, _, v = part.partition("=")
            try:
                val = float(v.rstrip("x"))
            except ValueError:
                continue
            if k in out:
                out[k][name] = val
    return out


#: derived keys of the measured ``serve.*`` rows that form the serving
#: latency trajectory (``perf_gate.py`` gates them at wall-ratio tolerance);
#: restore/degraded keys come from the fault-tolerance rows (DESIGN.md §11)
_SERVE_KEYS = ("p50_us", "p99_us", "dispatches_per_image",
               "restore_us", "recovered_imgs_per_s", "degraded_imgs_per_s",
               "imgs_per_s")


def _serve_latency(rows: list[tuple]) -> dict:
    """Latency-percentile section: p50/p99 and dispatch amortisation of the
    measured serving drains, keyed ``serve.<row>`` -> metric."""
    out: dict[str, dict[str, float]] = {}
    for name, _, derived in rows:
        if not name.startswith("serve."):
            continue
        for part in str(derived).split(","):
            k, _, v = part.partition("=")
            if k in _SERVE_KEYS:
                try:
                    out.setdefault(name, {})[k] = float(v)
                except ValueError:
                    continue
    return out


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit-json", action="store_true",
                    help="write BENCH_<rev>.json next to the CSV output "
                         "(implied by --smoke)")
    ap.add_argument("--no-json", action="store_true",
                    help="never write BENCH_<rev>.json (overrides both)")
    ap.add_argument("--smoke", action="store_true",
                    help="pass smoke mode to the kernel microbenchmarks; "
                         "emits BENCH_<rev>.json by default")
    ap.add_argument("--calibrate-backends", default="xla",
                    help="comma list of backends the calibration capture "
                         "times (default xla; add pallas on accelerators)")
    ns = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (enet_roofline, fig10_enet_speedup,
                            fig11_dilated_layers, fig12_transposed_layers,
                            kernel_bench, mixed_precision, roofline,
                            serve_bench, table1_throughput)

    all_rows = []
    print("name,us_per_call,derived")
    for mod in (fig10_enet_speedup, fig11_dilated_layers,
                fig12_transposed_layers, table1_throughput, kernel_bench,
                serve_bench, enet_roofline, roofline):
        kw = ({"smoke": True}
              if (ns.smoke and mod in (kernel_bench, serve_bench)) else {})
        for name, us, derived in mod.run(csv=True, **kw):
            print(f"{name},{us:.1f},{derived}")
            all_rows.append((name, us, derived))

    # bf16/fp32 wall ratios + analytic-policy agreement (DESIGN.md §12);
    # measured once, feeding both the CSV stream and the JSON section
    mp_section = mixed_precision.section(smoke=ns.smoke)
    for name, us, derived in mixed_precision.rows(mp_section):
        print(f"{name},{us:.1f},{derived}")
        all_rows.append((name, us, derived))

    if (ns.emit_json or ns.smoke) and not ns.no_json:
        import jax

        from repro.core import calibrate

        backends = tuple(b for b in ns.calibrate_backends.split(",") if b)
        rev = _git_rev()
        payload = {
            "rev": rev,
            "generated_unix": time.time(),
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            # sharded serve.mesh_d<N> rows are only comparable at equal
            # mesh size; perf_gate skips them when this differs
            "device_count": len(jax.devices()),
            "jax_version": jax.__version__,
            "smoke": ns.smoke,
            "rows": [{"name": n, "us_per_call": round(u, 1), "derived": d}
                     for n, u, d in all_rows],
            "ratios": _ratios(all_rows),
            # measured serving p50/p99 + dispatches/image (DESIGN.md §9) —
            # gated by perf_gate.py like the wall-ratio families
            "serve_latency": _serve_latency(all_rows),
            # bf16/fp32 wall ratio per engine + analytic tiling policy vs
            # exhaustive sweep (DESIGN.md §12) — wall-class gate family
            "mixed_precision": mp_section,
            # calibrated cycles->us fit + prediction-error report per
            # (engine kind, backend, device kind) — the trajectory the
            # perf gate tracks (DESIGN.md §10)
            "calibration": calibrate.capture_and_fit(
                smoke=ns.smoke, backends=backends),
        }
        path = f"BENCH_{rev}.json"
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
        # stderr: stdout is the CSV stream (CI redirects it into bench.csv)
        print(f"wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
