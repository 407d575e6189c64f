#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; its configuration, driver,
program adapter, reference and work table are found by name from there
(see ``bench/common.py``).  One process: set-up (weights from the seed,
compiles, warm-up), a measured window of ``--seconds``, then the
``correct`` comparison against the plain reference.  With ``--trace 0``
the result holds the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result holds the per-layer metrics
read from the trace by ``bench/metrics/<metric>.py``.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``; ``compared``
last: each number compared with its limit).  The numbers compared are also
the last lines of stderr.  Without a TPU, with fewer chips than the cell
asks for, or outside a checkout that holds the program, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: where traces of ``--trace 1`` runs are written (inside the checkout)
TRACE_DIR = ROOT / "bench" / ".trace"


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


class Run:
    """What one run knows: its arguments and the pieces found by name."""

    def __init__(self, workload: str, seed: int, seconds: float, trace: bool,
                 spec: dict | None = None):
        from bench import common

        self.workload, self.seed = workload, seed
        self.seconds, self.trace = seconds, trace
        self.spec = spec or common.benchmark_spec()
        self.cell = common.cell_spec(workload)
        self.cfg = common.config_spec(self.cell["config"])
        self.prog = common.program(self.cfg)
        self.ref = common.reference(self.cfg)
        self.work = common.work(self.cfg)
        self.driver = common.driver(self.cell)
        self.e2e, self.per_layer = common.cell_metrics(self.spec, workload)


def prepare_process() -> None:
    """Import paths and settings every benchmark process starts with."""
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    # default tiles: a run depends on nothing outside its checkout
    os.environ["REPRO_AUTOTUNE"] = "off"


def enable_cache() -> None:
    """The persistent compilation cache (``repro.launch.compile_cache``),
    holding every program however quick to compile, so that only a
    checkout's first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(devices) -> dict:
    peaks = [s.get("peak_bytes_in_use") for s in
             (d.memory_stats() or {} for d in devices)]
    peaks = [p for p in peaks if p is not None]
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


def compared_lines(compared: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits[k]} for k, v in compared.items()}


def traced_window(run: Run, cell) -> tuple[dict, str]:
    """The window under ``jax.profiler``; returns its result and the
    ``.xplane.pb`` path."""
    import glob
    import shutil

    import jax

    out = TRACE_DIR / run.workload
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(str(out))
    try:
        res = cell.window(run.seconds)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {out}, found {paths}")
    return res, paths[0]


def per_layer_metrics(run: Run, cell, path: str, peak: dict):
    from bench import common
    from bench.trace_reduce import reduce_trace

    red = reduce_trace(path, span=cell.span)
    ctx = {"trace": red, "units": cell.units, "peak": peak,
           "work": cell.work_per_unit(run.work, peak),
           "counters": getattr(cell, "window_counters", {})}
    metrics = {}
    for m in run.per_layer:
        value = common.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, red


def execute(run: Run, devices, peak: dict | None,
            t_start: float = T_START) -> dict:
    """Set-up, window and ``correct`` of one run; returns the result line.
    ``setup_s`` counts from ``t_start`` (the process's start).  The tests
    and ``bench/control.py`` call this past the look for a chip."""
    import jax

    cell = run.driver.Cell(run)
    with jax.default_matmul_precision(run.cfg["precision"]):
        cell.setup()
        setup_s = time.perf_counter() - t_start
        if run.trace:
            res, trace_path = traced_window(run, cell)
        else:
            res = cell.window(run.seconds)
    device = device_info(devices)
    cell.release()
    compared = cell.check()
    limits = run.cell["limits"]
    correct = (set(compared) == set(limits) and res["failed"] == 0
               and all(compared[k] <= limits[k] for k in limits))
    result = {"correct": correct, "attempted": res["attempted"],
              "failed": res["failed"]}
    if run.trace:
        metrics, red = per_layer_metrics(run, cell, trace_path, peak)
        device |= {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": red["top_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"] for m in run.e2e}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in res["metrics"].items() if k in units}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}
        result["metrics"] = metrics
        result["device"] = device
    if "notes" in res:
        result["notes"] = res["notes"]
    result["compared"] = compared_lines(compared, limits)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no program under {ROOT / 'src'}: run from a checkout of the "
             f"repository")
    prepare_process()
    run = Run(ns.workload, ns.seed, ns.seconds, bool(ns.trace))

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no backend: {e}", 3)
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX's first device is {devices[0].platform}", 3)
    chips = run.cell["chips"]
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX sees {len(devices)}", 3)
    devices = devices[:chips]

    from bench.peaks import peaks

    peak = peaks(devices[0].device_kind)
    enable_cache()

    result = execute(run, devices, peak)
    for k, v in result["compared"].items():
        print(f"compared {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
