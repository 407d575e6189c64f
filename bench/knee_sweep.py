#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate at
which the queue does not grow over the window.

  python bench/knee_sweep.py --workload dcgan64.burst \\
      --backlog dcgan64.backlog --fractions 0.5,0.7,0.8,0.9,1.0 \\
      --seconds 10 --seed 1

One process on the chip.  The backlog cell's window runs first and gives
the server's full-load rate; then the open-loop cell's window runs at each
fraction of that rate, each on a fresh server (the server keeps every
request it was given, so one server per rate keeps the rates apart).  Per
rate it prints one JSON line: offered and completed rate, the queue
(requests submitted and not yet done) averaged over the first and the last
fifth of the window, its growth, the time left to drain after the window,
and the latency percentiles.  The queue grows where ``growth`` exceeds one
lane batch; the knee is the highest rate below the first rate that grows.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def queue_growth(queue, seconds: float) -> tuple[float, float]:
    """Mean queue over the first and the last fifth of the window."""
    first = [q for t, q in queue if t <= seconds / 5]
    last = [q for t, q in queue if 4 * seconds / 5 <= t <= seconds]
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0
    return mean(first), mean(last)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--backlog", required=True)
    ap.add_argument("--fractions", default="0.5,0.7,0.8,0.9,1.0")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ns = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import enable_cache, prepare_process

    prepare_process()
    import jax

    from bench.run import Run

    if jax.devices()[0].platform != "tpu":
        print("knee_sweep: needs a TPU", file=sys.stderr)
        return 3
    enable_cache()

    def cell_for(name):
        run = Run(name, ns.seed, ns.seconds, False)
        cell = run.driver.Cell(run)
        with jax.default_matmul_precision(run.cfg["precision"]):
            cell.setup()
        return run, cell

    run, cell = cell_for(ns.backlog)
    with jax.default_matmul_precision(run.cfg["precision"]):
        full = cell.window(ns.seconds)["metrics"]["gen_images_per_s"]
    print(json.dumps({"backlog_images_per_s": full}), flush=True)
    del cell
    for frac in (float(f) for f in ns.fractions.split(",")):
        rate = frac * full
        run, cell = cell_for(ns.workload)
        with jax.default_matmul_precision(run.cfg["precision"]):
            res = cell.window(ns.seconds, rate)
        first, last = queue_growth(cell.queue, ns.seconds)
        end = max(t for t, _ in cell.queue)
        print(json.dumps({
            "fraction": frac, "offered_per_s": rate,
            "completed_per_s": res["metrics"]["gen_images_per_s"],
            "p95_ms": res["metrics"]["gen_latency_p95_ms"],
            "p50_ms": res["notes"]["latency_p50_ms"],
            "generator_lag_p95_ms": res["notes"]["generator_lag_p95_ms"],
            "queue_first": first, "queue_last": last,
            "growth": last - first, "drain_s": max(0.0, end - ns.seconds),
            "failed": res["failed"], "ticks": cell.units}), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
