"""Open loop: requests arrive on a schedule from
:mod:`bench.traffic.gamma_arrivals` at ``rate_per_s`` with inter-arrival
coefficient of variation ``cv``, whatever the server does.  Each request is
timed from when it was due to when its image is on the host; after the
window arrivals stop and what is left drains (for at most ``drain_s``).
A request that never completes counts as missing every latency limit.

Reported: the 95th percentile of the latency of every request due in the
window, and the images completed inside the window per second.  How late
the generator ran (submit time past due time) is printed beside them.
"""

from __future__ import annotations

import math
import time

import jax

from bench.common import percentile
from bench.traffic import gamma_arrivals
from bench.traffic.serving import ServingCell


class Cell(ServingCell):

    def window(self, seconds: float, rate: float | None = None) -> dict:
        """``rate`` overrides the cell's (``bench/knee_sweep.py``)."""
        srv, p = self.server, self.params_
        due = gamma_arrivals.schedule(rate or p["rate_per_s"], p["cv"],
                                      seconds, self.run.seed)
        n = len(due)
        rid_due: dict[int, float] = {}
        lat: dict[int, float] = {}
        lag, queue = [], []
        i, ticks, done_in_window = 0, 0, 0
        t0 = time.perf_counter()
        t_stop = t0 + seconds + p["drain_s"]
        while True:
            now = time.perf_counter() - t0
            while i < n and due[i] <= now:
                rid_due[self.submit()] = due[i]
                lag.append(now - due[i])
                i += 1
            if len(lat) < len(rid_due):
                with jax.profiler.TraceAnnotation(self.span):
                    finished = srv.step()
                t_done = time.perf_counter() - t0
                ticks += 1
                for req in finished:
                    if req.rid in rid_due:
                        lat[req.rid] = t_done - rid_due[req.rid]
                        done_in_window += t_done <= seconds
                queue.append((t_done, len(rid_due) - len(lat)))
            elif i < n:
                time.sleep(max(0.0, min(due[i] - now, 0.001)))
            else:
                break
            if time.perf_counter() > t_stop:
                break
        self.units = ticks
        self.window_counters = self.counters()
        self.window_rids = sorted(lat)
        self.queue = queue
        lats = [lat.get(r, math.inf) for r in rid_due]
        failed = self.failed_of(list(rid_due))
        return {"metrics": {
                    "gen_images_per_s": done_in_window / seconds,
                    "gen_latency_p95_ms": 1000.0 * percentile(lats, 95)},
                "attempted": n, "failed": failed,
                "notes": {"generator_lag_p95_ms":
                          1000.0 * percentile(lag, 95),
                          "latency_p50_ms": 1000.0 * percentile(lats, 50),
                          "offered_per_s": n / seconds}}
