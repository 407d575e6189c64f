"""Offline backlog: before every tick the queue is topped up to
``queue_depth`` lane batches, so it never empties and every tick runs full.
Judged on the images completed per second of the window.

Requests still queued when the window closes are served after it; every
request submitted before the close is attempted, and ``correct`` compares
a sample of those completed in the window, drawn from the seed.
"""

from __future__ import annotations

import time

import jax

from bench.traffic.serving import ServingCell


class Cell(ServingCell):

    def window(self, seconds: float) -> dict:
        srv, depth = self.server, self.params_["queue_depth"] * self.batch
        rids, outstanding, done = [], 0, 0
        ticks, t0 = 0, time.perf_counter()
        while True:
            while outstanding < depth:
                rids.append(self.submit())
                outstanding += 1
            with jax.profiler.TraceAnnotation(self.span):
                finished = srv.step()
            ticks += 1
            outstanding -= len(finished)
            done += len(finished)
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        self.units = ticks
        self.window_counters = self.counters()
        self.window_rids = [r for r in rids
                            if srv.request(r).status == "done"]
        self.drain(rids, self.params_["drain_s"])
        return {"metrics": {"gen_images_per_s": done / elapsed},
                "attempted": len(rids), "failed": self.failed_of(rids)}
