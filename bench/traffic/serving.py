"""What the two serving drivers share: the server with its weights, the
warm-up of every shape a tick uses, the request seeds, and the ``correct``
comparison of every image completed in the window with the plain
reference, once the window has closed.

Request ``i`` of a run carries the seed ``base + i``, with ``base`` drawn
from the run's seed and small enough that every request seed fits the
serving API's 32-bit seeds.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import prng_key, rng
from bench.work.layers import totals


class ServingCell:
    unit = "tick"
    span = "bench.step"

    def __init__(self, run):
        self.run = run
        self.cfg, self.params_ = run.cfg, run.cell["params"]
        self.lane = run.prog.lane(self.cfg)

    def setup(self) -> None:
        cfg, run = self.cfg, self.run
        self.weights = jax.jit(functools.partial(run.ref.make_params, cfg))(
            prng_key(run.seed, 0))
        self.base = int(rng(run.seed, 3).integers(0, 1 << 30))
        self.next_i = 0
        self.server = run.prog.server(cfg, self.weights)
        self.batch = cfg["lane_batch"]
        # full ticks, then a partial one: every shape the window will use
        for n in (self.batch, self.batch, self.batch // 2 + 1):
            for _ in range(n):
                self.submit()
            self.server.run()
        self.stats0 = self.server.stats()

    def submit(self) -> int:
        rid = self.server.submit(self.lane, seed=self.base + self.next_i)
        self.next_i += 1
        return rid

    def seed_of(self, rid: int) -> int:
        return self.server.request(rid).seed

    def drain(self, rids, deadline_s: float) -> None:
        """Run the server until every request of ``rids`` has an end, for
        at most ``deadline_s`` seconds."""
        import time

        t_end = time.perf_counter() + deadline_s
        while (any(self.server.request(r).status in ("pending", "active")
                   for r in rids) and time.perf_counter() < t_end):
            self.server.step()

    def failed_of(self, rids) -> int:
        st = self.server.stats()
        if st["degraded"] > self.stats0["degraded"] or \
                st["retries"] > self.stats0["retries"]:
            return len(rids)       # a degraded lane or a retry taints all
        return sum(self.server.request(r).status != "done" for r in rids)

    def counters(self) -> dict:
        """Program counters over the window (``GenServer.stats()``)."""
        st = self.server.stats()
        return {k: st[k] - self.stats0[k]
                for k in ("device_steps", "substeps", "requests")} | {
            "batch": self.batch}

    def release(self) -> None:
        """Keep every image completed in the window, with its seed."""
        self.images = {r: np.asarray(self.server.request(r).result)
                       for r in self.window_rids}
        self.seeds = {r: self.seed_of(r) for r in self.window_rids}
        del self.server

    def check(self) -> dict:
        cfg, ref = self.cfg, self.run.ref
        f = jax.jit(functools.partial(ref.forward, cfg, precision="highest"))
        rids = sorted(self.images)
        gap = 0.0 if rids else math.inf
        for i in range(0, len(rids), self.batch):
            block = rids[i:i + self.batch]
            z = ref.latents(jnp.asarray([self.seeds[r] for r in block],
                                        jnp.int32), cfg["nz"])
            want = np.asarray(f(self.weights, z))
            for r, w in zip(block, want):
                got = self.images[r]
                if got is None or got.shape != w.shape or \
                        not np.all(np.isfinite(got)):
                    return {"image_gap": math.inf}
                gap = max(gap, float(np.max(np.abs(got - w))
                                     / max(np.max(np.abs(w)), 1e-30)))
        return {"image_gap": gap}

    def work_per_unit(self, work, peak) -> dict:
        cfg = self.cfg
        layers = work.layers(cfg["nz"], cfg["ngf"], cfg["nc"])
        b = self.batch
        return {"flops": totals(layers, b)["flops"],
                "conv_min_s": totals(layers, b, peak,
                                     work.PALLAS_KINDS)["min_seconds"]}
