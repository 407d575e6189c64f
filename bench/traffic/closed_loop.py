"""Closed-loop frames: one camera stream sends its next frame when the
last one's logits are back (``block_until_ready``), at the configuration's
inference batch.  A pool of ``pool`` distinct frames is made on the device
from the seed and sent in an order drawn from the seed, cycled.

``correct``: the logits of the last frame of each pool entry in the window
(``pool`` distinct frames, the window's own outputs) against the plain
reference, one frame at a time once the window has closed.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import prng_key, rel_gap, rng
from bench.work.layers import totals


class Cell:
    unit = "frame"
    span = "bench.frame"

    def __init__(self, run):
        self.run = run
        self.cfg, self.params_ = run.cfg, run.cell["params"]

    def setup(self) -> None:
        cfg, run = self.cfg, self.run
        self.params = jax.jit(functools.partial(run.ref.make_params, cfg))(
            prng_key(run.seed, 0))
        n, b = self.params_["pool"], cfg["infer_batch"]
        shape = (b, cfg["height"], cfg["width"], cfg["in_channels"])

        @jax.jit
        def frames(key):
            return tuple(jax.random.normal(k, shape, jnp.float32)
                         for k in jax.random.split(key, n))

        self.frames = frames(prng_key(run.seed, 1))
        self.order = [int(i) for i in rng(run.seed, 2).permutation(n)]
        self.fwd = run.prog.forward(cfg)
        for i in self.order[:2]:
            jax.block_until_ready(self.fwd(self.params, self.frames[i]))

    def window(self, seconds: float) -> dict:
        outs: dict[int, jax.Array] = {}
        n = len(self.order)
        done, t0 = 0, time.perf_counter()
        while True:
            i = self.order[done % n]
            with jax.profiler.TraceAnnotation(self.span):
                y = self.fwd(self.params, self.frames[i])
                y.block_until_ready()
            outs[i] = y
            done += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        self.outs = outs
        self.units = done
        frames = done * self.cfg["infer_batch"]
        return {"metrics": {"seg_frames_per_s": frames / elapsed},
                "attempted": frames, "failed": 0}

    def release(self) -> None:
        self.outs = {i: np.asarray(y) for i, y in self.outs.items()}
        del self.fwd

    def check(self) -> dict:
        cfg, ref = self.cfg, self.run.ref
        f = jax.jit(functools.partial(ref.forward, cfg, precision="highest"))
        gap = max(rel_gap(y, f(self.params, self.frames[i]))
                  for i, y in sorted(self.outs.items()))
        return {"logit_gap": gap}

    def work_per_unit(self, work, peak) -> dict:
        layers = work.layers(self.cfg["num_classes"])
        b = self.cfg["infer_batch"]
        return {"flops": totals(layers, b)["flops"],
                "conv_min_s": totals(layers, b, peak,
                                     work.PALLAS_KINDS)["min_seconds"]}

