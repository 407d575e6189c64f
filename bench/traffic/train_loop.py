"""A training job: the repository's jitted train step at the
configuration's batch, fed from a pool of ``pool`` distinct batches (frames
and labels) made on the device from the seed in set-up, in an order drawn
from the seed.  One step is in flight while the host waits on the last
one's loss, as a loop that logs its loss does.

Set-up compiles the step and drives the same object through its first
``check_steps`` steps (all on distinct rows); the window goes on from
there.  ``correct`` follows those steps with the plain reference once the
window has closed: each step's loss, the first gradient as AdamW got it
(its first moment after one step over ``1 - b1``) and the parameters'
change after the last checked step, leaf by leaf.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import prng_key, rng
from bench.work.layers import totals


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree.leaves(tree)]


@jax.jit
def _delta_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x - y)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def norm_gap(got, ref, keep=None) -> float:
    """Worst leaf: |norm - reference norm| over the larger of that leaf's
    reference norm and the median leaf's."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        got, ref = got[keep], ref[keep]
    if not np.all(np.isfinite(got)):
        return float("inf")
    scale = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(got - ref) / np.maximum(scale, 1e-30)))


class Cell:
    unit = "step"
    span = "bench.train_step"

    def __init__(self, run):
        self.run = run
        self.cfg, self.params_ = run.cfg, run.cell["params"]

    def setup(self) -> None:
        cfg, run, p = self.cfg, self.run, self.params_
        self.params0 = jax.jit(functools.partial(run.ref.make_params, cfg))(
            prng_key(run.seed, 0))
        b = cfg["train_batch"]
        shape = (b, cfg["height"], cfg["width"], cfg["in_channels"])

        @jax.jit
        def batches(key):
            out = []
            for k in jax.random.split(key, p["pool"]):
                ki, kl = jax.random.split(k)
                out.append({"image": jax.random.normal(ki, shape, jnp.float32),
                            "label": jax.random.randint(
                                kl, shape[:3], 0, cfg["num_classes"],
                                jnp.int32)})
            return tuple(out)

        self.batches = batches(prng_key(run.seed, 1))
        self.order = [int(i) for i in rng(run.seed, 2).permutation(p["pool"])]
        init_state, self.step = run.prog.train(cfg)
        state = init_state(self.params0)
        self.losses = []
        for k in range(p["check_steps"]):
            state, m = self.step(state, self.batches[self.order[k]])
            self.losses.append(float(m["loss"]))
            if k == 0:
                self.mu_norms = np.asarray(jax.device_get(_leaf_norms(
                    run.prog.first_moment(state))))
        self.delta_norms = np.asarray(jax.device_get(_delta_norms(
            run.prog.params_of(state), self.params0)))
        self.state = state

    def window(self, seconds: float) -> dict:
        state, order, n = self.state, self.order, len(self.order)
        k = self.params_["check_steps"]
        done, prev, skipped = 0, None, []
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation(self.span):
                state, m = self.step(state, self.batches[order[k % n]])
            k += 1
            skipped.append(m["skipped"])
            if prev is not None:
                prev.block_until_ready()
                done += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            prev = m["loss"]
        prev.block_until_ready()
        done += 1
        elapsed = time.perf_counter() - t0
        self.state, self.units = state, done
        failed = int(sum(float(s) for s in skipped))
        return {"metrics": {"train_step_ms": 1000.0 * elapsed / done},
                "attempted": done, "failed": failed}

    def release(self) -> None:
        keep = {self.order[k] for k in range(self.params_["check_steps"])}
        self.batches = {i: b for i, b in enumerate(self.batches) if i in keep}
        del self.state, self.step

    def check(self) -> dict:
        cfg, ref = self.cfg, self.run.ref
        opt = cfg["optimizer"]
        params, state = self.params0, ref.adamw_init(self.params0)
        losses = []
        for k in range(self.params_["check_steps"]):
            b = self.batches[self.order[k]]
            loss, g = ref.grads(cfg, params, b["image"], b["label"],
                                rows=self.params_["ref_rows"],
                                precision="highest")
            params, state, g_clipped = ref.adamw(params, g, state, opt)
            losses.append(float(loss))
            if k == 0:
                g_ref = np.asarray(jax.device_get(_leaf_norms(g_clipped)))
        d_ref = np.asarray(jax.device_get(_delta_norms(params, self.params0)))
        losses_ref = np.asarray(losses)
        loss_gap = float(np.max(np.abs(np.asarray(self.losses) - losses_ref)
                                / np.abs(losses_ref)))
        if not np.all(np.isfinite(self.losses)):
            loss_gap = float("inf")
        # leaves whose reference gradient is nought to rounding move under
        # Adam by round-off alone: they are left out of the change
        moved = g_ref >= self.params_["moved_leaf_share"] * np.median(g_ref)
        return {"loss_gap": loss_gap,
                "grad_gap": norm_gap(self.mu_norms / (1.0 - opt["b1"]), g_ref),
                "update_gap": norm_gap(self.delta_norms, d_ref, moved)}

    def work_per_unit(self, work, peak) -> dict:
        layers = work.layers(self.cfg["num_classes"])
        b = self.cfg["train_batch"]
        fwd = totals(layers, b, peak, work.PALLAS_KINDS)
        # useful training work is forward, input gradient and weight
        # gradient; the Pallas kernels run the forward and the input
        # gradient (weight gradients are XLA tap correlations)
        return {"flops": 3.0 * totals(layers, b)["flops"],
                "conv_min_s": 2.0 * fwd["min_seconds"]}
