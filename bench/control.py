#!/usr/bin/env python3
"""The readings that set each limit of ``correct``: the program on many
seeds, and its control on some, in one process.

  python bench/control.py --workload <cell> --seeds 1,2,3 \\
      --control-seeds 1,2,3 --seconds 2 [--out control.jsonl]

The program is the cell's timed path as ``bench/run.py`` drives it.  The
control is the plain reference put in the program's place, computed at the
nearest precision below the one the configuration states: ``"high"``
(three bfloat16 passes) for float32 at ``"highest"``.  The program has no
such path of its own: its Pallas kernels accept only the default and the
highest precision.  Each run prints one JSON line with the numbers
compared; the limits in ``bench/workloads/<cell>.json`` are set between
the largest program reading and the smallest control reading.

:func:`control_program` is also what ``bench/tests`` put in the program's
place, at the highest precision, to drive a run on the CPU.
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: the precision below each one a configuration can state
LOWER = {"highest": "high"}


class _Request:
    def __init__(self, rid: int, seed: int):
        self.rid, self.seed = rid, seed
        self.status, self.result = "pending", None


class RefServer:
    """A server with ``GenServer``'s calls, whose ticks run the plain
    reference: one lane, ``lane_batch`` slots, first come first served."""

    def __init__(self, ref, cfg: dict, params: dict, precision: str):
        import jax

        self.ref, self.cfg, self.params = ref, cfg, params
        self.batch = cfg["lane_batch"]
        self.f = jax.jit(functools.partial(ref.forward, cfg,
                                           precision=precision))
        self.pending: collections.deque = collections.deque()
        self.reqs: dict[int, _Request] = {}
        self.ticks = self.slots = 0

    def submit(self, lane: str, *, seed: int) -> int:
        req = _Request(len(self.reqs), seed)
        self.reqs[req.rid] = req
        self.pending.append(req)
        return req.rid

    def step(self):
        import jax.numpy as jnp
        import numpy as np

        take = [self.pending.popleft()
                for _ in range(min(self.batch, len(self.pending)))]
        if not take:
            return []
        z = self.ref.latents(jnp.asarray([r.seed for r in take], jnp.int32),
                             self.cfg["nz"])
        z = jnp.pad(z, ((0, self.batch - len(take)), (0, 0)))
        imgs = np.asarray(self.f(self.params, z))
        for r, img in zip(take, imgs):
            r.result, r.status = img, "done"
        self.ticks += 1
        self.slots += len(take)
        return take

    def run(self):
        while self.pending:
            self.step()

    def request(self, rid: int):
        return self.reqs[rid]

    def stats(self) -> dict:
        return {"device_steps": self.ticks, "substeps": self.slots,
                "requests": sum(r.status == "done"
                                for r in self.reqs.values()),
                "degraded": 0.0, "retries": 0.0}


def control_program(run, precision: str):
    """A program adapter whose every entry is the reference at
    ``precision``, in the place of ``run.prog``."""
    import types

    import jax

    ref, prog = run.ref, run.prog
    ns = types.SimpleNamespace()
    if hasattr(prog, "lane"):
        ns.lane = prog.lane
        ns.server = lambda cfg, params: RefServer(ref, cfg, params, precision)
        return ns
    ns.forward = lambda cfg: jax.jit(functools.partial(
        ref.forward, cfg, precision=precision))

    def train(cfg):
        opt, rows = cfg["optimizer"], run.cell["params"]["ref_rows"]

        def step(state, batch):
            params, opt_state = state
            loss, g = ref.grads(cfg, params, batch["image"], batch["label"],
                                rows=rows, precision=precision)
            params, opt_state, _ = ref.adamw(params, g, opt_state, opt)
            return (params, opt_state), {"loss": loss, "skipped": 0.0}

        return (lambda params: (params, ref.adamw_init(params))), step

    ns.train = train
    ns.params_of = lambda state: state[0]
    ns.first_moment = lambda state: state[1][1]
    return ns


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    ns = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import enable_cache, prepare_process

    prepare_process()
    import jax

    from bench.run import Run, execute

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    enable_cache()
    out = open(ns.out, "a") if ns.out else None
    seeds = [("program", int(s)) for s in ns.seeds.split(",") if s]
    seeds += [("control", int(s)) for s in ns.control_seeds.split(",") if s]
    for side, seed in seeds:
        run = Run(ns.workload, seed, ns.seconds, False)
        if side == "control":
            run.prog = control_program(run, LOWER[run.cfg["precision"]])
        t0 = time.perf_counter()
        res = execute(run, devices[:run.cell["chips"]], None, t0)
        line = json.dumps({"workload": ns.workload, "side": side,
                           "seed": seed, "correct": res["correct"],
                           "compared": res["compared"],
                           "metrics": res["metrics"],
                           "failed": res["failed"],
                           "wall_s": time.perf_counter() - t0})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
