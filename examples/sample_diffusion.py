"""Serve diffusion sampling requests through the batched generative server.

Drives :class:`repro.launch.serve_gen.GenServer` — the continuous-batching
DDIM loop over the U-Net decoder denoiser (DESIGN.md §9) — with a queue of
requests at *mixed* step budgets, then checks the served output of one
request against an unbatched reference sampling loop (the issue's 1e-5
parity bar: mixed-timestep batching must not change any request's result),
and prints the cycle-model steady-state serving table (decomposed vs naive
array schedule) for the generative workloads.

  PYTHONPATH=src python examples/sample_diffusion.py
  PYTHONPATH=src python examples/sample_diffusion.py --backend pallas --smoke
  PYTHONPATH=src python examples/sample_diffusion.py --smoke   # CI widths
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.core import cycle_model as cm
from repro.core.gen_spec import GEN_WORKLOADS
from repro.launch.serve_gen import GenServer, reference_sample
from repro.models import unet_decoder


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--steps", default="8,5,3",
                    help="comma list of DDIM step budgets, cycled")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--backend", default="xla", choices=("xla", "pallas"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny widths + short trajectories (CI)")
    ns = ap.parse_args(argv)
    if ns.smoke:
        ns.requests, ns.steps = min(ns.requests, 5), "4,2,3"

    # interpret-mode pallas on CPU needs tiny widths to stay tractable —
    # same gate as examples/generate_dcgan.py
    small = ns.smoke or (ns.backend == "pallas"
                         and jax.default_backend() == "cpu")
    widths, hw = ((8, 8), 4) if small else ((32, 16, 8), 4)
    if small and not ns.smoke:
        print("[sample_diffusion] pallas on a CPU backend runs interpret "
              "mode: smoke widths, not the demo ones")
    step_list = [int(s) for s in ns.steps.split(",")]

    params = unet_decoder.init_denoiser_params(
        jax.random.PRNGKey(ns.seed), widths=widths)
    server = GenServer(batch=ns.batch, backend=ns.backend,
                       unet_widths=widths, unet_hw=hw,
                       params={"unet_dec": params})
    reqs = {}
    for i in range(ns.requests):
        steps = step_list[i % len(step_list)]
        reqs[server.submit("unet_dec", steps=steps, seed=ns.seed + i)] = steps
    images = server.run()
    st = server.stats()
    size = hw * 2 ** len(widths)
    print(f"served {st['requests']:.0f} requests (steps "
          f"{sorted(set(reqs.values()))}) on backend={ns.backend}: "
          f"{size}x{size} images, {st['ticks']:.0f} ticks / "
          f"{st['device_steps']:.0f} device steps, "
          f"{st['images_per_s']:.2f} img/s, mean queue wait "
          f"{st['mean_wait_ticks']:.1f} ticks")

    # parity: the request with the LONGEST trajectory lived alongside the
    # most churn (neighbours completed and were replaced mid-flight), so it
    # is the strongest witness that mixed-timestep batching is lossless
    rid = max(reqs, key=lambda r: reqs[r])
    ref = reference_sample(params, steps=reqs[rid], seed=ns.seed + rid,
                           image_size=size, backend=ns.backend)
    dev = float(np.abs(images[rid] - ref).max())
    print(f"max deviation served-vs-unbatched reference "
          f"(request {rid}, {reqs[rid]} steps): {dev:.2e} (bar: 1e-5)")
    assert dev <= 1e-5, dev

    print("\n== cycle model: steady-state serving on the paper's array "
          "(decomposed vs naive) ==")
    hdr = (f"{'workload':<10} {'steps':>5} {'img/s ours':>11} "
           f"{'img/s naive':>12} {'speedup':>8} {'latency ms':>11}")
    print(hdr + "\n" + "-" * len(hdr))
    for name, fn in GEN_WORKLOADS.items():
        steps = 25 if name == "unet_dec" else 1
        rep = cm.serve_report(fn(), steps=steps, batch=ns.batch)
        print(f"{name:<10} {steps:>5} {rep['images_per_s_ours']:>11.1f} "
              f"{rep['images_per_s_naive']:>12.1f} "
              f"{rep['serve_speedup_vs_naive']:>7.2f}x "
              f"{rep['latency_ms_ours']:>11.1f}")


if __name__ == "__main__":
    main()
