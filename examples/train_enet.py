"""End-to-end driver: train ENet on synthetic Cityscapes-like data with every
dilated/transposed convolution running through the paper's decomposition.

  PYTHONPATH=src python examples/train_enet.py --steps 200 --hw 64

``--backend pallas`` trains through the fused Pallas engine end to end: the
forward runs the decomposed kernels and the backward runs their custom VJPs
(input-gradients re-enter the engine through the adjoint symmetry, weight
gradients are tap-gather correlations — DESIGN.md §6).

(~100M-MAC-scale model; a few hundred steps on CPU at --hw 64.  The pallas
backend on a CPU host runs in interpret mode — use small --steps/--hw there.)
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import SegDataPipeline
from repro.launch import train_recipes
from repro.launch.compile_cache import enable_compile_cache
from repro.models import enet
from repro.optim import cosine_schedule


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--hw", type=int, default=64)
    ap.add_argument("--classes", type=int, default=19)
    ap.add_argument("--lr", type=float, default=5e-4)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--backend", choices=("xla", "pallas"), default="xla",
                    help="execution engine for every conv (fwd AND bwd)")
    ap.add_argument("--dtype", choices=("fp32", "bf16"), default="fp32",
                    help="compute dtype of the forward/backward activations; "
                         "bf16 trains through the mixed-precision recipe "
                         "(fp32 masters + dynamic loss scaling, DESIGN.md "
                         "§12)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized run (caps steps/batch/hw)")
    ap.add_argument("--naive", action="store_true",
                    help="run the zero-laden baseline (no decomposition; "
                         "xla backend only)")
    args = ap.parse_args()
    decomposed = not args.naive
    if args.naive and args.backend == "pallas":
        ap.error("--naive has no pallas kernels; use --backend xla")
    if args.smoke:
        args.steps = min(args.steps, 3)
        args.batch = min(args.batch, 1)
        args.hw = min(args.hw, 16)
        args.log_every = 1

    enable_compile_cache()
    params = enet.init_params(jax.random.PRNGKey(0), args.classes)
    pipe = SegDataPipeline(args.batch, hw=args.hw, classes=args.classes)
    cd = "bf16" if args.dtype == "bf16" else None

    # the recipe: fp32 masters + AdamW + dynamic loss scaling; bf16 compute
    # when asked (DESIGN.md §12)
    state = train_recipes.init_state(params)
    train_step = train_recipes.make_train_step(
        "enet", backend=args.backend, decomposed=decomposed,
        compute_dtype=cd, weight_decay=1e-4,
        lr=lambda t: cosine_schedule(t, args.steps // 10, args.steps,
                                     args.lr))

    losses = []
    for step in range(args.steps):
        b = pipe.batch_at(step)
        t0 = time.time()
        state, m = train_step(state, {"image": jnp.asarray(b["image"]),
                                      "label": jnp.asarray(b["label"])})
        losses.append(float(m["loss"]))
        if step % args.log_every == 0:
            print(f"step {step:4d} loss {losses[-1]:.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} "
                  f"dt {(time.time()-t0)*1e3:.0f}ms", flush=True)
        if not np.isfinite(losses[-1]):
            raise SystemExit(f"non-finite loss at step {step}")

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"\nloss: first10={first:.4f} last10={last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    # pixel accuracy on a fresh batch
    b = pipe.batch_at(10_000)
    pred = jnp.argmax(enet.forward(state.params, jnp.asarray(b["image"]),
                                   decomposed=decomposed,
                                   backend=args.backend,
                                   compute_dtype=cd), -1)
    acc = float(jnp.mean(pred == jnp.asarray(b["label"])))
    print(f"pixel accuracy on held-out batch: {acc:.3f} "
          f"(chance = {1.0 / args.classes:.3f})")


if __name__ == "__main__":
    main()
