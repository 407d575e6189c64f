"""ENet on the TPU roofline: naive zero-laden execution vs the paper's
decomposition, measured on the *compiled HLO* (FLOPs/bytes from the
loop-aware analyzer) — the XLA-level counterpart of Fig. 10.

This is the cell most representative of the paper's technique; §Perf
hillclimbs it (ragged -> phase-batched -> fused stitching).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.distributed.hlo_analysis import V5E_KIND, analyze, roofline_terms


def _enet_flops(decomposed: bool, batch: int = 1, hw: int = 512):
    from repro.models import enet

    params = jax.eval_shape(
        lambda k: enet.init_params(k, 19), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((batch, hw, hw, 3), jnp.float32)
    lowered = jax.jit(
        lambda p, x: enet.forward(p, x, decomposed=decomposed)).lower(
            params, x)
    return analyze(lowered.compile().as_text())


def run(csv: bool = False) -> list[tuple]:
    rows = []
    t0 = time.perf_counter()
    naive = _enet_flops(False)
    dec = _enet_flops(True)
    us = (time.perf_counter() - t0) * 1e6

    cut = 100.0 * (1 - dec.flops / naive.flops)
    rows.append(("enet_hlo.naive_gflops", us, f"{naive.flops/1e9:.2f}"))
    rows.append(("enet_hlo.decomposed_gflops", us, f"{dec.flops/1e9:.2f}"))
    rows.append(("enet_hlo.flop_cut_pct", us,
                 f"{cut:.1f} (paper cycle cut: 87.8)"))
    rows.append(("enet_hlo.flop_speedup_x", us,
                 f"{naive.flops/dec.flops:.2f} (paper: 8.2)"))
    tn, td = roofline_terms(naive, V5E_KIND), roofline_terms(dec, V5E_KIND)
    for k in ("compute_s", "memory_s"):
        rows.append((f"enet_hlo.naive_{k}", us, f"{tn[k]*1e3:.3f} ms"))
        rows.append((f"enet_hlo.dec_{k}", us, f"{td[k]*1e3:.3f} ms"))
    bound_n = "compute" if tn["compute_s"] > tn["memory_s"] else "memory"
    bound_d = "compute" if td["compute_s"] > td["memory_s"] else "memory"
    rows.append(("enet_hlo.naive_bound", us, bound_n))
    rows.append(("enet_hlo.dec_bound", us, bound_d))

    if not csv:
        print("== ENet @512x512 compiled-HLO roofline (1 v5e chip) ==")
        for name, _, derived in rows:
            print(f"  {name:30s} {derived}")
    return rows


if __name__ == "__main__":
    run()
