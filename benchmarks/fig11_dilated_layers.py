"""Fig. 11 reproduction: per-dilation-rate speedup + efficiency vs ideal
sparse (paper: 83%-98%, higher speedup for larger D), plus an executable
cross-check that the decomposed convolution's MAC skip matches the model.

Costs BOTH workloads: ENet (the paper's test case) and ESPNet (the spatial
pyramid of dilated convolutions — Mehta et al. 2018), whose every ESP module
runs the whole band D = 1, 3, 7, 15 on narrow stride-1 branches.
"""

from __future__ import annotations

import time

from repro.core import cycle_model as cm
from repro.core import dilated as dil
from repro.core.enet_spec import dilated_layer_sets, enet_512_layers
from repro.core.espnet_spec import espnet_layers

WORKLOADS = {"enet": enet_512_layers, "espnet": espnet_layers}


def _epilogue_deltas() -> list[tuple]:
    """Measured fused-vs-unfused epilogue delta on the dilated engine
    (ESP-branch geometry; pallas — interpret-mode relative on CPU; shared
    measurement harness: ``benchmarks.kernel_bench``)."""
    from benchmarks.kernel_bench import epilogue_delta_rows
    from repro.kernels import ops
    from repro.kernels.epilogue import EpilogueSpec

    xs, ws = (1, 16, 16, 16), (3, 3, 16, 16)
    cases = [
        (f"epilogue_d{d}",
         lambda x, w, d=d, **ep: ops.dilated_conv2d(x, w, d, **ep), xs, ws)
        for d in (2, 8)
    ]
    return epilogue_delta_rows("fig11.", cases, iters=5,
                               spec=EpilogueSpec(bn=True, prelu=True))


def run(csv: bool = False, workloads: tuple[str, ...] = ("enet", "espnet")
        ) -> list[tuple]:
    rows = []
    for wl in workloads:
        layers = WORKLOADS[wl]()
        for D, ls in sorted(dilated_layer_sets(layers).items()):
            # per-group timer: a run-wide t0 would accumulate earlier
            # groups' cost into later rows' us_per_call column
            t0 = time.perf_counter()
            dense = sum(cm.cycles_ideal_dense(l) for l in ls)
            sparse = sum(cm.cycles_ideal_sparse(l) for l in ls)
            ours = sum(cm.cycles_our_decomposed(l) for l in ls)
            # executable cross-check from the layer set's own geometry
            # (input extent s*h_out, which a strided branch would exercise
            # through the output-class MAC accounting)
            mac_ratio = (
                sum(dil.macs_dense(l.stride * l.h_out, l.stride * l.w_out,
                                   l.cin, l.cout, l.kh, l.D + 1, l.stride)
                    for l in ls)
                / sum(dil.macs_decomposed(l.stride * l.h_out,
                                          l.stride * l.w_out, l.cin, l.cout,
                                          l.kh, l.D + 1, l.stride)
                      for l in ls))
            us = (time.perf_counter() - t0) * 1e6
            tag = f"fig11.{wl}.D{D}"
            rows.append((f"{tag}.speedup_x", us, f"{dense / ours:.2f}"))
            rows.append((f"{tag}.eff_vs_sparse_pct", us,
                         f"{100 * sparse / ours:.1f}"))
            rows.append((f"{tag}.mac_skip_ratio", us, f"{mac_ratio:.2f}"))
    rows += _epilogue_deltas()
    if not csv:
        print("== Fig. 11: dilated layers (ENet L1..L4 <-> D = 1,3,7,15; "
              "ESPNet pyramid D = 1,3,7,15 in every module) ==")
        print("   paper: efficiency 83%..98%, falling with D; speedup rising")
        for name, _, derived in rows:
            print(f"  {name:36s} {derived}")
    return rows


if __name__ == "__main__":
    run()
