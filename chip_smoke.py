#!/usr/bin/env python3
"""Bring-up smoke of the decomposition engine on TPU.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the multi-chip paths on four chips

One chip: ENet at 512x512 with 19 classes runs inference and three
training steps through the compiled Pallas engines, and a ``GenServer``
at its default (full) widths answers four requests (two ``dcgan64``, two
``unet_dec`` with mixed step budgets) on Pallas.  Four chips: the sharded
ENet train step on a 4-device mesh against the same step on one device,
and a meshed ``GenServer`` drain against the unbatched reference.

Everything runs in this one process, through the entry points a user
calls (``repro.models.enet``, ``repro.launch.train_recipes``,
``repro.launch.serve_gen.GenServer``), with weights and inputs drawn from
``--seed``.  Tiles are the engine defaults (``REPRO_AUTOTUNE=off``), so no
machine-local table is read.  Each phase prints one JSON line with its
errors, tolerances, compile seconds and ``peak_bytes_in_use``; the last
line of stdout is ``{"ok": true, "device": {...}}``.  Serving compiles
inside the drain, so its phases report ``cold_s``, the seconds outside the
warm ticks: compiles plus the dispatches of the ticks that compiled.  The
script exits non-zero without that line when any phase fails, when JAX
finds no TPU, and outside a checkout of this repository.

Precision and tolerances.  On TPU an fp32 matmul at JAX's default
precision is a single bf16 pass, in XLA's convolutions and in Mosaic's
(the Pallas kernels) alike: at that precision the compiled Pallas ENet-512
forward sits ~7e-3 off an fp32 reference, and rounding, not the
algorithm, sets the error.  So every compared program, the Pallas path and
its reference alike, runs under ``jax.default_matmul_precision("highest")``
(fp32 contraction in XLA, ``contract_precision<fp32>`` in Mosaic), and the
reference is an XLA program that shares no Pallas code: the zero-laden
``decomposed=False`` convolutions.  What is left is fp32 rounding through
ENet's depth: ``REL_TOL = 1e-3`` of the reference's largest magnitude for
activations, losses and gradient norms.  The inference phase also runs
the forward at the default precision, as a user calls it, and holds it to
``PREC_FACTOR`` times the error XLA itself makes at that precision against
the same reference: the Pallas path is no less accurate than XLA at the
precision both were asked for.  The sharded train step is compared with
itself on one device, at the default precision: DESIGN.md §13 claims the
two bitwise equal.  AdamW's first update is ``lr * g / |g|``
per element, so a gradient element near zero may flip sign between two
correct backends and move its parameter by ``2 lr``; parameters are
therefore held to ``UPDATE_TOL = 1e-2``: the L2 norm of the difference of
the two updated parameter sets over the L2 norm of the update itself.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

#: matmul precision of every compared program (see above)
FP32 = "highest"
#: relative error bar for activations, losses and gradient norms (see above)
REL_TOL = 1e-3
#: default-precision Pallas error bar, in units of XLA's own error there
PREC_FACTOR = 4.0
#: relative L2 bar for parameters after AdamW updates (see above)
UPDATE_TOL = 1e-2

HW, CLASSES = 512, 19
LR = 5e-4                       # examples/train_enet.py's default peak lr
TRAIN_STEPS = 3
SHARDED_BATCH = 8               # global batch of the four-chip train step


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


def emit(phase: str, ok: bool, **fields) -> bool:
    print(json.dumps({"phase": phase, "ok": ok, **fields}), flush=True)
    return ok


def errors(got, ref) -> dict:
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise AssertionError(f"shape {got.shape} != reference {ref.shape}")
    diff = float(np.max(np.abs(got - ref))) if got.size else 0.0
    scale = float(np.max(np.abs(ref))) if ref.size else 0.0
    return {"max_abs_err": diff, "rel_err": diff / max(scale, 1e-30),
            "finite": bool(np.all(np.isfinite(got)))}


def update_error(p_got, p_ref, p0) -> float:
    """||p_got - p_ref|| / ||p_ref - p0|| over every parameter leaf."""
    import jax
    import numpy as np

    def sq(a, b):
        return sum(float(np.sum((np.asarray(x, np.float64)
                                 - np.asarray(y, np.float64)) ** 2))
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))

    return (sq(p_got, p_ref) / max(sq(p_ref, p0), 1e-30)) ** 0.5


def peak_bytes(devices) -> int | None:
    stats = [d.memory_stats() for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats if s]
    return max(peaks) if peaks else None


def compile_timed(jitted, *args, **static):
    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **static).compile()
    return compiled, time.perf_counter() - t0


# ------------------------------------------------------------ one chip ----

def phase_inference(devices, *, seed: int) -> bool:
    import jax
    from repro.models import enet

    t0 = time.perf_counter()
    params = enet.init_params(jax.random.PRNGKey(seed), CLASSES)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, HW, HW, 3))
    kw = dict(backend="pallas", interpret=False)
    with jax.default_matmul_precision(FP32):
        fwd, compile_s = compile_timed(enet.forward, params, x, **kw)
        y = jax.block_until_ready(fwd(params, x))
        ref = enet.forward(params, x, decomposed=False)
    # the same forward at the default precision, as a user calls it, and
    # XLA's own error at that precision
    fwd_d, compile_d_s = compile_timed(enet.forward, params, x, **kw)
    e_d = errors(fwd_d(params, x), ref)
    e_xla = errors(enet.forward(params, x, decomposed=False), ref)
    tol_d = max(REL_TOL, PREC_FACTOR * e_xla["rel_err"])
    e = errors(y, ref)
    ok = (e["finite"] and e["rel_err"] <= REL_TOL and e_d["finite"]
          and e_d["rel_err"] <= tol_d and y.shape == (1, HW, HW, CLASSES))
    return emit("inference", ok, model=f"enet-{HW}", shape=list(y.shape),
                tol_rel=REL_TOL, compile_s=compile_s, **e,
                default_precision={"rel_err": e_d["rel_err"],
                                   "max_abs_err": e_d["max_abs_err"],
                                   "xla_rel_err": e_xla["rel_err"],
                                   "tol_rel": tol_d,
                                   "compile_s": compile_d_s},
                peak_bytes_in_use=peak_bytes(devices),
                wall_s=time.perf_counter() - t0)


def phase_training(devices, *, seed: int) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import SegDataPipeline
    from repro.launch import train_recipes
    from repro.models import enet

    t0 = time.perf_counter()
    params = enet.init_params(jax.random.PRNGKey(seed), CLASSES)
    pipe = SegDataPipeline(1, hw=HW, classes=CLASSES, seed=seed)
    batches = [{k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
               for i in range(TRAIN_STEPS)]
    state0 = train_recipes.init_state(params)
    with jax.default_matmul_precision(FP32):
        step = train_recipes.make_train_step(
            "enet", backend="pallas", interpret=False, lr=LR)
        compiled, compile_s = compile_timed(step, state0, batches[0])
        state, losses = state0, []
        for i, b in enumerate(batches):
            state, m = compiled(state, b)
            losses.append(float(m["loss"]))
            if i == 0:
                s1, m1 = state, m
        # the same first step on the XLA engine (zero-laden convolutions)
        ref_step = train_recipes.make_train_step(
            "enet", backend="xla", decomposed=False, lr=LR)
        ref_state, ref_m = ref_step(state0, batches[0])
    loss_e = errors(m1["loss"], ref_m["loss"])
    gnorm_e = errors(m1["grad_norm"], ref_m["grad_norm"])
    upd = update_error(s1.params, ref_state.params, state0.params)
    finite = bool(np.all(np.isfinite(losses)))
    ok = (finite and loss_e["rel_err"] <= REL_TOL
          and gnorm_e["rel_err"] <= REL_TOL and upd <= UPDATE_TOL
          and float(m1["skipped"]) == 0.0)
    return emit("training", ok, model=f"enet-{HW}", steps=TRAIN_STEPS,
                losses=losses, loss_rel_err=loss_e["rel_err"],
                grad_norm=float(m1["grad_norm"]),
                grad_norm_rel_err=gnorm_e["rel_err"],
                param_update_rel_err=upd, tol_rel=REL_TOL,
                tol_update=UPDATE_TOL, compile_s=compile_s,
                peak_bytes_in_use=peak_bytes(devices),
                wall_s=time.perf_counter() - t0)


def _serve_and_check(phase: str, devices, server, ref_params, *, requests,
                     check_devices=None):
    """Drain ``requests`` ((workload, steps, seed) triples) and compare
    each served image with its unbatched XLA reference, at the caller's
    matmul precision."""
    import numpy as np
    from repro.launch.serve_gen import init_noise, reference_sample
    from repro.models import dcgan

    t0 = time.perf_counter()
    rids = {server.submit(w, steps=s, seed=sd): (w, s, sd)
            for w, s, sd in requests}
    images = server.run()
    run_s = time.perf_counter() - t0
    st = server.stats()
    worst = {"max_abs_err": 0.0, "rel_err": 0.0, "finite": True}
    for rid, (w, s, sd) in rids.items():
        if w == "unet_dec":
            ref = reference_sample(ref_params[w], steps=s, seed=sd,
                                   image_size=server.unet_hw
                                   * 2 ** len(server.unet_widths),
                                   decomposed=False)
        else:
            z = init_noise(sd, (server.dcgan_nz,))[None]
            ref = np.asarray(dcgan.forward(ref_params[w], z,
                                           decomposed=False))[0]
        e = errors(images[rid], ref)
        worst = {"max_abs_err": max(worst["max_abs_err"], e["max_abs_err"]),
                 "rel_err": max(worst["rel_err"], e["rel_err"]),
                 "finite": worst["finite"] and e["finite"]}
    statuses = [server.request(r).status for r in rids]
    lanes_ok = True
    if check_devices is not None:
        lanes_ok = all(lane_devs == check_devices
                       for lane_devs in server.lane_devices().values())
    ok = (len(images) == len(rids) and worst["finite"]
          and worst["rel_err"] <= REL_TOL and st["degraded"] == 0
          and st["retries"] == 0 and lanes_ok
          and all(s == "done" for s in statuses))
    return emit(phase, ok, requests=[list(r) for r in rids.values()],
                statuses=statuses, degraded=st["degraded"],
                retries=st["retries"], tol_rel=REL_TOL, run_s=run_s,
                cold_s=st["wall_s"] - st["warm_wall_s"],
                lanes_on_all_devices=lanes_ok,
                peak_bytes_in_use=peak_bytes(devices),
                wall_s=time.perf_counter() - t0, **worst)


def phase_serving(devices, *, seed: int) -> bool:
    import jax
    from repro.launch.serve_gen import GenServer
    from repro.models import dcgan, unet_decoder

    # GenServer's default (full) widths: the U-Net decoder at its default
    # widths from 8x8, DCGAN-64 with nz=100, ngf=64
    params = {
        "unet_dec": unet_decoder.init_denoiser_params(
            jax.random.PRNGKey(seed)),
        "dcgan64": dcgan.init_params(jax.random.PRNGKey(seed + 1), size=64,
                                     nz=100, ngf=64),
    }
    requests = [("dcgan64", 1, seed + 10), ("unet_dec", 3, seed + 11),
                ("dcgan64", 1, seed + 12), ("unet_dec", 5, seed + 13)]
    with jax.default_matmul_precision(FP32):
        server = GenServer(backend="pallas", interpret=False, params=params)
        return _serve_and_check("serving", devices, server, params,
                                requests=requests)


# ---------------------------------------------------------- four chips ----

def phase_sharded_training(devices, *, seed: int) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import SegDataPipeline
    from repro.launch import train_recipes as tr
    from repro.launch.mesh import make_train_mesh
    from repro.models import enet

    t0 = time.perf_counter()
    params = enet.init_params(jax.random.PRNGKey(seed), CLASSES)
    pipe = SegDataPipeline(SHARDED_BATCH, hw=HW, classes=CLASSES,
                           seed=seed)
    batches = [{k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
               for i in range(TRAIN_STEPS)]
    state0 = tr.init_state(params)
    runs, compile_s, placed = {}, {}, True
    for nd in (len(devices), 1):
        mesh = make_train_mesh(nd)
        want = set(mesh.devices.flat)
        state = tr.place_state(mesh, state0)
        chunks = [tr.shard_batch(mesh, b) for b in batches]
        step = tr.make_sharded_train_step("enet", mesh, lr=LR)
        compiled, compile_s[nd] = compile_timed(step, state, chunks[0])
        losses = []
        for c in chunks:
            placed &= c["image"].sharding.device_set == want
            state, m = compiled(state, c)
            losses.append(float(m["loss"]))
        placed &= all(leaf.sharding.device_set == want
                      for leaf in jax.tree_util.tree_leaves(state.params))
        runs[nd] = (jax.device_get(state.params), losses)
    (p_n, l_n), (p_1, l_1) = runs[len(devices)], runs[1]
    leaves_n = jax.tree_util.tree_leaves(p_n)
    leaves_1 = jax.tree_util.tree_leaves(p_1)
    bitwise = all(np.array_equal(a, b) for a, b in zip(leaves_n, leaves_1)) \
        and l_n == l_1
    max_diff = max(float(np.max(np.abs(np.asarray(a, np.float64)
                                       - np.asarray(b, np.float64))))
                   for a, b in zip(leaves_n, leaves_1))
    upd = update_error(p_n, p_1, state0.params)
    ok = (placed and bool(np.all(np.isfinite(l_n))) and upd <= UPDATE_TOL)
    return emit("sharded_training", ok, model=f"enet-{HW}",
                devices=len(devices), global_batch=SHARDED_BATCH, steps=TRAIN_STEPS,
                losses=l_n, losses_1dev=l_1, bitwise=bitwise,
                param_max_abs_diff=max_diff, param_update_rel_err=upd,
                tol_update=UPDATE_TOL, arrays_on_all_devices=placed,
                compile_s=compile_s[len(devices)],
                compile_s_1dev=compile_s[1],
                peak_bytes_in_use=peak_bytes(devices),
                wall_s=time.perf_counter() - t0)


def phase_meshed_serving(devices, *, seed: int) -> bool:
    import jax
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.serve_gen import GenServer
    from repro.models import unet_decoder

    params = {"unet_dec": unet_decoder.init_denoiser_params(
        jax.random.PRNGKey(seed))}
    mesh = make_smoke_mesh(len(devices))
    requests = [("unet_dec", s, seed + 20 + i)
                for i, s in enumerate((3, 5, 2, 4))]
    with jax.default_matmul_precision(FP32):
        server = GenServer(mesh=mesh, params=params)
        return _serve_and_check("meshed_serving", devices, server, params,
                                requests=requests,
                                check_devices=set(mesh.devices.flat))


# ----------------------------------------------------------------- main ----

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: ENet-512 inference, training and serving on "
                         "Pallas; 4: only the multi-chip paths")
    ap.add_argument("--seed", type=int, default=0)
    ns = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}: run from a checkout "
             f"of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["REPRO_AUTOTUNE"] = "off"     # default tiles, no local table

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no backend: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX's first device is {dev.platform} "
             f"({dev.device_kind})")
    if len(devices) < ns.chips:
        fail(f"--chips {ns.chips} but JAX sees {len(devices)} device(s)")
    devices = devices[:ns.chips]

    from repro.launch.compile_cache import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache(),
                      "platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices)}), flush=True)
    phases = ([phase_inference, phase_training, phase_serving]
              if ns.chips == 1 else
              [phase_sharded_training, phase_meshed_serving])
    ok = True
    for phase in phases:
        try:
            ok &= phase(devices, seed=ns.seed)
        except Exception:           # the run still fails: ok is cleared
            traceback.print_exc()
            ok = emit(phase.__name__.removeprefix("phase_"), False) and ok
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
