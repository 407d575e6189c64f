"""Generative serving loop: continuous-batching iterative decoder sampling
on the decomposition engine (DESIGN.md §9).

Requests arrive as ``(workload, steps, seed, slo)`` and are packed into
per-workload device batches (*lanes*).  Diffusion requests iterate the DDIM
step — timestep embedding + U-Net decoder forward through the fused
transposed-conv kernels + DDIM update — and each scheduler tick is ONE
jitted call that fuses up to ``scan_steps`` DDIM steps via ``lax.scan``
(:func:`repro.launch.steps.make_gen_scan_step`): per-slot trajectories are
padded into ``(B, K)`` timestep matrices, so mixed-step requests still
share one compiled step while host dispatch overhead is paid once per
``K`` steps.  Because the transposed-conv geometry is timestep-*invariant*
(the timestep enters only as an embedded value), in-flight requests sitting
at different denoising timesteps share a batch; a slot that finishes is
refilled from the queue on the next tick while its neighbours keep
denoising.  DCGAN requests are single-shot: one tick through the k=4/s=2
generator completes every active slot.

The scheduler is SLO-aware (DESIGN.md §9): every request carries an
:class:`SLOClass` (priority rank + optional latency target + optional
timeout).  Admission per lane orders by ``(class rank, deadline, arrival)``
— strict priority across classes, FIFO within a class (same-class deadlines
are arrival-ordered by construction), with an aging bound so no class
starves — and *acts* on the calibrated ``est_us`` stamped at submit:
a request whose remaining deadline budget cannot cover its estimated
service time is shed at admission instead of wasting a slot.  Requests can
be cancelled (or time out) both queued and mid-flight; a vacated slot is
reusable on the next tick.  Under ``autoscale=True`` each lane grows and
shrinks its device batch between compiled sizes as its backlog moves
(``jax.jit`` caches one executable per batch shape, so revisited sizes
redispatch without recompiling).

The server is fault-tolerant (DESIGN.md §11).  Every ``snapshot_every``
ticks (and on demand via :meth:`GenServer.snapshot`) the full
scheduler-visible state — per-slot image tensors, trajectory cursors,
per-request seeds and SLO metadata, the admission queue, and completed
results — is written through the atomic manifest+COMMITTED checkpoint
layout (``repro.checkpoint``); :meth:`GenServer.restore` resumes a killed
drain mid-flight, and because the mixed-timestep step is timestep-*data*
driven the recovered drain is bitwise-identical on xla to an uninterrupted
run.  A dispatch that raises retries with exponential backoff, then the
lane *degrades* in place to the xla engine (the dispatcher routes both)
instead of killing the server; corrupted slots are detected by a
completion-time finiteness check and re-run from their seed; repeated
stuck-tick flags from a :class:`StragglerWatchdog` shed the
lowest-priority pending class first.  ``faults=`` accepts a
:class:`repro.distributed.fault_tolerance.FailureInjector` so chaos drills
drive all of these paths deterministically.

This mirrors the LM path (``repro.launch.serve``): the scheduler is
host-side and dumb, the device step is pure and compiled once.  The image
state takes its sharding from :func:`repro.distributed.sharding.image_sharding`
(batch over the data axes, optionally spatial rows over the model axis).

CPU-scale usage:

  PYTHONPATH=src python -m repro.launch.serve_gen --smoke
  PYTHONPATH=src python -m repro.launch.serve_gen --requests 6 \
      --steps 8,5,3 --batch 4 --backend xla --scan-steps 4 --slo realtime
"""

from __future__ import annotations

import argparse
import functools
import math
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro import obs
from repro.core import cycle_model as cm
from repro.core.gen_spec import GEN_WORKLOADS, UNET_WIDTHS
from repro.distributed import sharding as shd
from repro.distributed.fault_tolerance import (FailureInjector,
                                               StragglerWatchdog)
from repro.kernels.util import canon_dtype
from repro.launch.mesh import auto_mesh
from repro.launch.steps import (DDIM_T_MAX, ddim_timesteps,
                                make_gen_scan_step)
from repro.models import dcgan, unet_decoder


def init_noise(seed: int, shape: tuple[int, ...]) -> jax.Array:
    """Seeded x_T (or latent) — shared by the server and the reference loop
    so a served request is bit-for-bit reproducible from its seed."""
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _refill(state: jax.Array, seeds: jax.Array,
            fresh: jax.Array) -> jax.Array:
    """Slot ``i`` of ``state`` becomes ``init_noise(seeds[i])`` (cast to
    the state's dtype) where ``fresh[i]``; every other slot passes through
    bitwise."""
    noise = jax.vmap(lambda s: init_noise(s, state.shape[1:]))(seeds)
    keep = fresh.reshape(fresh.shape + (1,) * (state.ndim - 1))
    return jnp.where(keep, noise.astype(state.dtype), state)


class _Refill:
    """A lane's admissions of one tick, written to its slot state in one
    device call.

    ``put`` records a slot's seed on the host; ``__call__`` then runs the
    jitted :func:`_refill` over every slot at once (fixed shape: one
    executable per lane batch size, full and partial ticks alike), with
    the state donated and, on a meshed lane, its sharding kept.
    ``init_noise`` keys on ``PRNGKey(seed)``, which (64-bit mode off)
    takes the seed modulo 2**32, so a ``uint32`` slot holds any int seed.
    """

    def __init__(self):
        # a function of the lane's own: its jit cache holds this lane's
        # executables alone, one per batch size, kept across resizes
        self._fun = functools.partial(_refill)

    def alloc(self, batch: int, sharding=None) -> None:
        kw = {} if sharding is None else {"out_shardings": sharding}
        self.fn = jax.jit(self._fun, donate_argnums=(0,), **kw)
        self.seeds = np.zeros(batch, np.uint32)
        self.fresh = np.zeros(batch, bool)

    def put(self, slot: int, seed: int) -> None:
        self.seeds[slot] = seed & 0xFFFFFFFF
        self.fresh[slot] = True

    def __call__(self, state: jax.Array) -> jax.Array | None:
        """The refilled state, or None when no slot was put since the last
        call.  The host arrays go in as copies: the call is asynchronous,
        and on the CPU a device array may share an argument's memory."""
        if not self.fresh.any():
            return None
        out = self.fn(state, self.seeds.copy(), self.fresh.copy())
        self.fresh[:] = False
        return out


# ---------------------------------------------------------------------------
# SLO classes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SLOClass:
    """One service-level class: admission priority + latency contract.

    ``rank`` orders admission (lower admits first).  ``target_us`` is the
    end-to-end latency budget measured from submit; when both it and the
    request's calibrated ``est_us`` are known, a request whose remaining
    budget cannot cover its estimated service time is *shed* at admission
    (status ``"shed"``) instead of occupying a slot it is guaranteed to
    miss in.  ``timeout_ticks`` is the default scheduler-tick lifetime
    (queued + in-flight) for requests of the class; ``None`` never expires.
    """
    name: str
    rank: int
    target_us: float | None = None
    timeout_ticks: int | None = None


#: built-in classes; ``submit(..., slo=...)`` accepts a name here or any
#: ad-hoc :class:`SLOClass` (tests pass tight targets to pin shedding).
SLO_CLASSES = {
    "realtime": SLOClass("realtime", 0, target_us=1e6),
    "standard": SLOClass("standard", 1),
    "batch": SLOClass("batch", 2),
}

#: admission waits longer than this many ticks promote a request to the
#: front regardless of class — the cross-class anti-starvation bound
#: (within a class admission is already FIFO).
DEFAULT_STARVATION_TICKS = 64

#: fused-dispatch depth used when ``scan_steps="auto"`` finds no
#: calibration coverage for the lane's layer mix.
DEFAULT_SCAN_STEPS = 4

#: upper bound for the auto-chosen fused depth: past this the per-dispatch
#: amortisation win is negligible while a tick's latency (and the work
#: wasted by a mid-flight cancel) keeps growing linearly.
MAX_SCAN_STEPS = 8


def choose_scan_steps(calibration, layers, *, backend: str = "xla",
                      batch: int = 1, target_tick_us: float = 50_000.0,
                      max_scan: int = MAX_SCAN_STEPS) -> int:
    """Fused depth K chosen against tick latency (the PR-6 calibration).

    The largest K whose predicted fused-tick wall time — ``batch x K`` per-
    pass compute plus one per-pass dispatch overhead
    (:meth:`Calibration.predict_layers_split`) — stays within
    ``target_tick_us``, clamped to ``[1, max_scan]``.  A longer scan
    amortises host dispatch further but delays scheduler decisions
    (admission, cancel, autoscale all happen between ticks), so the target
    bounds the scheduler's reaction latency.  Without a calibration (or
    without coverage for some layer kind) returns
    :data:`DEFAULT_SCAN_STEPS`.
    """
    if max_scan < 1:
        raise ValueError(f"max_scan must be >= 1, got {max_scan}")
    split = (calibration.predict_layers_split(layers, backend=backend)
             if calibration is not None else None)
    if split is None:
        return min(DEFAULT_SCAN_STEPS, max_scan)
    compute_us, dispatch_us = split
    per_step = batch * compute_us
    if per_step <= 0.0:
        return max_scan
    k = int((target_tick_us - dispatch_us) // per_step)
    return max(1, min(max_scan, k))


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------

@dataclass
class GenRequest:
    """One sampling request; ticks are scheduler steps, not wall time."""
    rid: int
    workload: str
    steps: int
    seed: int
    submit_tick: int
    slo: SLOClass = SLO_CLASSES["standard"]
    timeout_ticks: int | None = None
    submit_wall: float = field(default_factory=time.perf_counter)
    admit_tick: int = -1
    done_tick: int = -1
    done_wall: float = 0.0
    result: np.ndarray | None = None
    # lifecycle: pending -> active -> done, or a terminal non-result state
    # (cancelled / timeout / shed / corrupt) — terminal states never hold a
    # result
    status: str = "pending"
    # calibrated host-time admission estimate (us) for the whole request, or
    # None when the server has no calibration fitted for this layer mix
    est_us: float | None = None
    # completion-time corruption detections that sent this request back to
    # the queue for a clean re-run (bounded by the server's max_requeues)
    requeues: int = 0

    @property
    def wait_ticks(self) -> int:
        return self.admit_tick - self.submit_tick

    @property
    def latency_s(self) -> float:
        """Submit-to-completion wall latency (0.0 until done)."""
        return (self.done_wall - self.submit_wall) if self.done_wall else 0.0

    def deadline_us(self) -> float:
        """Absolute wall deadline in perf-counter microseconds (inf when the
        class carries no latency target)."""
        if self.slo.target_us is None:
            return math.inf
        return self.submit_wall * 1e6 + self.slo.target_us


# ---------------------------------------------------------------------------
# Lanes
# ---------------------------------------------------------------------------

class _DiffusionLane:
    """Resizable batch of diffusion slots over one compiled K-step scan."""

    kind = "diffusion"

    def __init__(self, params: dict, *, batch: int, widths: tuple[int, ...],
                 hw: int, out_ch: int, backend: str,
                 interpret: bool | None, decomposed: bool, mesh=None,
                 spatial: bool = False, scan_steps: int = 1,
                 compute_dtype: str | None = None):
        size = hw * 2 ** len(widths)
        self.image_shape = (size, size, out_ch)
        self.params = params
        self.scan_steps = scan_steps
        self.backend = backend
        self.decomposed, self.interpret = decomposed, interpret
        self.compute_dtype = compute_dtype
        # lane image state lives in the compute dtype: the fused step's
        # fp32 DDIM update casts back to it, so the slots stay bf16-resident
        # end to end (half the HBM per slot) when the lane opts in
        self._x_dtype = (jnp.float32 if compute_dtype is None
                         else canon_dtype(compute_dtype))
        self.mesh, self.spatial = mesh, spatial
        self._raw_step = make_gen_scan_step(scan_steps, decomposed=decomposed,
                                            backend=backend,
                                            interpret=interpret,
                                            compute_dtype=compute_dtype)
        if mesh is not None:
            self.params = jax.device_put(params, shd.replicated(mesh))
        self.device_steps = 0       # host dispatches (one per busy tick)
        self.substeps = 0           # active trajectory steps actually taken
        self.compiled_sizes: set[int] = set()
        self._refill = _Refill()
        self._alloc(batch)

    def set_backend(self, backend: str) -> None:
        """Swap the dispatch backend in place (graceful degradation,
        DESIGN.md §11): the compiled step is rebuilt, every slot's image
        state, trajectory cursor, and request stay exactly where they are —
        the DDIM update is backend-invariant data flow, so a degraded lane
        continues the same trajectories on the fallback engine."""
        if backend == self.backend:
            return
        self.backend = backend
        self._raw_step = make_gen_scan_step(
            self.scan_steps, decomposed=self.decomposed, backend=backend,
            interpret=self.interpret, compute_dtype=self.compute_dtype)
        self._step, _ = self._jit_step(self.batch)
        self.compiled_sizes = set()

    def corrupt(self, slot: int) -> None:
        """Chaos hook: poison one slot's image state with NaNs (the
        completion-time finiteness check must catch and re-run it)."""
        self.x = self.x.at[slot % self.batch].set(jnp.nan)

    def state_arrays(self) -> dict[str, np.ndarray]:
        """Device state for a lane snapshot (everything non-reconstructible:
        slot metadata travels in the manifest extra instead)."""
        return {"x": np.asarray(self.x)}

    def load_state(self, arrays: dict[str, np.ndarray]) -> None:
        x = jnp.asarray(arrays["x"])
        self.x = x if self.mesh is None else jax.device_put(
            x, shd.image_sharding(self.mesh, x.shape, spatial=self.spatial))

    def _jit_step(self, batch: int):
        """One jitted K-step scan per mesh sharding; ``jax.jit`` itself
        caches one executable per batch shape, so lanes revisiting a size
        after autoscaling redispatch without recompiling."""
        if self.mesh is not None:
            sh = shd.image_sharding(self.mesh, (batch,) + self.image_shape,
                                    spatial=self.spatial)
            return jax.jit(self._raw_step, donate_argnums=(1,),
                           out_shardings=sh), sh
        return jax.jit(self._raw_step, donate_argnums=(1,)), None

    def _alloc(self, batch: int) -> None:
        self.batch = batch
        self._step, sh = self._jit_step(batch)
        x = jnp.zeros((batch,) + self.image_shape, self._x_dtype)
        self.x = x if sh is None else jax.device_put(x, sh)
        self._refill.alloc(batch, sh)
        self.slots: list[GenRequest | None] = [None] * batch
        self._traj: list[np.ndarray | None] = [None] * batch
        self._pos = [0] * batch
        self.active = np.zeros(batch, bool)

    @property
    def busy(self) -> bool:
        return self.active.any()

    @property
    def active_count(self) -> int:
        return int(self.active.sum())

    def free_slot(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def admit(self, req: GenRequest, slot: int) -> None:
        traj = ddim_timesteps(req.steps)
        self.slots[slot] = req
        self._traj[slot] = traj
        self._pos[slot] = 0
        self.active[slot] = True
        self._refill.put(slot, req.seed)

    def refill(self) -> bool:
        """Write x_T of every slot admitted since the last refill, in one
        device call; returns whether a call was made."""
        x = self._refill(self.x)
        if x is None:
            return False
        self.x = x
        return True

    def release(self, slot: int) -> None:
        """Vacate a slot mid-flight (cancel/timeout): the slot is reusable
        on the next admission pass; the stale image rows are inert (the
        active mask keeps them out of every future scan substep)."""
        self.slots[slot] = self._traj[slot] = None
        self._pos[slot] = 0
        self.active[slot] = False

    def resize(self, new_batch: int) -> None:
        """Re-pack occupied slots into a ``new_batch``-sized lane.

        Occupied slots compact to the front in slot order; every request's
        trajectory position and image state move with it, so a resize never
        perturbs a sample (pinned bitwise in ``tests/test_serve_gen.py``).
        """
        occ = [i for i, s in enumerate(self.slots) if s is not None]
        if len(occ) > new_batch:
            raise ValueError(
                f"cannot shrink to {new_batch}: {len(occ)} slots occupied")
        if new_batch == self.batch:
            return
        old = (self.x, [self.slots[i] for i in occ],
               [self._traj[i] for i in occ], [self._pos[i] for i in occ])
        self._alloc(new_batch)
        x_old, slots, trajs, poss = old
        if occ:
            self.x = self.x.at[:len(occ)].set(
                x_old[jnp.asarray(occ, jnp.int32)])
        for i, (s, tr, p) in enumerate(zip(slots, trajs, poss)):
            self.slots[i], self._traj[i], self._pos[i] = s, tr, p
            self.active[i] = True

    def tick(self, tick: int) -> list[GenRequest]:
        b, k = self.batch, self.scan_steps
        t = np.zeros((b, k), np.int32)
        t_next = np.full((b, k), -1, np.int32)
        act = np.zeros((b, k), bool)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            traj, p = self._traj[i], self._pos[i]
            take = min(k, len(traj) - p)
            for j in range(take):
                t[i, j] = traj[p + j]
                if p + j + 1 < len(traj):
                    t_next[i, j] = traj[p + j + 1]
                act[i, j] = True
        self.compiled_sizes.add(self.batch)
        with obs.span(obs.GEN_DISPATCH, tick=tick):
            batch = {"t": jnp.asarray(t), "t_next": jnp.asarray(t_next),
                     "active": jnp.asarray(act)}
            self.x = self._step(self.params, self.x, batch)
        self.device_steps += 1
        self.substeps += int(act.sum())
        done = []
        with obs.span(obs.GEN_FETCH, tick=tick):
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                self._pos[i] += int(act[i].sum())
                if self._pos[i] == len(self._traj[i]):    # landed on x0
                    req.result = np.asarray(self.x[i])
                    done.append(req)
                    self.release(i)
        return done


class _DCGANLane:
    """Single-shot generation: one tick drains every active latent slot.

    The generator forward is jitted ONCE here (with the static backend
    arguments closed over), not re-entered through the module-level wrapper
    every tick — one compile per batch size, then pure dispatch (warm-tick
    dispatch count pinned in ``tests/test_serve_gen.py``).
    """

    kind = "dcgan"
    scan_steps = 1

    def __init__(self, params: dict, *, batch: int, nz: int, backend: str,
                 interpret: bool | None, decomposed: bool, mesh=None,
                 compute_dtype: str | None = None):
        self.params = params
        self.nz = nz
        self.backend = backend
        self.decomposed, self.interpret = decomposed, interpret
        self.compute_dtype = compute_dtype
        self.mesh = mesh
        if mesh is not None:
            self.params = jax.device_put(params, shd.replicated(mesh))
        self._step = jax.jit(functools.partial(
            dcgan.forward, decomposed=decomposed, backend=backend,
            interpret=interpret, compute_dtype=compute_dtype))
        self.device_steps = 0
        self.substeps = 0
        self.compiled_sizes: set[int] = set()
        self._refill = _Refill()
        self._alloc(batch)

    def set_backend(self, backend: str) -> None:
        if backend == self.backend:
            return
        self.backend = backend
        self._step = jax.jit(functools.partial(
            dcgan.forward, decomposed=self.decomposed, backend=backend,
            interpret=self.interpret, compute_dtype=self.compute_dtype))
        self.compiled_sizes = set()

    def corrupt(self, slot: int) -> None:
        self.z = self.z.at[slot % self.batch].set(jnp.nan)

    def state_arrays(self) -> dict[str, np.ndarray]:
        return {"z": np.asarray(self.z)}

    def load_state(self, arrays: dict[str, np.ndarray]) -> None:
        self.z = self._place(jnp.asarray(arrays["z"]))

    def _place(self, z: jax.Array) -> jax.Array:
        """Latent slots shard over the mesh's data axes (lanes span the
        mesh like the diffusion lane's image state; the generator's
        transposed-conv parity planes are batch-parallel)."""
        if self.mesh is None:
            return z
        return jax.device_put(z, shd.image_sharding(self.mesh, z.shape))

    def _alloc(self, batch: int) -> None:
        self.batch = batch
        self.z = self._place(jnp.zeros((batch, self.nz), jnp.float32))
        self._refill.alloc(
            batch, None if self.mesh is None else self.z.sharding)
        self.slots: list[GenRequest | None] = [None] * batch
        self.active = np.zeros(batch, bool)

    @property
    def busy(self) -> bool:
        return self.active.any()

    @property
    def active_count(self) -> int:
        return int(self.active.sum())

    def free_slot(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def admit(self, req: GenRequest, slot: int) -> None:
        self.slots[slot] = req
        self.active[slot] = True
        self._refill.put(slot, req.seed)

    def refill(self) -> bool:
        """Write the latent of every slot admitted since the last refill,
        in one device call; returns whether a call was made."""
        z = self._refill(self.z)
        if z is None:
            return False
        self.z = z
        return True

    def release(self, slot: int) -> None:
        self.slots[slot] = None
        self.active[slot] = False

    def resize(self, new_batch: int) -> None:
        occ = [i for i, s in enumerate(self.slots) if s is not None]
        if len(occ) > new_batch:
            raise ValueError(
                f"cannot shrink to {new_batch}: {len(occ)} slots occupied")
        if new_batch == self.batch:
            return
        z_old, slots = self.z, [self.slots[i] for i in occ]
        self._alloc(new_batch)
        if occ:
            self.z = self.z.at[:len(occ)].set(
                z_old[jnp.asarray(occ, jnp.int32)])
        for i, s in enumerate(slots):
            self.slots[i] = s
            self.active[i] = True

    def tick(self, tick: int) -> list[GenRequest]:
        self.compiled_sizes.add(self.batch)
        with obs.span(obs.GEN_DISPATCH, tick=tick):
            imgs = self._step(self.params, self.z)
        with obs.span(obs.GEN_FETCH, tick=tick):
            imgs = np.asarray(imgs)
        self.device_steps += 1
        done = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.substeps += 1
            req.result = imgs[i]
            done.append(req)
            self.release(i)
        return done


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------

class GenServer:
    """Continuous-batching generative server over the decomposition engine.

    One lane (device batch + compiled K-step scan) per workload, built
    lazily on the first request for it.  ``submit`` enqueues, ``step`` runs
    one scheduler tick (expire timeouts, autoscale, admit into free slots,
    then one fused device dispatch per busy lane), ``run`` drains the queue
    and returns ``rid -> image``.

    **Admission** (DESIGN.md §9): per lane, pending requests order by
    ``(SLO rank, deadline, arrival)`` — strict priority across classes,
    FIFO within a class (a class's deadlines are arrival-ordered because
    the latency target is a constant offset), and any request waiting
    longer than ``starvation_ticks`` is promoted to the front, so no class
    starves.  A full lane never blocks another lane.  When a request
    carries both a calibrated ``est_us`` stamp and a latency target, an
    admission attempt whose remaining budget is below the estimate *sheds*
    the request (status ``"shed"``) instead of burning a slot on a
    guaranteed SLO miss — the scheduler finally acting on the PR-6
    admission estimates.  Admitting a request is host bookkeeping; each
    lane then writes the noise of every slot it admitted in one device
    call a tick (``stats()["admit_calls"]`` counts them).

    ``scan_steps`` fuses K DDIM steps per dispatch (``"auto"`` sizes K per
    lane from the calibration via :func:`choose_scan_steps`); ``autoscale``
    lets each lane grow/shrink its batch between compiled sizes with its
    backlog.  ``params`` overrides model parameters per workload name
    (tests and the smoke paths pass tiny-width denoisers); otherwise lanes
    initialise canonical-width parameters from ``param_seed``.

    **Fault tolerance** (DESIGN.md §11): a lane dispatch that raises is
    retried ``max_retries`` times with exponential backoff starting at
    ``retry_backoff_s``; a lane still failing on a non-xla backend then
    *degrades* in place to xla and keeps its trajectories.  Results are
    finiteness-checked at completion; a corrupted sample re-runs from its
    seed (at most ``max_requeues`` times, then status ``"corrupt"``).
    ``watchdog`` (a :class:`StragglerWatchdog`) flags stuck ticks;
    ``stuck_shed_after`` consecutive flags shed the lowest-priority pending
    class.  With ``snapshot_dir`` set, :meth:`snapshot` checkpoints the
    full scheduler state (auto every ``snapshot_every`` ticks) and
    :meth:`restore` resumes a killed drain exactly.  ``faults`` accepts a
    :class:`FailureInjector` whose scheduled faults the tick loop consumes
    at fixed points, so chaos drills are deterministic.
    """

    def __init__(self, *, batch: int = 4, backend: str = "xla",
                 interpret: bool | None = None, decomposed: bool = True,
                 mesh=None, spatial: bool = False,
                 unet_widths: tuple[int, ...] = UNET_WIDTHS, unet_hw: int = 8,
                 out_ch: int = 3, dcgan_nz: int = 100, dcgan_ngf: int = 64,
                 params: dict | None = None, param_seed: int = 0,
                 calibration=None, scan_steps: int | str = 1,
                 autoscale: bool = False, min_batch: int = 1,
                 max_batch: int | None = None, shrink_patience: int = 2,
                 starvation_ticks: int = DEFAULT_STARVATION_TICKS,
                 faults: FailureInjector | None = None,
                 watchdog: StragglerWatchdog | None = None,
                 max_retries: int = 3, retry_backoff_s: float = 0.05,
                 stuck_shed_after: int = 3, max_requeues: int = 1,
                 snapshot_dir: str | None = None, snapshot_every: int = 0,
                 snapshot_keep: int = 3,
                 compute_dtype: str | None = None):
        if isinstance(scan_steps, str):
            if scan_steps != "auto":
                raise ValueError(
                    f"scan_steps must be an int >= 1 or 'auto', "
                    f"got {scan_steps!r}")
        elif scan_steps < 1:
            raise ValueError(f"scan_steps must be >= 1, got {scan_steps}")
        self.batch = batch
        self.backend = backend
        self.interpret = interpret
        self.decomposed = decomposed
        self.compute_dtype = compute_dtype
        self.mesh = mesh
        self.spatial = spatial
        self.unet_widths, self.unet_hw, self.out_ch = unet_widths, unet_hw, out_ch
        self.dcgan_nz, self.dcgan_ngf = dcgan_nz, dcgan_ngf
        self._params = dict(params or {})
        self._param_seed = param_seed
        self.calibration = calibration
        self.scan_steps = scan_steps
        self.autoscale = autoscale
        self.min_batch = max(1, min_batch)
        self.max_batch = max(batch, max_batch or batch * 4)
        self.shrink_patience = shrink_patience
        self.starvation_ticks = starvation_ticks
        self.faults = faults
        self.watchdog = watchdog
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.stuck_shed_after = max(1, stuck_shed_after)
        self.max_requeues = max_requeues
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = snapshot_every
        self.snapshot_keep = snapshot_keep
        # fault-tolerance counters (surfaced by stats(), DESIGN.md §11)
        self._degraded: dict[str, str] = {}   # workload -> fallback backend
        self._retries = 0
        self._recoveries = 0
        self._snapshots = 0
        self._stuck = 0                       # consecutive stuck-tick flags
        self._admit_calls = 0                 # lane refill calls (_admit)
        self._lanes: dict[str, _DiffusionLane | _DCGANLane] = {}
        self._idle_ticks: dict[str, int] = {}
        self._pending: list[GenRequest] = []
        self._done: dict[int, GenRequest] = {}
        self._requests: dict[int, GenRequest] = {}
        self._tick = 0
        self._next_rid = 0
        self._t0: float | None = None
        # per-tick log: (wall_s, dispatches, completions, substeps, cold) —
        # cold = JAX built or loaded an executable inside the tick
        # (obs.compiles() moved), so warm throughput can be reported
        # without the compile wall (stats())
        self._tick_log: list[tuple[float, int, int, int, bool]] = []
        self._compiles0 = obs.compiles()

    # -------------------------------------------------------------- lanes --
    def _workload_layers(self, workload: str):
        """Layer table of the geometry this server will actually execute.

        The canonical ``GEN_WORKLOADS`` tables assume canonical widths; a
        server constructed with overrides (``--smoke``, tests,
        ``unet_widths``/``unet_hw``) runs a different geometry, and an
        admission estimate priced off the canonical table would not match
        what executes — so the table is derived from the lane parameters.
        """
        from repro.core import gen_spec

        if workload == "unet_dec":
            return gen_spec.unet_decoder_layers(
                tuple(self.unet_widths), hw=self.unet_hw, out_ch=self.out_ch)
        if workload in ("dcgan64", "dcgan128"):
            return gen_spec.dcgan_layers(
                int(workload[5:]), nz=self.dcgan_nz, ngf=self.dcgan_ngf,
                out_ch=self.out_ch)
        raise ValueError(f"unknown workload {workload!r}; "
                         f"known: {sorted(GEN_WORKLOADS)}")

    def _lane_scan_steps(self, workload: str) -> int:
        if workload != "unet_dec":
            return 1            # single-shot lanes have no trajectory to fuse
        if self.scan_steps == "auto":
            return choose_scan_steps(self.calibration,
                                     self._workload_layers(workload),
                                     backend=self.backend, batch=self.batch)
        return int(self.scan_steps)

    def _init_params(self, workload: str) -> dict:
        """Lane parameters: the per-workload override if given, else a
        deterministic init from ``param_seed`` (also the structural template
        restore() unflattens snapshotted parameter leaves into)."""
        if workload == "unet_dec":
            return self._params.get(workload) or \
                unet_decoder.init_denoiser_params(
                    jax.random.PRNGKey(self._param_seed),
                    widths=self.unet_widths, out_ch=self.out_ch)
        if workload in ("dcgan64", "dcgan128"):
            return self._params.get(workload) or dcgan.init_params(
                jax.random.PRNGKey(self._param_seed), size=int(workload[5:]),
                nz=self.dcgan_nz, ngf=self.dcgan_ngf, out_ch=self.out_ch)
        raise ValueError(f"unknown workload {workload!r}; "
                         f"known: {sorted(GEN_WORKLOADS)}")

    def _lane(self, workload: str, *, batch: int | None = None,
              scan_steps: int | None = None):
        """The lane for ``workload``, built on first use.  ``batch`` /
        ``scan_steps`` override the configured sizing — restore() passes the
        snapshotted values so a recovered lane compiles the exact geometry
        that was running."""
        lane = self._lanes.get(workload)
        if lane is not None:
            return lane
        p = self._init_params(workload)
        kw = dict(backend=self.backend, interpret=self.interpret,
                  decomposed=self.decomposed, batch=batch or self.batch,
                  compute_dtype=self.compute_dtype)
        if workload == "unet_dec":
            lane = _DiffusionLane(
                p, widths=self.unet_widths, hw=self.unet_hw,
                out_ch=self.out_ch, mesh=self.mesh, spatial=self.spatial,
                scan_steps=(scan_steps if scan_steps is not None
                            else self._lane_scan_steps(workload)), **kw)
        else:
            lane = _DCGANLane(p, nz=self.dcgan_nz, mesh=self.mesh, **kw)
        self._lanes[workload] = lane
        self._idle_ticks[workload] = 0
        return lane

    # ---------------------------------------------------------- scheduling --
    def admission_estimate(self, workload: str, steps: int = 1) -> float | None:
        """Calibrated host-time estimate (us) for one request: the fitted
        per-kind cycles->us mapping applied to the layer table of the
        geometry THIS server executes (``_workload_layers`` — canonical only
        when the server runs canonical widths) x DDIM ``steps``.  None
        without a calibration, or when the calibration lacks a fitted key
        for one of the workload's layer kinds on this server's backend —
        callers must treat that as "no estimate", not zero cost."""
        if self.calibration is None:
            return None
        dtype = ("float32" if self.compute_dtype is None
                 else canon_dtype(self.compute_dtype).name)
        us = self.calibration.predict_layers(self._workload_layers(workload),
                                             backend=self.backend,
                                             dtype=dtype)
        return None if us is None else us * max(steps, 1)

    def submit(self, workload: str, *, steps: int = 1, seed: int = 0,
               slo: str | SLOClass = "standard",
               timeout_ticks: int | None = None) -> int:
        """Enqueue a request; returns its id.  DCGAN is single-shot
        (``steps`` is forced to 1); diffusion runs a ``steps``-step DDIM
        trajectory.  ``slo`` is a name from :data:`SLO_CLASSES` or an
        ad-hoc :class:`SLOClass`; ``timeout_ticks`` overrides the class
        default lifetime."""
        self._lane(workload)        # fail fast on unknown workloads
        if isinstance(slo, str):
            try:
                slo = SLO_CLASSES[slo]
            except KeyError:
                raise ValueError(f"unknown SLO class {slo!r}; known: "
                                 f"{sorted(SLO_CLASSES)}") from None
        if workload != "unet_dec":
            steps = 1
        req = GenRequest(self._next_rid, workload, steps, seed, self._tick,
                         slo=slo,
                         timeout_ticks=(slo.timeout_ticks
                                        if timeout_ticks is None
                                        else timeout_ticks))
        req.est_us = self.admission_estimate(workload, steps)
        self._next_rid += 1
        self._pending.append(req)
        self._requests[req.rid] = req
        return req.rid

    def cancel(self, rid: int, status: str = "cancelled") -> bool:
        """Cancel a request wherever it lives.

        Queued requests leave the queue; in-flight requests vacate their
        slot (reusable on the next tick; the lane's active mask keeps the
        stale image rows out of every future substep).  Terminal requests
        (done or already cancelled) are left untouched.  Returns whether
        anything was cancelled.  No result is ever recorded for a cancelled
        request.
        """
        req = self._requests.get(rid)
        if req is None or req.status in ("done", "cancelled", "timeout",
                                         "shed", "corrupt"):
            return False
        if req.status == "pending":
            self._pending.remove(req)
        else:                                   # active: vacate the slot
            lane = self._lanes[req.workload]
            lane.release(lane.slots.index(req))
        req.status = status
        return True

    def _expire(self) -> None:
        """Time out requests (queued or in-flight) past their tick budget,
        in rid order.  Only live requests are visited — the queue and the
        occupied slots — so a tick's cost does not grow with the requests
        served before it."""
        live = [*self._pending, *(r for lane in self._lanes.values()
                                  for r in lane.slots if r is not None)]
        due = [r for r in live if r.timeout_ticks is not None
               and self._tick - r.submit_tick >= r.timeout_ticks]
        for req in sorted(due, key=lambda r: r.rid):
            self.cancel(req.rid, status="timeout")

    def _admission_key(self, req: GenRequest):
        """Priority ordering: aged requests first (cross-class starvation
        bound), then SLO rank, then deadline (FIFO within a class — equal
        targets make deadline order arrival order), then arrival."""
        aged = (self._tick - req.submit_tick) >= self.starvation_ticks
        return (0 if aged else 1, req.slo.rank, req.deadline_us(), req.rid)

    def _admit(self) -> tuple[int, int]:
        """Fill free lane slots from the queue, then write the admitted
        slots' noise with one refill call per lane that admitted any;
        returns ``(admitted, refill calls)``."""
        now_us = time.perf_counter() * 1e6
        admitted = refills = 0
        by_lane: dict[str, list[GenRequest]] = {}
        for req in self._pending:
            by_lane.setdefault(req.workload, []).append(req)
        for workload, reqs in by_lane.items():
            lane = self._lane(workload)
            for req in sorted(reqs, key=self._admission_key):
                # deadline-infeasible: the stamped estimate says the SLO is
                # already unmeetable — shed rather than burn the slot
                if (req.est_us is not None
                        and req.deadline_us() - now_us < req.est_us):
                    self._pending.remove(req)
                    req.status = "shed"
                    continue
                slot = lane.free_slot()
                if slot is None:
                    break               # lane full; later classes wait too
                req.admit_tick = self._tick
                req.status = "active"
                lane.admit(req, slot)
                self._pending.remove(req)
                admitted += 1
            refills += lane.refill()
        return admitted, refills

    def _autoscale(self) -> None:
        """Grow a backlogged lane / shrink an underused one, one ladder
        rung (x2 / ÷2) per tick, within ``[min_batch, max_batch]``.  Policy
        is a pure function of queue state, so a given request sequence
        always produces the same batch trajectory (pinned in tests)."""
        backlog: dict[str, int] = {}
        for req in self._pending:
            backlog[req.workload] = backlog.get(req.workload, 0) + 1
        for workload, lane in self._lanes.items():
            want = backlog.get(workload, 0)
            free = lane.batch - lane.active_count
            if want > free and lane.batch < self.max_batch:
                lane.resize(min(lane.batch * 2, self.max_batch))
                self._idle_ticks[workload] = 0
                continue
            half = lane.batch // 2
            if (want == 0 and half >= self.min_batch
                    and lane.active_count <= half):
                self._idle_ticks[workload] += 1
                if self._idle_ticks[workload] >= self.shrink_patience:
                    lane.resize(half)
                    self._idle_ticks[workload] = 0
            else:
                self._idle_ticks[workload] = 0

    # ------------------------------------------------------ fault handling --
    def _lane_tick(self, workload: str, lane) -> list[GenRequest]:
        """One lane dispatch behind the retry/degrade ladder (DESIGN.md §11).

        A raise is retried up to ``max_retries`` times with exponential
        backoff; a lane that keeps failing on a non-xla backend then
        degrades in place to xla (``set_backend`` keeps every trajectory
        where it is) and the ladder restarts on the fallback engine.  An
        xla lane that exhausts its retries propagates — there is no lower
        rung.  Injected ``raise`` faults fire *before* the device call, so
        a retried tick re-enters with untouched lane state (matching the
        real failure mode: pallas errors surface at trace/lower/launch
        time, before the donated image buffer is consumed).
        """
        backoff = self.retry_backoff_s
        attempts, failed = 0, False
        while True:
            try:
                if self.faults is not None and self.faults.take(
                        self._tick, kind="raise", target=workload,
                        backend=lane.backend):
                    raise RuntimeError(
                        f"injected {lane.backend} dispatch failure on lane "
                        f"{workload!r} at tick {self._tick}")
                done = lane.tick(self._tick)
            except Exception:
                failed = True
                attempts += 1
                if attempts <= self.max_retries:
                    self._retries += 1
                    if backoff > 0:
                        time.sleep(backoff)
                    backoff *= 2
                    continue
                if lane.backend != "xla":
                    lane.set_backend("xla")
                    self._degraded[workload] = "xla"
                    attempts, backoff = 0, self.retry_backoff_s
                    continue
                raise
            if failed:
                self._recoveries += 1
            return done

    def _result_ok(self, req: GenRequest) -> bool:
        """Completion-time corruption gate: a non-finite sample is never
        surfaced.  The request re-runs from its seed (bitwise-correct on a
        clean pass) up to ``max_requeues`` times, then lands terminal as
        ``"corrupt"``."""
        if req.result is not None and np.isfinite(req.result).all():
            return True
        req.result = None
        if req.requeues < self.max_requeues:
            req.requeues += 1
            req.status = "pending"
            req.admit_tick = -1
            self._pending.append(req)
            self._recoveries += 1
        else:
            req.status = "corrupt"
        return False

    def _shed_lowest_class(self) -> None:
        """Stuck-tick load shedding: drop every *pending* request of the
        lowest-priority class present (highest SLO rank) — the PR-7 ladder
        applied as back-pressure relief.  In-flight work is never shed."""
        if not self._pending:
            return
        worst = max(r.slo.rank for r in self._pending)
        for req in [r for r in self._pending if r.slo.rank == worst]:
            self._pending.remove(req)
            req.status = "shed"

    def step(self) -> list[GenRequest]:
        """One scheduler tick; returns the requests completed by it.

        Fault-plane injection points, in tick order: ``kill`` (raised
        before any state mutates — simulates the process dying; recovery
        is :meth:`restore` from the last snapshot), ``slow`` (stall inside
        the timed window, seen by the watchdog), ``corrupt`` (poisons a
        lane slot, caught by the completion gate), ``raise`` (inside
        :meth:`_lane_tick`'s retry/degrade ladder).
        """
        t_start = time.perf_counter()
        compiles0 = obs.compiles()
        if self._t0 is None:
            self._t0 = t_start
        inj = self.faults
        if inj is not None and inj.take(self._tick, kind="kill"):
            raise RuntimeError(f"injected server kill at tick {self._tick}")
        with obs.span(obs.GEN_EXPIRE, tick=self._tick):
            self._expire()
        if self.autoscale:
            self._autoscale()
        with obs.span(obs.GEN_ADMIT, tick=self._tick) as sp:
            admitted, refills = self._admit()
            sp.set_metadata(admitted=admitted, refills=refills)
        self._admit_calls += refills
        if inj is not None:
            stall = inj.sleep_faults(self._tick)
            if stall > 0:
                time.sleep(stall)
            for f in inj.take(self._tick, kind="corrupt"):
                lane = (self._lanes.get(f.target) if f.target is not None
                        else next((l for l in self._lanes.values()
                                   if l.busy), None))
                if lane is not None:
                    lane.corrupt(f.slot)
        done: list[GenRequest] = []
        dispatches = substeps = 0
        for workload, lane in self._lanes.items():
            if lane.busy:
                sub0 = lane.substeps
                done.extend(self._lane_tick(workload, lane))
                dispatches += 1
                substeps += lane.substeps - sub0
        self._tick += 1
        t_end = time.perf_counter()
        done = [r for r in done if self._result_ok(r)]
        for req in done:
            req.done_tick = self._tick
            req.done_wall = t_end
            req.status = "done"
            self._done[req.rid] = req
        self._tick_log.append((t_end - t_start, dispatches, len(done),
                               substeps, obs.compiles() != compiles0))
        if self.watchdog is not None and dispatches:
            self._stuck = (self._stuck + 1 if self.watchdog.observe(
                self._tick - 1, t_end - t_start) else 0)
            if self._stuck >= self.stuck_shed_after:
                self._shed_lowest_class()
                self._stuck = 0
        if (self.snapshot_dir is not None and self.snapshot_every > 0
                and self._tick % self.snapshot_every == 0):
            self.snapshot()
        return done

    def run(self) -> dict[int, np.ndarray]:
        """Drain queue + in-flight work; returns ``rid -> image`` for the
        requests that completed (cancelled/timed-out/shed requests are
        absent — their status lives on ``server.request(rid)``)."""
        while self._pending or any(l.busy for l in self._lanes.values()):
            self.step()
        return {rid: r.result for rid, r in sorted(self._done.items())}

    # ---------------------------------------------------- snapshot/restore --
    _CONFIG_ATTRS = ("batch", "backend", "interpret", "decomposed", "spatial",
                     "unet_hw", "out_ch", "dcgan_nz", "dcgan_ngf",
                     "scan_steps", "autoscale", "min_batch", "max_batch",
                     "shrink_patience", "starvation_ticks", "max_retries",
                     "retry_backoff_s", "stuck_shed_after", "max_requeues",
                     "snapshot_every", "snapshot_keep", "compute_dtype")

    def _snapshot_config(self) -> dict:
        cfg = {k: getattr(self, k) for k in self._CONFIG_ATTRS}
        cfg["unet_widths"] = list(self.unet_widths)
        cfg["param_seed"] = self._param_seed
        if self.mesh is not None:
            # geometry only — devices are process-relative.  restore()
            # rebuilds the same (shape, axes) mesh over whatever devices
            # exist, or reshapes onto a mesh override (resharded restore).
            cfg["mesh"] = {"shape": [int(self.mesh.shape[a])
                                     for a in self.mesh.axis_names],
                           "axes": list(self.mesh.axis_names)}
        return cfg

    @staticmethod
    def _req_meta(req: GenRequest) -> dict:
        """JSON form of everything about a request except its image payload
        (results ride as ``done:<rid>`` arrays; in-flight image state lives
        in the lane arrays).  Wall-clock fields are deliberately absent:
        ``perf_counter`` is process-relative, so restore() re-bases every
        live request to one common "now" — deadline order within a class
        falls back to rid, which *is* arrival order."""
        return {"rid": req.rid, "workload": req.workload, "steps": req.steps,
                "seed": req.seed, "submit_tick": req.submit_tick,
                "slo": {"name": req.slo.name, "rank": req.slo.rank,
                        "target_us": req.slo.target_us,
                        "timeout_ticks": req.slo.timeout_ticks},
                "timeout_ticks": req.timeout_ticks,
                "admit_tick": req.admit_tick, "done_tick": req.done_tick,
                "status": req.status, "est_us": req.est_us,
                "requeues": req.requeues}

    @staticmethod
    def _req_from_meta(m: dict, now: float) -> GenRequest:
        s = m["slo"]
        req = GenRequest(m["rid"], m["workload"], m["steps"], m["seed"],
                         m["submit_tick"],
                         slo=SLOClass(s["name"], s["rank"],
                                      target_us=s["target_us"],
                                      timeout_ticks=s["timeout_ticks"]),
                         timeout_ticks=m["timeout_ticks"])
        req.submit_wall = now
        req.admit_tick = m["admit_tick"]
        req.done_tick = m["done_tick"]
        req.status = m["status"]
        req.est_us = m["est_us"]
        req.requeues = m["requeues"]
        if req.status == "done":
            req.done_wall = now
        return req

    def snapshot(self, directory: str | None = None) -> str:
        """Checkpoint the full scheduler-visible state atomically.

        Everything a restored server needs to finish the drain exactly —
        per-slot image tensors and lane parameters (arrays), trajectory
        cursors, request/SLO metadata, the admission queue, completed
        results, and the fault-tolerance counters (manifest ``extra``) —
        goes through the ``repro.checkpoint`` manifest+COMMITTED layout, so
        a crash mid-snapshot leaves the previous snapshot intact.
        """
        directory = directory or self.snapshot_dir
        if directory is None:
            raise ValueError("snapshot() needs a directory argument or a "
                             "server constructed with snapshot_dir=")
        arrays: dict[str, np.ndarray] = {}
        lanes_meta: dict[str, dict] = {}
        for wl, lane in self._lanes.items():
            lm = {"kind": lane.kind, "batch": lane.batch,
                  "backend": lane.backend, "scan_steps": lane.scan_steps,
                  "device_steps": lane.device_steps,
                  "substeps": lane.substeps,
                  "idle_ticks": self._idle_ticks[wl],
                  "slots": [None if s is None else self._req_meta(s)
                            for s in lane.slots]}
            if lane.kind == "diffusion":
                lm["pos"] = [int(p) for p in lane._pos]
            lanes_meta[wl] = lm
            for k, v in lane.state_arrays().items():
                arrays[f"lane:{wl}:{k}"] = v
            leaves, _ = jax.tree_util.tree_flatten(lane.params)
            for i, leaf in enumerate(leaves):
                arrays[f"param:{wl}:{i:05d}"] = np.asarray(
                    jax.device_get(leaf))
        done_meta, dropped_meta = [], []
        for req in self._requests.values():
            if req.status == "done":
                done_meta.append(self._req_meta(req))
                arrays[f"done:{req.rid:08d}"] = req.result
            elif req.status in ("cancelled", "timeout", "shed", "corrupt"):
                dropped_meta.append(self._req_meta(req))
        meta = {"tick": self._tick, "next_rid": self._next_rid,
                "config": self._snapshot_config(), "lanes": lanes_meta,
                "pending": [self._req_meta(r) for r in self._pending],
                "done": done_meta, "dropped": dropped_meta,
                "degraded": dict(self._degraded), "retries": self._retries,
                "recoveries": self._recoveries,
                "snapshots": self._snapshots + 1}
        ckpt.save_checkpoint(directory, self._tick, arrays,
                             keep=self.snapshot_keep, extra=meta)
        self._snapshots += 1
        return directory

    @classmethod
    def restore(cls, directory: str, *, step: int | None = None,
                **overrides) -> "GenServer":
        """Rebuild a server from the latest (or given) snapshot and resume.

        The drain continues exactly where the snapshot left it: because the
        mixed-timestep scan is timestep-*data* driven and the image state
        round-trips bitwise through the checkpoint, a restored drain on xla
        reproduces the uninterrupted run sample-for-sample (pinned in
        ``tests/test_chaos.py``).  Work that completed *after* the snapshot
        in the killed process is simply recomputed — deterministically, to
        the same images.  ``overrides`` are constructor keywords (pass
        ``calibration=``/``mesh=``/``faults=`` here; they are not
        serialized).
        """
        if step is None:
            step = ckpt.latest_step(directory)
        if step is None:
            raise FileNotFoundError(
                f"no committed snapshot under {directory!r}")
        arrays, meta = ckpt.load_flat(directory, step)
        cfg = dict(meta["config"])
        cfg["unet_widths"] = tuple(cfg["unet_widths"])
        mesh_cfg = cfg.pop("mesh", None)
        if mesh_cfg is not None and "mesh" not in overrides:
            # same-geometry restore: rebuild the snapshotted mesh over this
            # process's devices.  A *resharded* restore (different device
            # count) passes mesh= in overrides instead; the lane state is
            # re-placed through image_sharding either way, so the drain is
            # bitwise regardless of the mesh it resumes on.
            shape = tuple(mesh_cfg["shape"])
            if math.prod(shape) > len(jax.devices()):
                raise ValueError(
                    f"snapshot took a {shape} mesh but only "
                    f"{len(jax.devices())} devices exist; pass mesh= to "
                    f"restore() to reshard")
            cfg["mesh"] = auto_mesh(shape, tuple(mesh_cfg["axes"]))
        kw = dict(cfg, snapshot_dir=directory)
        kw.update(overrides)
        server = cls(**kw)
        now = time.perf_counter()
        server._tick = meta["tick"]
        server._next_rid = meta["next_rid"]
        server._degraded = dict(meta["degraded"])
        server._retries = meta["retries"]
        server._recoveries = meta["recoveries"] + 1  # this restore is one
        server._snapshots = meta["snapshots"]
        for wl, lm in meta["lanes"].items():
            lane = server._lane(wl, batch=lm["batch"],
                                scan_steps=lm["scan_steps"])
            if lm["backend"] != lane.backend:
                lane.set_backend(lm["backend"])
            prefix = f"param:{wl}:"
            leaves = [jnp.asarray(arrays[k])
                      for k in sorted(k for k in arrays
                                      if k.startswith(prefix))]
            _, treedef = jax.tree_util.tree_flatten(lane.params)
            params = jax.tree_util.tree_unflatten(treedef, leaves)
            lane.params = params if server.mesh is None else jax.device_put(
                params, shd.replicated(server.mesh))
            sp = f"lane:{wl}:"
            lane.load_state({k[len(sp):]: v for k, v in arrays.items()
                             if k.startswith(sp)})
            lane.device_steps = lm["device_steps"]
            lane.substeps = lm["substeps"]
            for i, sm in enumerate(lm["slots"]):
                if sm is None:
                    continue
                req = cls._req_from_meta(sm, now)
                lane.slots[i] = req
                lane.active[i] = True
                if lane.kind == "diffusion":
                    lane._traj[i] = ddim_timesteps(req.steps)
                server._requests[req.rid] = req
            if lane.kind == "diffusion":
                lane._pos = list(lm["pos"])
            server._idle_ticks[wl] = lm["idle_ticks"]
        for m in meta["pending"]:
            req = cls._req_from_meta(m, now)
            server._pending.append(req)
            server._requests[req.rid] = req
        for m in meta["done"]:
            req = cls._req_from_meta(m, now)
            req.result = arrays[f"done:{req.rid:08d}"]
            server._done[req.rid] = req
            server._requests[req.rid] = req
        for m in meta["dropped"]:
            server._requests[m["rid"]] = cls._req_from_meta(m, now)
        return server

    # ------------------------------------------------------------- metrics --
    @property
    def completed(self) -> dict[int, GenRequest]:
        return dict(self._done)

    def request(self, rid: int) -> GenRequest:
        """Any submitted request by id (whatever its lifecycle state)."""
        return self._requests[rid]

    def lane_devices(self) -> dict[str, set]:
        """Devices holding each built lane's slot state (the diffusion image
        batch, the DCGAN latents): where a meshed lane really runs."""
        return {w: (lane.x if lane.kind == "diffusion" else lane.z)
                .sharding.device_set for w, lane in self._lanes.items()}

    def stats(self) -> dict[str, float]:
        wall = (time.perf_counter() - self._t0) if self._t0 else 0.0
        dev_steps = sum(l.device_steps for l in self._lanes.values())
        substeps = sum(l.substeps for l in self._lanes.values())
        n = len(self._done)
        waits = [r.wait_ticks for r in self._done.values()]
        lats = sorted(r.latency_s for r in self._done.values())
        statuses = [r.status for r in self._requests.values()]
        # warm-steady window: ticks in which JAX built no executable —
        # first-tick (and resize-tick) compiles are excluded the same way
        # ``kernels.util.time_call`` excludes compile from every other
        # timed region in the repo
        warm = [t for t in self._tick_log if not t[4]]
        warm_wall = sum(t[0] for t in warm)
        warm_imgs = sum(t[2] for t in warm)
        warm_sub = sum(t[3] for t in warm)
        pct = (lambda p: cm.np_percentile(lats, p)) if lats else (lambda p: 0.0)
        return {
            "requests": n,
            "ticks": self._tick,
            "device_steps": dev_steps,
            "substeps": substeps,
            "wall_s": wall,
            # whole-window throughput (includes first-tick compile — kept
            # for trajectory continuity with pre-fix revisions)
            "images_per_s": n / wall if wall else 0.0,
            "steps_per_s": dev_steps / wall if wall else 0.0,
            # warm-steady throughput: compile ticks excluded
            "warm_wall_s": warm_wall,
            "warm_images_per_s": warm_imgs / warm_wall if warm_wall else 0.0,
            "warm_steps_per_s": warm_sub / warm_wall if warm_wall else 0.0,
            "latency_p50_s": pct(50.0),
            "latency_p99_s": pct(99.0),
            "mean_wait_ticks": float(np.mean(waits)) if waits else 0.0,
            "max_wait_ticks": float(np.max(waits)) if waits else 0.0,
            "cancelled": float(statuses.count("cancelled")),
            "timeout": float(statuses.count("timeout")),
            "shed": float(statuses.count("shed")),
            # fault-tolerance counters (DESIGN.md §11)
            "degraded": float(len(self._degraded)),
            "retries": float(self._retries),
            "recoveries": float(self._recoveries),
            "corrupt": float(statuses.count("corrupt")),
            "snapshots": float(self._snapshots),
            # lane refill calls since the server was made: requests
            # admitted / admit_calls is how far admission batches
            "admit_calls": float(self._admit_calls),
            # executables JAX built or loaded since the server was made
            "compiles": float(obs.compiles() - self._compiles0),
        }


def reference_sample(params: dict, *, steps: int, seed: int, image_size: int,
                     out_ch: int = 3, backend: str = "xla",
                     interpret: bool | None = None, decomposed: bool = True,
                     t_max: int = DDIM_T_MAX) -> np.ndarray:
    """Unbatched single-request DDIM loop — the parity oracle the served
    (mixed-timestep, continuously batched, K-step fused) path must match
    bitwise on xla / <= 1e-5 across backends.  Deliberately K=1: the fused
    scan must reproduce the one-step-at-a-time trajectory exactly."""
    step = jax.jit(make_gen_scan_step(1, t_max=t_max, decomposed=decomposed,
                                      backend=backend, interpret=interpret),
                   donate_argnums=(1,))
    traj = ddim_timesteps(steps, t_max)
    x = init_noise(seed, (image_size, image_size, out_ch))[None]
    for i, t in enumerate(traj):
        nxt = int(traj[i + 1]) if i + 1 < len(traj) else -1
        batch = {"t": jnp.full((1, 1), int(t), jnp.int32),
                 "t_next": jnp.full((1, 1), nxt, jnp.int32),
                 "active": jnp.ones((1, 1), bool)}
        x = step(params, x, batch)
    return np.asarray(x)[0]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="unet_dec",
                    choices=sorted(GEN_WORKLOADS))
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--steps", default="8,5,3",
                    help="comma list of diffusion step budgets, cycled")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--backend", default="xla", choices=("xla", "pallas"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scan-steps", default="auto",
                    help="DDIM steps fused per dispatch (int or 'auto': "
                         "sized against tick latency from the calibration)")
    ap.add_argument("--slo", default="standard", choices=sorted(SLO_CLASSES),
                    help="SLO class stamped on every submitted request")
    ap.add_argument("--timeout-ticks", type=int, default=None,
                    help="per-request scheduler-tick timeout")
    ap.add_argument("--autoscale", action="store_true",
                    help="grow/shrink lane batches with backlog")
    ap.add_argument("--devices", type=int, default=1,
                    help="span the lanes over a mesh of this many devices "
                         "(DESIGN.md §13; simulate on CPU with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    ap.add_argument("--spatial", action="store_true",
                    help="also shard image rows over the mesh's model axis")
    ap.add_argument("--snapshot-dir", default=None,
                    help="checkpoint scheduler state here (DESIGN.md §11); "
                         "with an existing committed snapshot the server "
                         "restores and resumes the drain")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="auto-snapshot every N ticks (0: on demand only)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny widths (CI): 16x16 images, small DCGAN")
    ns = ap.parse_args()

    from repro.core import calibrate as cal
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    scan: int | str = ns.scan_steps if ns.scan_steps == "auto" \
        else int(ns.scan_steps)
    kw: dict = dict(batch=ns.batch, backend=ns.backend, scan_steps=scan,
                    autoscale=ns.autoscale,
                    snapshot_dir=ns.snapshot_dir,
                    snapshot_every=ns.snapshot_every)
    if ns.devices > 1:
        from repro.launch.mesh import make_smoke_mesh

        if ns.devices > len(jax.devices()):
            raise SystemExit(
                f"--devices {ns.devices} but only {len(jax.devices())} "
                f"devices exist (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N to simulate)")
        kw.update(mesh=make_smoke_mesh(ns.devices), spatial=ns.spatial)
    if ns.smoke or (ns.backend == "pallas" and jax.default_backend() == "cpu"):
        # interpret-mode pallas needs tiny widths to stay tractable on CPU
        if not ns.smoke:
            print("[serve_gen] pallas on a CPU backend runs interpret mode: "
                  "serving smoke widths, not the canonical ones")
        kw.update(unet_widths=(8, 8), unet_hw=4, dcgan_nz=16, dcgan_ngf=4)
    cache = cal.default_cache_path()
    if cache.exists():          # host-grounded admission estimates when a
        kw["calibration"] = cal.Calibration.load(cache)  # table was captured
    step_list = [int(s) for s in ns.steps.split(",")]
    if ns.snapshot_dir and ckpt.latest_step(ns.snapshot_dir) is not None:
        server = GenServer.restore(
            ns.snapshot_dir, snapshot_every=ns.snapshot_every,
            calibration=kw.get("calibration"))
        print(f"[serve_gen] restored tick {server._tick} from "
              f"{ns.snapshot_dir} — resuming drain")
    else:
        server = GenServer(**kw)
        for i in range(ns.requests):
            server.submit(ns.workload, steps=step_list[i % len(step_list)],
                          seed=ns.seed + i, slo=ns.slo,
                          timeout_ticks=ns.timeout_ticks)
    images = server.run()
    st = server.stats()
    lane = server._lanes.get(ns.workload)
    print(f"[serve_gen] {st['requests']} requests "
          f"({ns.workload}, steps {ns.steps}, slo={ns.slo}, "
          f"scan_steps={getattr(lane, 'scan_steps', 1)}) in "
          f"{st['wall_s']:.2f}s over {st['ticks']} ticks / "
          f"{st['device_steps']} dispatches ({st['substeps']} substeps): "
          f"{st['images_per_s']:.2f} img/s "
          f"(warm {st['warm_images_per_s']:.2f}), "
          f"p50 {st['latency_p50_s'] * 1e3:.0f} ms / "
          f"p99 {st['latency_p99_s'] * 1e3:.0f} ms")
    if st["degraded"] or st["retries"] or st["recoveries"] or st["snapshots"]:
        print(f"[serve_gen] fault plane: {st['degraded']:.0f} degraded "
              f"lane(s), {st['retries']:.0f} retries, "
              f"{st['recoveries']:.0f} recoveries, "
              f"{st['snapshots']:.0f} snapshots")
    dropped = int(st["cancelled"] + st["timeout"] + st["shed"] +
                  st["corrupt"])
    if dropped:
        print(f"[serve_gen] dropped {dropped} request(s): "
              f"{st['cancelled']:.0f} cancelled, {st['timeout']:.0f} "
              f"timed out, {st['shed']:.0f} shed at admission")
    if images:
        shp = next(iter(images.values())).shape
        print(f"[serve_gen] image shape {shp}; "
              f"mean wait {st['mean_wait_ticks']:.1f} ticks "
              f"(max {st['max_wait_ticks']:.0f})")
    rep = cm.serve_report(GEN_WORKLOADS[ns.workload](),
                          steps=max(step_list),
                          scan_steps=getattr(lane, "scan_steps", 1),
                          steps_list=[step_list[i % len(step_list)]
                                      for i in range(ns.requests)],
                          calibration=server.calibration,
                          backend=ns.backend, devices=max(ns.devices, 1))
    print(f"[serve_gen] cycle model ({ns.workload}, canonical widths, "
          f"{max(step_list)} steps/sample, "
          f"{rep['dispatches_per_image']:.0f} dispatches/image): "
          f"{rep['images_per_s_ours']:.1f} img/s decomposed vs "
          f"{rep['images_per_s_naive']:.1f} naive "
          f"({rep['serve_speedup_vs_naive']:.2f}x); modeled drain "
          f"p50 {rep['latency_p50_ms']:.1f} ms / "
          f"p99 {rep['latency_p99_ms']:.1f} ms")
    if "calibrated_us_per_image" in rep:
        print(f"[serve_gen] calibrated host estimate: "
              f"{rep['calibrated_us_per_image']:.0f} us/image "
              f"({rep['calibrated_images_per_s']:.2f} img/s on this host)")


if __name__ == "__main__":
    main()
