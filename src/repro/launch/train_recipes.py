"""Mixed-precision train recipes for the conv workloads (DESIGN.md §12).

One recipe per decomposition workload — ENet / ESPNet (segmentation NLL)
and the DCGAN generator (pixel regression smoke objective) — each wiring
the same four-part bf16 contract around the model's ``forward``:

* **fp32 masters**: parameters (and AdamW state) stay fp32; the forward
  casts per-layer via ``compute_dtype`` so only activations are bf16;
* **fp32 loss**: logits/images are promoted to fp32 before the reduction,
  so the objective itself never rounds in bf16;
* **dynamic loss scaling** (:class:`repro.optim.DynamicLossScale`): the
  loss is amplified before ``grad`` and the gradients divided after;
* **skip-on-nonfinite**: a step whose unscaled gradients contain inf/nan
  applies *no* update (params and optimizer state pass through bitwise via
  :func:`repro.optim.select_tree`) and backs the scale off.

``compute_dtype=None`` degenerates to the plain fp32 step (the scaler
still runs, at scale 1 if configured so) — the parity tests train both
and compare.  Everything jits into ONE step function; the skip logic is
branchless so a skipped step costs the same dispatch.

:func:`make_sharded_train_step` is the multi-device variant (DESIGN.md
§13): the batch is pre-chunked into a fixed number of *virtual shards*
(independent of the mesh size), per-chunk gradients are taken under
``shard_map`` over the data axes, and the cross-device reduction goes
through :func:`repro.distributed.compression.mesh_allreduce` — an
all-gather of the chunk stacks plus ONE fixed-order sum, so the
reduction tree (and therefore every fp32 rounding) is identical on every
mesh size.  With the dense transport the step is 1-device ≡ N-device
*bitwise*; the bf16 transport halves the collective's wire size and is
held to convergence bounds instead.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.distributed import compression as _compression
from repro.distributed import sharding as _sharding
from repro.models import dcgan, enet, espnet
from repro.optim import (DynamicLossScale, LossScaleState, adamw_init,
                         adamw_update, select_tree)

#: workloads with a recipe here (DCGAN trains the generator alone against a
#: pixel target — the adversarial game is out of scope for a step recipe).
RECIPES = ("enet", "espnet", "dcgan")


class TrainState(NamedTuple):
    """Everything one recipe step threads: fp32 params + AdamW + scaler."""
    params: dict
    opt: object
    scale: LossScaleState


def _seg_loss(forward, params, batch, **fw_kw):
    """Mean per-pixel NLL, reduced in fp32 regardless of compute dtype."""
    logits = forward(params, batch["image"], **fw_kw)
    with jax.named_scope(obs.TRAIN_LOSS):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["label"][..., None], axis=-1)
        return jnp.mean(nll)


def _gen_loss(params, batch, **fw_kw):
    """Generator pixel-regression smoke objective (fp32 reduction)."""
    img = dcgan.forward(params, batch["z"], **fw_kw)
    with jax.named_scope(obs.TRAIN_LOSS):
        err = img.astype(jnp.float32) - batch["target"].astype(jnp.float32)
        return jnp.mean(jnp.square(err))


def _loss_fn(model: str, *, backend: str, decomposed: bool,
             interpret: bool | None, compute_dtype: str | None):
    if model in ("enet", "espnet"):
        forward = enet.forward if model == "enet" else espnet.forward
        kw = dict(backend=backend, decomposed=decomposed,
                  interpret=interpret, compute_dtype=compute_dtype)
        return functools.partial(_seg_loss, forward, **kw)
    if model == "dcgan":
        kw = dict(backend=backend, decomposed=decomposed,
                  interpret=interpret, compute_dtype=compute_dtype)
        return functools.partial(_gen_loss, **kw)
    raise ValueError(f"unknown recipe {model!r}; known: {RECIPES}")


def init_state(params: dict,
               scaler: DynamicLossScale | None = None) -> TrainState:
    """fp32 masters + AdamW state + loss-scale state for a recipe step."""
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.float32), params)
    scaler = scaler or DynamicLossScale()
    return TrainState(params, adamw_init(params), scaler.init())


def make_train_step(model: str, *, backend: str = "xla",
                    decomposed: bool = True, interpret: bool | None = None,
                    compute_dtype: str | None = None,
                    scaler: DynamicLossScale | None = None,
                    lr: float | Callable[[jax.Array], jax.Array] = 1e-3,
                    weight_decay: float = 1e-4):
    """Jitted ``step(state, batch) -> (state', metrics)`` for one recipe.

    ``batch`` is ``{"image", "label"}`` for the segmentation recipes and
    ``{"z", "target"}`` for the generator.  ``lr`` is a constant or a
    schedule called with the optimizer's step count (0 on the first step).
    Metrics: ``loss`` (unscaled, fp32), ``grad_norm`` (of the *applied*
    gradients; 0 on a skipped step), ``scale`` (loss scale after the
    update), ``skipped`` (1.0 when non-finite gradients suppressed the
    update).
    """
    scaler = scaler or DynamicLossScale()
    loss_fn = _loss_fn(model, backend=backend, decomposed=decomposed,
                       interpret=interpret, compute_dtype=compute_dtype)

    @jax.jit
    def step(state: TrainState, batch: dict):
        def scaled_loss(p):
            loss = loss_fn(p, batch)
            return scaler.scale(state.scale, loss), loss

        (_, loss), grads = jax.value_and_grad(scaled_loss,
                                              has_aux=True)(state.params)
        with jax.named_scope(obs.TRAIN_OPTIMIZER):
            grads = scaler.unscale(state.scale, grads)
            finite = scaler.all_finite(grads)
            # a non-finite gradient must not reach the AdamW moments: zero
            # the grads before the update, then discard the update anyway
            zeros = jax.tree_util.tree_map(jnp.zeros_like, grads)
            safe = select_tree(finite, grads, zeros)
            step_lr = lr(state.opt.step) if callable(lr) else jnp.float32(lr)
            new_params, new_opt, gnorm = adamw_update(
                safe, state.opt, state.params, lr=step_lr,
                weight_decay=weight_decay)
            new_params = select_tree(finite, new_params, state.params)
            new_opt = select_tree(finite, new_opt, state.opt)
            scale_state = scaler.update(state.scale, finite)
        metrics = {"loss": loss,
                   "grad_norm": jnp.where(finite, gnorm, 0.0),
                   "scale": scale_state.scale,
                   "skipped": 1.0 - finite.astype(jnp.float32)}
        return TrainState(new_params, new_opt, scale_state), metrics

    return step


# ---------------------------------------------------------------------------
# Sharded train step (DESIGN.md §13)
# ---------------------------------------------------------------------------

def shard_batch(mesh, batch: dict, *, virtual_shards: int = 8):
    """Pre-chunk a recipe batch into ``(C, B/C, ...)`` and place it.

    ``C = virtual_shards`` is FIXED (independent of the mesh), so the chunk
    boundaries — and with them every per-chunk rounding — never move when the
    device count changes.  The leading chunk axis shards over the mesh's data
    axes; each device vmaps over its local chunks.
    """
    c = virtual_shards
    nd = _sharding.data_axis_size(mesh)
    if c % nd:
        raise ValueError(
            f"virtual_shards={c} must be a multiple of the data-axis "
            f"extent {nd} so every device holds whole chunks")

    def chunk(x):
        b = x.shape[0]
        if b % c:
            raise ValueError(
                f"batch dim {b} not divisible by virtual_shards={c}")
        return x.reshape((c, b // c) + x.shape[1:])

    axes = _sharding.data_axes(mesh)
    spec = P(axes if len(axes) > 1 else axes[0])
    return jax.device_put(jax.tree_util.tree_map(chunk, batch),
                          NamedSharding(mesh, spec))


def place_state(mesh, state: TrainState) -> TrainState:
    """Replicate a :class:`TrainState` over every device of the mesh."""
    return jax.device_put(state, _sharding.replicated(mesh))


def make_sharded_train_step(model: str, mesh, *, virtual_shards: int = 8,
                            grad_transport: str = "dense",
                            backend: str = "xla", decomposed: bool = True,
                            interpret: bool | None = None,
                            compute_dtype: str | None = None,
                            scaler: DynamicLossScale | None = None,
                            lr: float = 1e-3, weight_decay: float = 1e-4):
    """Jitted multi-device ``step(state, chunks) -> (state', metrics)``.

    ``chunks`` comes from :func:`shard_batch` (leading virtual-shard axis
    sharded over the mesh's data axes); ``state`` from :func:`place_state`.
    The recipe contract is identical to :func:`make_train_step` — fp32
    masters, fp32 loss reduction, dynamic loss scaling, branchless
    skip-on-nonfinite — with the gradient reduction routed through
    :func:`repro.distributed.compression.mesh_allreduce`:

    * ``grad_transport="dense"`` — fp32 chunk stacks on the wire; the step is
      **bitwise identical** on every mesh size (the fixed-order sum is the
      only cross-chunk reduction).
    * ``grad_transport="bf16"`` — bf16 stacks on the wire (2x smaller
      collective in the compiled HLO); convergence-bounded, not bitwise.

    XLA backend only: each device takes its chunks' gradients one after
    another with ``lax.map`` inside ``shard_map``, and the bitwise
    mesh-invariance is pinned for the XLA engine alone — the Pallas kernels
    have not been run under ``shard_map``.
    """
    if backend != "xla":
        raise ValueError(
            f"sharded step requires backend='xla', got {backend!r}")
    scaler = scaler or DynamicLossScale()
    loss_fn = _loss_fn(model, backend=backend, decomposed=decomposed,
                       interpret=interpret, compute_dtype=compute_dtype)
    axes = _sharding.data_axes(mesh)
    axis = axes if len(axes) > 1 else axes[0]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(), P(axis)), out_specs=(P(), P()),
        check_vma=False)
    def chunk_grads(params, scale_state, chunks):
        # per-chunk scaled-loss gradients, SEQUENTIALLY per device: lax.map
        # compiles one per-chunk graph applied to every chunk, so the chunk
        # backward is identical on every mesh size (a vmap over the local
        # chunks fuses at the local width and breaks bitwise at ~1e-8).
        # Only the reduction order could then differ — mesh_allreduce pins it.
        def scaled_chunk_loss(p, chunk):
            loss = loss_fn(p, chunk)
            return scaler.scale(scale_state, loss), loss

        def one(chunk):
            (_, loss), g = jax.value_and_grad(
                scaled_chunk_loss, has_aux=True)(params, chunk)
            return g, loss

        grads, losses = jax.lax.map(one, chunks)
        grads = _compression.mesh_allreduce(grads, axis,
                                            transport=grad_transport)
        losses = jax.lax.all_gather(losses, axis, axis=0, tiled=True)
        return grads, losses

    @jax.jit
    def step(state: TrainState, chunks: dict):
        grad_sum, losses = chunk_grads(state.params, state.scale, chunks)
        # equal-size chunks: the batch mean is the mean of chunk means
        loss = jnp.sum(losses.astype(jnp.float32)) / virtual_shards
        with jax.named_scope(obs.TRAIN_OPTIMIZER):
            grads = scaler.unscale(state.scale, grad_sum)
            grads = jax.tree_util.tree_map(
                lambda g: g / virtual_shards, grads)
            finite = scaler.all_finite(grads)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, grads)
            safe = select_tree(finite, grads, zeros)
            new_params, new_opt, gnorm = adamw_update(
                safe, state.opt, state.params, lr=jnp.float32(lr),
                weight_decay=weight_decay)
            new_params = select_tree(finite, new_params, state.params)
            new_opt = select_tree(finite, new_opt, state.opt)
            scale_state = scaler.update(state.scale, finite)
        metrics = {"loss": loss,
                   "grad_norm": jnp.where(finite, gnorm, 0.0),
                   "scale": scale_state.scale,
                   "skipped": 1.0 - finite.astype(jnp.float32)}
        return TrainState(new_params, new_opt, scale_state), metrics

    return step


__all__ = ["RECIPES", "TrainState", "init_state", "make_train_step",
           "shard_batch", "place_state", "make_sharded_train_step"]
