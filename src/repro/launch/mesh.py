"""Mesh factories: one place every loop gets its device mesh from.

``make_production_mesh`` builds the 256-chip single-pod / 512-chip two-pod
meshes the dry-run and sharding rules target; ``make_smoke_mesh`` builds a
small ``(data, model)`` mesh over whatever devices exist — 1 CPU device in
the tests, 8 fake devices under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
— so the same loop code runs at every scale.  Both are FUNCTIONS: importing
this module never touches jax device state (the dry-run must set XLA_FLAGS
before the first jax init).

CPU-scale smoke (any launch loop picks the mesh up automatically):

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --reduced
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --reduced --steps 3 --batch 8 --seq 32
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: shardings propagate
    through GSPMD, and eager ops may mix meshed and single-device arrays
    (the serving lanes write one slot at a time).  ``make_mesh``'s own
    default makes the axes ``Explicit``, which refuses that mix."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_smoke_mesh(devices: int | None = None, model: int = 2):
    """Small mesh over however many (possibly fake) devices exist."""
    n = devices or len(jax.devices())
    model = min(model, n)
    return auto_mesh((n // model, model), ("data", "model"))


def make_train_mesh(devices: int | None = None):
    """1-D ``(data,)`` mesh for the sharded conv train step (DESIGN.md §13).

    The sharded recipes chunk the batch over ``data`` only; a model axis
    would just replicate, so the whole device count goes to data.
    """
    n = devices or len(jax.devices())
    return auto_mesh((n,), ("data",))
