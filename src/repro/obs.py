"""The names the program gives its work in a profiler trace, and a count
of the executables it builds.

Two kinds of name, both read from a ``jax.profiler`` trace:

* **device scopes** (``jax.named_scope``): they live in the HLO metadata
  only, so they cost nothing when the program runs.  A TPU trace carries
  each op's name stack (its ``tf_op``), e.g.
  ``jit(forward)/engine.dilated/jit(conv2d)/pallas_call``.  Every conv
  falls under one ``engine.*`` scope (set by ``core.decompose.conv2d``);
  the decomposition's layout passes under ``layout.*``; the custom-VJP
  backward rules under ``grad.dx`` / ``grad.dw``; the train step's loss
  and optimizer under ``train.*``; ESPNet's elementwise work between its
  convolutions under ``esp.*`` (each ESP module's merge, and the input
  reinforcement).
* **host spans** (:func:`span`, a ``jax.profiler.TraceAnnotation``): they
  write into the profiler's own trace, on the clock of the device planes,
  and cost a check of a flag when no profiler runs.  ``GenServer.step``
  splits each tick into ``gen.*`` spans.

:func:`compiles` counts the executables JAX builds or loads from the
persistent cache (a ``jax.monitoring`` listener on the backend-compile
event), and each one also leaves a zero-length ``repro.compile`` span.
"""

from __future__ import annotations

import threading

import jax
from jax import monitoring

ENGINE_DENSE = "engine.dense"
ENGINE_DILATED = "engine.dilated"
ENGINE_TRANSPOSED = "engine.transposed"
ENGINES = (ENGINE_DENSE, ENGINE_DILATED, ENGINE_TRANSPOSED)

LAYOUT_PHASE_SPLIT = "layout.phase_split"
LAYOUT_PHASE_STITCH = "layout.phase_stitch"
LAYOUT_PARITY_INTERLEAVE = "layout.parity_interleave"
LAYOUT_PAD = "layout.pad"
LAYOUT_CROP = "layout.crop"

ESP_MERGE = "esp.merge"
ESP_REINFORCE = "esp.reinforce"

GRAD_DX = "grad.dx"
GRAD_DW = "grad.dw"
TRAIN_LOSS = "train.loss"
TRAIN_OPTIMIZER = "train.optimizer"

GEN_EXPIRE = "gen.expire"
GEN_ADMIT = "gen.admit"
GEN_DISPATCH = "gen.dispatch"
GEN_FETCH = "gen.fetch"

COMPILE = "repro.compile"

#: JAX records this duration around every ``compile_or_get_cached``: once
#: per new executable, whether compiled or loaded from the persistent cache
#: (whose ``/jax/compilation_cache/cache_hits`` event fires inside it, so
#: counting both would count a load twice)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span in the profiler's trace, e.g. ``with span(GEN_ADMIT,
    tick=3) as s: ...; s.set_metadata(admitted=n)``."""
    return jax.profiler.TraceAnnotation(name, **meta)


_lock = threading.Lock()
_compiles = 0


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    global _compiles
    if event != _BACKEND_COMPILE_EVENT:
        return
    with _lock:
        _compiles += 1
    with span(COMPILE, fun=str(kw.get("fun_name", ""))):
        pass


def compiles() -> int:
    """Executables built (or loaded) by this process since it imported
    this module."""
    return _compiles


monitoring.register_event_duration_secs_listener(_on_duration)
