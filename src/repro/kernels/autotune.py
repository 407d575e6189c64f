"""Per-shape ``(th, tc)`` tile autotuning for the Pallas engines (DESIGN.md §7).

The kernels' tile shape used to be hard-coded at ``(th, tc) = (8, 128)``
regardless of layer geometry.  This module ranks a small candidate grid per
*(engine kind, input shape, kernel, stride, dilation, dtype, epilogue)* key
with the analytic policy (:mod:`repro.kernels.tiling_policy` — VMEM
footprint + MXU occupancy, DESIGN.md §12), times only the top few plus
``DEFAULT_TILES``, and caches the winner — in memory for the process, and
on disk so the cost is paid once per machine.  ``$REPRO_AUTOTUNE_SWEEP=1``
forces the old exhaustive timing of the whole grid.

Cache layout and invalidation (DESIGN.md §7):

* one JSON file per ``(device kind, jax version)`` —
  ``<cache dir>/<device_kind>-jax<version>-v<SCHEMA>.json`` — so a different
  accelerator, an upgraded jax, or a schema bump each start from a clean
  table rather than serving stale timings;
* the cache dir is ``$REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro-autotune``;
* entries map :func:`make_key` strings to ``[th, tc]`` pairs.

``get_tiles`` is wired into the dispatcher (``repro.core.decompose.conv2d``)
so every call site benefits transparently: a cache hit returns the tuned
tiles, a miss returns the defaults *without* sweeping unless autotuning is
switched on (``REPRO_AUTOTUNE=1``) — keeping cold-start latency and CI
determinism intact.  Sweeps can also be run ahead of time via :func:`tune`
(``benchmarks/kernel_bench.py`` does, and reports the tuned-vs-default
delta).
"""

from __future__ import annotations

import json
import os
import pathlib

import jax
import jax.numpy as jnp

DEFAULT_TILES = (8, 128)
#: schema 2: the fused-epilogue configuration joined the cache key — v1
#: tables conflated epilogue variants of the same geometry (wrong winners
#: for whichever configuration tuned second), so they must invalidate.
_SCHEMA = 2
#: how many analytically ranked candidates the default tune() times
#: (plus DEFAULT_TILES) — the policy replaces the exhaustive sweep
POLICY_TOP = 3
#: candidate grids — th rides the sublane axis, tc the 128-wide lane axis
TH_CANDIDATES = (4, 8, 16, 32)
TC_CANDIDATES = (64, 128, 256)
KINDS = ("dense", "dilated", "tconv")

_MEM: dict[str, tuple[int, int]] = {}
_DISK: dict[str, tuple[int, int]] | None = None


def autotune_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "").lower() in ("1", "true", "on")


def table_disabled() -> bool:
    """``$REPRO_AUTOTUNE=off``: every geometry gets ``DEFAULT_TILES`` and
    the on-disk table is never read — a run built only from the checkout
    (``chip_smoke.py``) depends on no machine-local state."""
    return os.environ.get("REPRO_AUTOTUNE", "").lower() in ("0", "false",
                                                            "off")


def _device_kind() -> str:
    try:
        kind = jax.devices()[0].device_kind
    except Exception:  # no backend at all — still allow cache-key formation
        kind = "unknown"
    return "".join(c if c.isalnum() else "_" for c in kind)


def cache_path() -> pathlib.Path:
    base = os.environ.get("REPRO_AUTOTUNE_CACHE")
    root = pathlib.Path(base) if base else (
        pathlib.Path.home() / ".cache" / "repro-autotune")
    return root / f"{_device_kind()}-jax{jax.__version__}-v{_SCHEMA}.json"


def make_key(kind: str, x_shape: tuple, w_shape: tuple, *, stride: int = 1,
             dilation: int = 1, dtype=jnp.float32, padding=None,
             output_padding: int | None = None, epilogue=None) -> str:
    """Canonical cache key for one kernel geometry.

    ``padding``/``output_padding`` are part of the geometry — they change
    the output extent and therefore the tiling.  ``None`` is *canonicalised*
    to the engine default (dense/dilated ``SAME``, tconv ``(k-1)//2`` and
    ``output_padding=1``) so the dispatcher's resolved values and an
    ahead-of-time ``tune()`` call with defaults produce the same key.

    ``epilogue`` is part of the key too: a fused residual streams a second
    output-shaped block through VMEM, so a winner timed without it is not
    a winner with it (the schema-2 bugfix — v1 keys conflated them).
    """
    from repro.kernels.epilogue import fingerprint

    if kind not in KINDS:
        raise ValueError(f"unknown engine kind {kind!r}")
    n, h, w, cin = x_shape
    kh, kw = w_shape[0], w_shape[1]
    cout = w_shape[3]
    if kind == "tconv":
        pad = (kh - 1) // 2 if padding is None else padding
        op = 1 if output_padding is None else output_padding
    else:
        pad = "SAME" if padding is None else padding
        op = 0      # forward convs have no output padding
    return (f"{kind}/n{n}x{h}x{w}x{cin}/k{kh}x{kw}x{cout}"
            f"/s{stride}/d{dilation}/p{pad}/op{op}/{jnp.dtype(dtype).name}"
            f"/ep{fingerprint(epilogue)}")


def candidates(h_out: int, cout: int) -> list[tuple[int, int]]:
    """The (th, tc) sweep grid, clipped to the output geometry.

    Oversized candidates are dropped rather than clamped — the kernels clamp
    internally, so a clamped duplicate would just re-time the same tiling.
    """
    ths = [t for t in TH_CANDIDATES if t <= max(h_out, TH_CANDIDATES[0])]
    tcs = [t for t in TC_CANDIDATES if t <= max(cout, TC_CANDIDATES[0])]
    return [(th, tc) for th in ths for tc in tcs]


def _load_disk() -> dict[str, tuple[int, int]]:
    global _DISK
    if _DISK is None:
        _DISK = {}
        path = cache_path()
        if path.exists():
            try:
                raw = json.loads(path.read_text())
                _DISK = {k: tuple(v) for k, v in raw.get("entries", {}).items()}
            except (json.JSONDecodeError, OSError):
                _DISK = {}      # corrupt cache — retune rather than crash
    return _DISK


def _persist(key: str, tiles: tuple[int, int]) -> None:
    disk = _load_disk()
    disk[key] = tiles
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = {"device_kind": _device_kind(), "jax_version": jax.__version__,
               "schema": _SCHEMA,
               "entries": {k: list(v) for k, v in sorted(disk.items())}}
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(payload, indent=1))
    tmp.replace(path)           # atomic: concurrent readers see old or new


def clear_memory_cache() -> None:
    """Drop the in-process caches (tests; after swapping the cache dir)."""
    global _DISK
    _MEM.clear()
    _DISK = None


def _out_hw(kind: str, x_shape: tuple, w_shape: tuple, stride: int,
            padding, output_padding) -> tuple[int, int]:
    """Output (H, W) of one geometry — sizes the synthetic residual operand."""
    n, h, w_in, _ = x_shape
    kh, kw = w_shape[0], w_shape[1]
    if kind == "tconv":
        from repro.core import transposed as tr

        p_lo = (kh - 1) // 2 if padding is None else padding
        op = 1 if output_padding is None else output_padding
        return (tr.out_size(h, stride, kh, p_lo, p_lo + op),
                tr.out_size(w_in, stride, kw, p_lo, p_lo + op))
    if kind == "dense" and isinstance(padding, int):
        return ((h + 2 * padding - kh) // stride + 1,
                (w_in + 2 * padding - kw) // stride + 1)
    return -(-h // stride), -(-w_in // stride)      # SAME


def _ep_operands(spec, kind: str, x_shape: tuple, w_shape: tuple,
                 stride: int, padding, output_padding, dtype) -> dict:
    """Synthetic epilogue operands so tuned calls time the real footprint."""
    if spec is None or spec.empty:
        return {}
    cout = w_shape[3]
    out = {}
    if spec.bn:
        out["scale"] = jnp.ones((cout,), jnp.float32)
        out["shift"] = jnp.zeros((cout,), jnp.float32)
    if spec.prelu:
        out["alpha"] = jnp.full((cout,), 0.25, jnp.float32)
    if spec.residual != "none":
        oh, ow = _out_hw(kind, x_shape, w_shape, stride, padding,
                         output_padding)
        out["residual"] = jnp.zeros((x_shape[0], oh, ow, cout), dtype)
    return out


def _build_call(kind: str, x: jax.Array, w: jax.Array, th: int, tc: int,
                stride: int, dilation: int, padding, output_padding,
                epilogue=None):
    ep_kw = _ep_operands(epilogue, kind, x.shape, w.shape, stride, padding,
                         output_padding, x.dtype)
    if kind == "dense":
        from repro.kernels.conv2d import conv2d
        return lambda: conv2d(x, w, stride=stride,
                              padding="SAME" if padding is None else padding,
                              th=th, tc=tc, epilogue=epilogue, **ep_kw)
    if kind == "dilated":
        from repro.kernels.dilated_conv import dilated_conv2d
        return lambda: dilated_conv2d(x, w, dilation, stride=stride,
                                      th=th, tc=tc, epilogue=epilogue,
                                      **ep_kw)
    from repro.kernels.transposed_conv import transposed_conv2d
    return lambda: transposed_conv2d(
        x, w, stride=stride, padding=padding,
        output_padding=1 if output_padding is None else output_padding,
        th=th, tc=tc, epilogue=epilogue, **ep_kw)


def _time_candidate(call, iters: int) -> float:
    """Best-of-``iters`` wall time (s) after a compile/warmup call.

    Delegates to the shared blocking timer (``repro.kernels.util.time_call``)
    so the timed region always includes ``jax.block_until_ready`` — async
    dispatch must not record launch latency as kernel runtime.
    """
    from repro.kernels.util import time_call

    return time_call(call, iters=iters)


def _prune_default() -> int | None:
    """Sweep-prune width from ``$REPRO_AUTOTUNE_PRUNE`` (unset/0 = off)."""
    raw = os.environ.get("REPRO_AUTOTUNE_PRUNE", "")
    try:
        k = int(raw)
    except ValueError:
        return None
    return k if k > 0 else None


def tune(kind: str, x_shape: tuple, w_shape: tuple, *, stride: int = 1,
         dilation: int = 1, dtype=jnp.float32, padding=None,
         output_padding: int | None = None, iters: int = 3,
         cands: list[tuple[int, int]] | None = None,
         prune: int | None = None, calibration=None,
         epilogue=None, policy_top: int | None = None) -> tuple[int, int]:
    """Time the promising candidates for one geometry; persist the winner.

    Deterministic given timings: candidates are visited in a fixed order and
    ties keep the earlier candidate.  Returns the winning ``(th, tc)``.

    By default the analytic policy (:mod:`repro.kernels.tiling_policy`,
    DESIGN.md §12) ranks the grid by VMEM footprint (dtype- and
    epilogue-aware) and MXU occupancy, and only the top ``policy_top``
    (default :data:`POLICY_TOP`) plus ``DEFAULT_TILES`` are timed.
    ``$REPRO_AUTOTUNE_SWEEP=1`` forces the exhaustive sweep of the whole
    grid instead.

    ``prune`` (or ``$REPRO_AUTOTUNE_PRUNE``) is the legacy calibrated
    pruner: the grid is ranked by ``repro.core.calibrate.tile_scores`` and
    only the top ``prune`` run.  In both modes the current default tiling
    is always kept in the timed set, so candidate selection can never
    regress below the no-autotune baseline.
    """
    key = make_key(kind, x_shape, w_shape, stride=stride, dilation=dilation,
                   dtype=dtype, padding=padding,
                   output_padding=output_padding, epilogue=epilogue)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, x_shape, jnp.float32).astype(dtype)
    w = jax.random.normal(k2, w_shape, jnp.float32).astype(dtype)
    if kind == "tconv":
        # th tiles the per-parity *block-row* axis: ~ceil(OH/s) ~ H rows
        h_out = x_shape[1]
    else:
        h_out = -(-x_shape[1] // stride)
    if cands is None:
        cands = candidates(h_out, w_shape[3])
    prune = _prune_default() if prune is None else prune
    if prune is not None and prune < len(cands):
        from repro.core.calibrate import CaptureCase, modeled_cycles, tile_scores

        case = CaptureCase(kind, tuple(x_shape), tuple(w_shape),
                           stride=stride, dilation=dilation)
        ranked = tile_scores(h_out, w_shape[3], cands, kind=kind,
                             base_cycles=modeled_cycles(case),
                             calibration=calibration,
                             dtype=jnp.dtype(dtype).name)
        keep = {c for _, c in ranked[:prune]}
        keep.add(DEFAULT_TILES)     # never time fewer than the baseline
        cands = [c for c in cands if c in keep]
    else:
        from repro.core.calibrate import CaptureCase, modeled_cycles
        from repro.kernels import tiling_policy

        try:
            base_cycles = modeled_cycles(CaptureCase(
                kind, tuple(x_shape), tuple(w_shape), stride=stride,
                dilation=dilation))
        except Exception:       # unmodeled geometry — rank without cell term
            base_cycles = None
        cands = tiling_policy.top_candidates(
            kind, x_shape, w_shape, cands,
            top=POLICY_TOP if policy_top is None else policy_top,
            default_tiles=DEFAULT_TILES, stride=stride, dilation=dilation,
            padding=padding, output_padding=output_padding, dtype=dtype,
            epilogue=epilogue, base_cycles=base_cycles,
            calibration=calibration)
    best, best_t = DEFAULT_TILES, float("inf")
    for th, tc in cands:
        t = _time_candidate(_build_call(kind, x, w, th, tc, stride, dilation,
                                        padding, output_padding,
                                        epilogue=epilogue),
                            iters)
        if t < best_t:
            best, best_t = (th, tc), t
    _MEM[key] = best
    _persist(key, best)
    return best


def get_tiles(kind: str, x_shape: tuple, w_shape: tuple, *, stride: int = 1,
              dilation: int = 1, dtype=jnp.float32, padding=None,
              output_padding: int | None = None,
              epilogue=None) -> tuple[int, int]:
    """Resolve the tile shape for one geometry: mem -> disk -> tune/defaults.

    ``REPRO_AUTOTUNE=off`` skips the lookup and returns the defaults.
    Only tunes on a full miss when ``REPRO_AUTOTUNE=1`` — the default is a
    pure lookup so cold paths (tests, first-run UX) stay deterministic and
    cheap; the table is populated by CI / ``kernel_bench`` runs and shipped
    via the CI cache.
    """
    if table_disabled():
        return DEFAULT_TILES
    key = make_key(kind, x_shape, w_shape, stride=stride, dilation=dilation,
                   dtype=dtype, padding=padding,
                   output_padding=output_padding, epilogue=epilogue)
    hit = _MEM.get(key)
    if hit is not None:
        return hit
    hit = _load_disk().get(key)
    if hit is not None:
        _MEM[key] = hit
        return hit
    if autotune_enabled():
        return tune(kind, x_shape, w_shape, stride=stride, dilation=dilation,
                    dtype=dtype, padding=padding,
                    output_padding=output_padding, epilogue=epilogue)
    _MEM[key] = DEFAULT_TILES   # negative-cache the lookup, not the timing
    return DEFAULT_TILES


__all__ = ["DEFAULT_TILES", "POLICY_TOP", "get_tiles", "tune", "make_key",
           "candidates", "cache_path", "clear_memory_cache",
           "autotune_enabled", "table_disabled"]
