"""Analytic ``(th, tc)`` tiling policy for the Pallas engines (DESIGN.md §12).

The autotuner used to *time* the whole candidate grid per geometry.  This
module scores every candidate from first principles instead, so only the
top few (plus ``DEFAULT_TILES``) are ever run:

* **VMEM footprint** — each candidate's per-grid-step working set, assembled
  from the same block shapes the kernels declare (`conv2d.py`,
  `transposed_conv.py`), doubled for the pipeline's double-buffered
  input/weight/output streams, plus the in-kernel halo window and the fp32
  accumulator.  Every buffer is counted as VMEM holds it: its last two dims
  padded to the (sublane, 128-lane) register tile — (8, 128) fp32, (16, 128)
  bf16 — so a 3-channel stem block costs 128 lanes, as the compiler counts
  it.  The footprint is dtype-aware (bf16 halves the streamed bytes) and
  epilogue-aware (a fused residual streams a second output-shaped block;
  channel vectors ride along as fp32 rows).  Candidates that overflow the
  budget score ``inf`` — they would spill or fail to fit, so they are never
  worth timing.  The kernels themselves compile with a raised scoped-VMEM
  limit (:data:`VMEM_LIMIT_BYTES`), which also covers what the count
  leaves out.
* **MXU occupancy** — each grid step issues GEMMs of shape
  ``(th * w_out, cin) x (cin, tc)``.  Lanes pad to 128, sublanes pack by
  dtype (8 fp32 / 16 bf16 rows per tile), so narrow ``tc`` or a flattened
  row count that straddles a packing boundary wastes issue slots.
* **tile quantization + grid overhead** — the classic terms shared with
  ``calibrate.tile_scores``: padded-output work multiplier and a per-cell
  dispatch weight (calibrated from the fitted ``b_us / (a * cycles)`` when
  a :class:`~repro.core.calibrate.Calibration` is supplied).

The combined score is ``quantization_waste / occupancy + cell_w * cells``
(lower is better), with ``inf`` for budget violations.  ``top_candidates``
returns the top-``k`` plus ``DEFAULT_TILES``; when the geometry cannot be
modeled (unknown kind) or ``$REPRO_AUTOTUNE_SWEEP`` is set, it falls back
to the full exhaustive sweep so the policy can never hide a winner the old
path would have found.
"""

from __future__ import annotations

import math
import os

import jax.numpy as jnp

#: the default scoped VMEM of a v5e kernel is 16 MiB; leave headroom for
#: compiler scratch and semaphores so a "fits" verdict survives lowering.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

#: scoped VMEM limit every engine kernel compiles with: half of a v5e
#: core's 128 MiB, the rest left to the compiler's own buffers.  Above the
#: 16 MiB default because the blocks are not all a kernel holds there: the
#: ENet-512 stem's blocks alone take 24.2 MiB (3 channels on 128 lanes),
#: and the per-tap slices and, at HIGHEST matmul precision, the operand
#: splits of each fp32 contraction come on top — 19.8 MiB for the 3x3
#: 4-channel backward of ENet-512's train step, whose blocks count 8.5 MiB.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: MXU lane width — the last-dim tiling quantum on TPU.
LANES = 128

_KINDS = ("dense", "dilated", "tconv")


def itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def sublanes(dtype) -> int:
    """Rows per (sublane, lane) register tile: 8 fp32, 16 bf16, 32 int8."""
    return max(8 * (4 // max(itemsize(dtype), 1)), 8)


def padded_bytes(shape, dtype) -> int:
    """Bytes of one VMEM buffer: the last two dims padded to the register
    tile (``sublanes(dtype)`` x 128 lanes), leading dims as they are."""
    *lead, rows, cols = (1,) * max(2 - len(shape), 0) + tuple(shape)
    rows = math.ceil(rows / sublanes(dtype)) * sublanes(dtype)
    cols = math.ceil(cols / LANES) * LANES
    return math.prod(lead) * rows * cols * itemsize(dtype)


def _ep_extra(spec, out_bytes: int, tc: int) -> int:
    """Streamed bytes a fused epilogue adds per grid step.

    Channel vectors (scale/shift/alpha) travel as fp32 ``(1, tc)`` rows; a
    residual streams a full output-shaped block in the output dtype.
    """
    if spec is None or spec.empty:
        return 0
    return sum(out_bytes if name == "residual"
               else padded_bytes((1, tc), jnp.float32) for name in spec.slots)


def _dense_geometry(x_shape, w_shape, stride, padding):
    n, h, w_in, cin = x_shape
    kh, kw = w_shape[0], w_shape[1]
    cout = w_shape[3]
    if padding is None or padding == "SAME":
        ph = ((kh - 1) // 2, kh // 2)
        pw = ((kw - 1) // 2, kw // 2)
    elif padding == "VALID":
        ph = pw = (0, 0)
    elif isinstance(padding, tuple):    # the kernel's resolved (ph, pw)
        ph, pw = padding
    else:
        ph = pw = (padding, padding)
    h_out = (h + ph[0] + ph[1] - kh) // stride + 1
    w_out = (w_in + pw[0] + pw[1] - kw) // stride + 1
    return n, h_out, w_out, cin, cout, kh, kw


def _phase_batched(x_shape, dilation):
    """Dilated convs run the dense kernel on the phase-batched layout."""
    n, h, w_in, cin = x_shape
    d = dilation
    return (n * d * d, -(-h // d), -(-w_in // d), cin)


def footprint_bytes(kind: str, x_shape, w_shape, th: int, tc: int, *,
                    stride: int = 1, dilation: int = 1, padding=None,
                    output_padding: int | None = None, dtype=jnp.float32,
                    epilogue=None) -> int:
    """Per-grid-step VMEM working set of one ``(th, tc)`` candidate (bytes).

    Mirrors the kernels' BlockSpecs: double-buffered input halo pair +
    weight tile + output tile (x2 for the pipeline), epilogue operands, the
    assembled halo window and the fp32 accumulator, each padded to the
    register tile (:func:`padded_bytes`).  Dilated geometries are scored as
    the dense kernel on the phase-batched layout they actually run; a
    strided dense conv as the larger of its two forms (``conv2d.py``).
    """
    f32 = jnp.float32
    if kind == "dilated":
        x_shape = _phase_batched(x_shape, dilation)
        stride, padding = 1, None   # classes fold the stride out
    if kind in ("dense", "dilated"):
        _, h_out, w_out, cin, cout, kh, kw = _dense_geometry(
            x_shape, w_shape, stride, padding)
        s = stride
        halo = (kh - 1) // s                # phase rows (conv2d.py)
        th_e = max(min(th, h_out), halo)
        tc_e = min(tc, cout)
        cols = w_out + (kw - 1) // s
        # the phase-plane form's blocks and window.  The table keys on
        # geometry, not on the form, and a strided conv may run the
        # strided-load form instead: its blocks of s*th padded rows of
        # s*cols columns take no more VMEM than s*s planes of th rows of
        # cols columns (s times cols rounded up to the sublane tile is at
        # least s*cols rounded up), and it assembles no window.  So this
        # set is the larger of the two forms'
        x_block = padded_bytes((s * s, th_e, cols, cin), dtype)
        window = padded_bytes((s * s, th_e + halo, cols, cin), dtype) \
            if halo else 0
        w_block = padded_bytes((kh, kw, cin, tc_e), dtype)
        out_block = padded_bytes((th_e, w_out, tc_e), dtype)
        acc = padded_bytes((th_e * w_out, tc_e), f32)
    else:       # tconv: parity-plane kernel (transposed_conv.py)
        from repro.core import transposed as tr
        from repro.kernels.transposed_conv import parity_schedule

        n, h, w_in, cin = x_shape
        k = w_shape[0]
        cout = w_shape[3]
        s = stride
        p_lo = (k - 1) // 2 if padding is None else padding
        op = 1 if output_padding is None else output_padding
        oh = tr.out_size(h, s, k, p_lo, p_lo + op)
        ow = tr.out_size(w_in, s, k, p_lo, p_lo + op)
        hb, wb = math.ceil(oh / s), math.ceil(ow / s)
        offs = [o for taps in parity_schedule(k, s, p_lo) for _, o in taps]
        shift = max(0, -min(offs, default=0))
        halo = max(offs, default=0) + shift
        th_e = max(min(th, hb), halo)
        tc_e = min(tc, cout)
        cols = max(wb + halo, w_in + shift)
        x_block = padded_bytes((th_e, cols, cin), dtype)
        window = padded_bytes((th_e + halo, cols, cin), dtype) if halo else 0
        w_block = padded_bytes((k, k, cin, tc_e), dtype)
        out_block = padded_bytes((s * s, th_e, wb, tc_e), dtype)
        acc = s * s * padded_bytes((th_e * wb, tc_e), f32)
    streamed = 2 * x_block + w_block + out_block
    streamed += _ep_extra(epilogue, out_block, tc_e)
    # x2: the pipeline double-buffers streams
    return 2 * streamed + window + acc


def mxu_occupancy(kind: str, x_shape, w_shape, th: int, tc: int, *,
                  stride: int = 1, dilation: int = 1, padding=None,
                  output_padding: int | None = None,
                  dtype=jnp.float32) -> float:
    """Fraction of MXU issue slots doing real work for one candidate's GEMM.

    The kernels flatten each tile to ``(th * w_out, cin) x (cin, tc)``;
    lanes quantize to 128 and sublane rows pack by dtype, so the occupancy
    is the product of the two padding fractions.
    """
    if kind == "dilated":
        x_shape = _phase_batched(x_shape, dilation)
        stride, padding = 1, None
    if kind in ("dense", "dilated"):
        _, h_out, w_out, _, cout, kh, _ = _dense_geometry(
            x_shape, w_shape, stride, padding)
        th_e = max(min(th, h_out), (kh - 1) // stride)
        rows = th_e * w_out
    else:
        from repro.core import transposed as tr

        n, h, w_in, _ = x_shape
        k = w_shape[0]
        cout = w_shape[3]
        p_lo = (k - 1) // 2 if padding is None else padding
        op = 1 if output_padding is None else output_padding
        oh = tr.out_size(h, stride, k, p_lo, p_lo + op)
        ow = tr.out_size(w_in, stride, k, p_lo, p_lo + op)
        hb, wb = math.ceil(oh / stride), math.ceil(ow / stride)
        rows = max(min(th, hb), 1) * wb
    tc_e = min(tc, cout)
    sub = sublanes(dtype)
    lane_occ = tc_e / (math.ceil(tc_e / LANES) * LANES)
    row_occ = rows / (math.ceil(rows / sub) * sub)
    return lane_occ * row_occ


def _cell_weight(kind: str, backend: str, base_cycles, calibration,
                 dtype) -> float:
    """Per-grid-cell overhead weight; calibrated when a fit is available."""
    cell_w = 1e-3
    if calibration is not None and base_cycles:
        from repro.core.calibrate import key_of

        co = calibration.coeffs.get(
            key_of(kind, backend, dtype=jnp.dtype(dtype).name))
        if co is None:      # fall back to the fp32 fit of the same engine
            co = calibration.coeffs.get(key_of(kind, backend))
        if co is not None and co.a_us_per_cycle > 0:
            compute_us = co.a_us_per_cycle * base_cycles
            if compute_us > 0:
                cell_w = co.b_us / compute_us
    return cell_w


def rank(kind: str, x_shape, w_shape, cands, *, stride: int = 1,
         dilation: int = 1, padding=None, output_padding: int | None = None,
         dtype=jnp.float32, epilogue=None, backend: str = "xla",
         base_cycles: float | None = None, calibration=None,
         vmem_budget: int = VMEM_BUDGET_BYTES
         ) -> list[tuple[float, tuple[int, int]]]:
    """Score every candidate analytically; ``(score, (th, tc))`` ascending.

    ``score = quantization_waste / mxu_occupancy + cell_w * n_cells``, with
    ``inf`` when the candidate's VMEM footprint exceeds ``vmem_budget``.
    Ties keep candidate order (the sweep's determinism rule).
    """
    if kind not in _KINDS:
        raise ValueError(f"unknown engine kind {kind!r}")
    if kind == "tconv":
        h_out, cout = x_shape[1], w_shape[3]    # th tiles the block-row axis
    else:
        h_out, cout = -(-x_shape[1] // stride), w_shape[3]
    cell_w = _cell_weight(kind, backend, base_cycles, calibration, dtype)
    geom = dict(stride=stride, dilation=dilation, padding=padding,
                output_padding=output_padding, dtype=dtype)
    scored = []
    for i, (th, tc) in enumerate(cands):
        vmem = footprint_bytes(kind, x_shape, w_shape, th, tc,
                               epilogue=epilogue, **geom)
        if vmem > vmem_budget:
            scored.append((float("inf"), i, (th, tc)))
            continue
        occ = mxu_occupancy(kind, x_shape, w_shape, th, tc, **geom)
        waste = (math.ceil(h_out / th) * th / h_out) * \
                (math.ceil(cout / tc) * tc / cout)
        cells = math.ceil(h_out / th) * math.ceil(cout / tc)
        scored.append((waste / max(occ, 1e-9) + cell_w * cells, i, (th, tc)))
    scored.sort(key=lambda t: (t[0], t[1]))
    return [(s, c) for s, _, c in scored]


def sweep_forced() -> bool:
    """``$REPRO_AUTOTUNE_SWEEP=1`` disables the policy (exhaustive timing)."""
    return os.environ.get("REPRO_AUTOTUNE_SWEEP", "").lower() in (
        "1", "true", "on")


def top_candidates(kind: str, x_shape, w_shape, cands, *, top: int = 3,
                   default_tiles: tuple[int, int] | None = None,
                   **rank_kw) -> list[tuple[int, int]]:
    """The candidates worth timing: analytic top-``top`` + ``default_tiles``.

    Returns the input list unchanged (exhaustive sweep) when the sweep is
    forced via the environment or the geometry cannot be scored — the
    policy degrades to the old behaviour, never to a smaller search space
    than the baseline tiling.
    """
    if sweep_forced():
        return list(cands)
    try:
        ranked = rank(kind, x_shape, w_shape, cands, **rank_kw)
    except (ValueError, ZeroDivisionError):
        return list(cands)      # unmodelable geometry: fall back to the sweep
    keep = [c for s, c in ranked[:top] if math.isfinite(s)]
    if not keep:                # every candidate over budget — time them all
        return list(cands)      # rather than guess blind
    if default_tiles is not None and default_tiles in cands \
            and default_tiles not in keep:
        keep.append(default_tiles)
    return [c for c in cands if c in keep]   # candidate order == sweep order


__all__ = ["VMEM_BUDGET_BYTES", "VMEM_LIMIT_BYTES", "LANES", "itemsize",
           "sublanes", "padded_bytes", "footprint_bytes", "mxu_occupancy", "rank", "top_candidates", "sweep_forced"]
