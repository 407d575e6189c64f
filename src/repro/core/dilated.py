"""Input decomposition for dilated convolutions (paper §II-B).

A dilated convolution with dilation rate ``d = D + 1`` (``D`` zeros inserted
between adjacent weight taps, effective kernel ``(d*(k-1)+1)``) touches, for the
output pixel at ``(y, x)``, only input pixels whose coordinates are congruent to
``(y, x) mod d``.  The input therefore splits exactly into ``d**2`` *phase
blocks* — block ``(i, j)`` holds input pixels at ``x[i::d, j::d]`` — and the
dilated convolution is equivalent to ``d**2`` independent *dense* SAME
convolutions of each phase block with the compact ``k x k`` kernel, stitched
back by interleaving.

This file provides three executable forms, all NHWC / HWIO:

* :func:`dilated_conv2d_reference` — XLA oracle (``rhs_dilation``).
* :func:`dilated_conv2d_naive` — what a dense accelerator does naively: the
  kernel is explicitly zero-inserted to its enlarged ``(d*(k-1)+1)`` footprint
  and convolved densely.  Numerically identical to the oracle but performs the
  full zero-laden MAC count; used as the cycle-model "ideal dense" workload.
* :func:`dilated_conv2d_decomposed` — the paper's method: phase split ->
  dense conv -> stitch.  Two execution strategies:

  - ``ragged``: faithful to the paper — each of the ``d**2`` ragged blocks is
    convolved separately (matches Fig. 4 block shapes).
  - ``batched``: TPU-native beyond-paper variant — the input is padded up to a
    multiple of ``d``, the phases are stacked on the batch axis and executed as
    ONE dense convolution (full MXU occupancy even for small phase extents).

All three forms accept an output ``stride``: the decomposition generalizes to
strided dilated convolutions via the output-class schedule
(:func:`stride_class_schedule`, DESIGN.md §2c) — ``(d/gcd(s,d))**2`` classes,
each a strided VALID dense conv of one phase block, still issuing exactly the
nonzero MACs.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs

_DIMS = ("NHWC", "HWIO", "NHWC")


def same_pad(k: int) -> int:
    """Padding for SAME output with an odd kernel of size ``k``."""
    if k % 2 != 1:
        raise ValueError(f"SAME padding defined for odd kernels only, got k={k}")
    return (k - 1) // 2


def effective_kernel_size(k: int, dilation: int) -> int:
    """Zero-inserted footprint: ``(2*D + k)`` for step ``d = D+1`` == d*(k-1)+1."""
    return dilation * (k - 1) + 1


def strided_out_size(h: int, k: int, dilation: int, stride: int) -> int:
    """Output extent of a SAME-padded strided dilated conv: ``ceil(h/s)``."""
    ke = effective_kernel_size(k, dilation)
    return (h + 2 * same_pad(ke) - ke) // stride + 1


def dilated_conv2d_reference(x: jax.Array, w: jax.Array, dilation: int,
                             stride: int = 1) -> jax.Array:
    """XLA oracle: SAME dilated convolution via ``rhs_dilation``.

    Args:
      x: (N, H, W, Cin).
      w: (k, k, Cin, Cout) compact (non-dilated) kernel.
      dilation: step ``d = D + 1`` (``d = 1`` is a plain dense convolution).
      stride: output stride ``s`` (output extent ``ceil(H/s)``).
    Returns:
      (N, ceil(H/s), ceil(W/s), Cout).
    """
    k = w.shape[0]
    pad = same_pad(effective_kernel_size(k, dilation))
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=[(pad, pad), (pad, pad)],
        rhs_dilation=(dilation, dilation), dimension_numbers=_DIMS,
    )


def zero_insert_weight(w: jax.Array, dilation: int) -> jax.Array:
    """Explicitly materialise the enlarged zero-inserted kernel (Fig. 2)."""
    k, _, cin, cout = w.shape
    ke = effective_kernel_size(k, dilation)
    we = jnp.zeros((ke, ke, cin, cout), w.dtype)
    return we.at[::dilation, ::dilation].set(w)


def dilated_conv2d_naive(x: jax.Array, w: jax.Array, dilation: int,
                         stride: int = 1) -> jax.Array:
    """Dense execution of the zero-inserted kernel — the paper's baseline."""
    we = zero_insert_weight(w, dilation)
    pad = same_pad(we.shape[0])
    return lax.conv_general_dilated(
        x, we, window_strides=(stride, stride), padding=[(pad, pad), (pad, pad)],
        dimension_numbers=_DIMS,
    )


@jax.named_scope(obs.LAYOUT_PHASE_SPLIT)
def phase_split(x: jax.Array, d: int) -> list[list[jax.Array]]:
    """Split NHWC input into ``d x d`` ragged phase blocks (paper Fig. 4).

    Block ``(i, j)`` has shape ``(N, ceil((H-i)/d), ceil((W-j)/d), C)``.
    """
    return [[x[:, i::d, j::d, :] for j in range(d)] for i in range(d)]


@jax.named_scope(obs.LAYOUT_PHASE_STITCH)
def phase_stitch(blocks: list[list[jax.Array]], out_shape: tuple[int, ...]) -> jax.Array:
    """Interleave ``d x d`` phase outputs back into a dense NHWC tensor."""
    d = len(blocks)
    out = jnp.zeros(out_shape, blocks[0][0].dtype)
    for i in range(d):
        for j in range(d):
            out = out.at[:, i::d, j::d, :].set(blocks[i][j])
    return out


@jax.named_scope(obs.LAYOUT_PHASE_SPLIT)
def _phase_to_batch(x: jax.Array, d: int) -> tuple[jax.Array, int, int]:
    """Pad H, W up to multiples of ``d`` and stack phases on the batch axis.

    Returns (stacked ``(d*d*N, H//d, W//d, C)``, padded H, padded W).  Padding
    with zeros is exact: the oracle's SAME conv also pads with zeros, and the
    excess rows are dropped at stitch time.
    """
    n, h, w_, c = x.shape
    hp, wp = math.ceil(h / d) * d, math.ceil(w_ / d) * d
    x = jnp.pad(x, ((0, 0), (0, hp - h), (0, wp - w_), (0, 0)))
    # (N, H/d, d, W/d, d, C) -> (d, d, N, H/d, W/d, C) -> merge phases into batch
    x = x.reshape(n, hp // d, d, wp // d, d, c).transpose(2, 4, 0, 1, 3, 5)
    return x.reshape(d * d * n, hp // d, wp // d, c), hp, wp


@jax.named_scope(obs.LAYOUT_PHASE_STITCH)
def _batch_to_phase(y: jax.Array, d: int, n: int, h: int, w_: int) -> jax.Array:
    """Inverse of :func:`_phase_to_batch` (crops the pad-up rows/cols)."""
    _, hb, wb, c = y.shape
    y = y.reshape(d, d, n, hb, wb, c).transpose(2, 3, 0, 4, 1, 5)
    y = y.reshape(n, hb * d, wb * d, c)
    return y[:, :h, :w_, :]


def stride_class_schedule(d: int, s: int, p: int, out_len: int
                          ) -> tuple[int, int, list[tuple[int, int, int]]]:
    """Output-class schedule for one spatial dim of a *strided* dilated conv.

    Output pixel ``y`` reads input ``s*y - p + d*t`` for taps ``t`` — all
    congruent to ``r(y) = (s*y - p) mod d``, so each output lives in exactly
    one input phase block.  ``r(y)`` is periodic in ``y`` with period
    ``q = d // gcd(s, d)``: outputs ``y = j + q*u`` (class ``j``) all read
    phase block ``r_j = (s*j - p) mod d`` at block positions
    ``m0_j + s_blk*u + t`` with ``s_blk = s // gcd(s, d)`` and
    ``m0_j = (s*j - p - r_j) // d``.

    Returns ``(q, s_blk, [(r_j, m0_j, n_out_j)])`` — each class is a dense
    VALID correlation of its phase block with the compact kernel at block
    stride ``s_blk``; MACs issued == nonzero MACs.  ``s = 1`` degenerates to
    the paper's schedule (``q = d``, ``s_blk = 1``, ``r_j = j`` up to the
    padding shift).
    """
    g = math.gcd(s, d)
    q, s_blk = d // g, s // g
    sched = []
    for j in range(q):
        r = (s * j - p) % d
        m0 = (s * j - p - r) // d
        n_out = len(range(j, out_len, q))
        sched.append((r, m0, n_out))
    return q, s_blk, sched


def _class_window(x: jax.Array, d: int, row, col,
                  rows_span: int, cols_span: int) -> jax.Array:
    """Extract one (row-class, col-class) phase window, padded to a common span.

    ``row``/``col`` are ``(r, m0, n_out)`` schedule entries.  The returned
    block is aligned so the class's first output reads rows/cols ``[0, k)``
    — a VALID correlation at stride ``s_blk`` then yields the class plane.
    Zero padding is exact: it mirrors the oracle's SAME-conv zero pads.
    """
    (ri, m0i, _), (rj, m0j, _) = row, col
    blk = x[:, ri::d, rj::d, :]
    pt, pl_ = max(0, -m0i), max(0, -m0j)
    st, sl = m0i + pt, m0j + pl_
    pb = max(0, st + rows_span - (blk.shape[1] + pt))
    pr = max(0, sl + cols_span - (blk.shape[2] + pl_))
    blk = jnp.pad(blk, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    return blk[:, st : st + rows_span, sl : sl + cols_span, :]


def _dilated_strided_decomposed(x: jax.Array, w: jax.Array, d: int, s: int,
                                strategy: str, conv_fn=None,
                                phase_sharding=None) -> jax.Array:
    """Strided-dilated decomposition: class split -> strided dense conv -> stitch.

    ``conv_fn(xb, w, sb)`` runs a VALID dense conv at stride ``sb`` (defaults
    to ``lax``; the Pallas pipeline passes its own engine here so both paths
    share one schedule/stitch implementation).
    """
    if conv_fn is None:
        def conv_fn(xb, wt, sb):
            return lax.conv_general_dilated(
                xb, wt, window_strides=(sb, sb), padding="VALID",
                dimension_numbers=_DIMS,
            )

    k = w.shape[0]
    p = same_pad(effective_kernel_size(k, d))
    n, h, w_, _ = x.shape
    cout = w.shape[-1]
    oh = strided_out_size(h, k, d, s)
    ow = strided_out_size(w_, k, d, s)
    q, sb, rsched = stride_class_schedule(d, s, p, oh)
    _, _, csched = stride_class_schedule(d, s, p, ow)
    ny_max = max(e[2] for e in rsched)
    nx_max = max(e[2] for e in csched)
    rows_span = sb * (ny_max - 1) + k
    cols_span = sb * (nx_max - 1) + k
    with jax.named_scope(obs.LAYOUT_PHASE_SPLIT):
        windows = [
            _class_window(x, d, row, col, rows_span, cols_span)
            for row in rsched for col in csched
        ]
        if strategy == "batched":
            xb = jnp.concatenate(windows, axis=0)
    if strategy == "batched":
        # all q*q class windows share one strided dense conv (phase-batched)
        if phase_sharding is not None:
            xb = lax.with_sharding_constraint(xb, phase_sharding)
        yb = conv_fn(xb, w, sb)
        planes = [yb[i * n : (i + 1) * n] for i in range(q * q)]
    else:  # ragged: one conv per class (paper-faithful schedule)
        planes = [conv_fn(win, w, sb) for win in windows]
    with jax.named_scope(obs.LAYOUT_PHASE_STITCH):
        out = jnp.zeros((n, oh, ow, cout), x.dtype)
        i = 0
        for ji, (_, _, nyi) in enumerate(rsched):
            for jj, (_, _, nxj) in enumerate(csched):
                out = out.at[:, ji::q, jj::q, :].set(
                    planes[i][:, :nyi, :nxj, :])
                i += 1
    return out


@partial(jax.jit,
         static_argnames=("dilation", "strategy", "stride", "phase_sharding"))
def dilated_conv2d_decomposed(
    x: jax.Array, w: jax.Array, dilation: int, strategy: str = "batched",
    stride: int = 1, phase_sharding=None,
) -> jax.Array:
    """The paper's method: phase decomposition -> dense conv -> stitch.

    ``strategy='ragged'`` runs the d**2 ragged blocks separately (faithful to
    the paper's schedule); ``strategy='batched'`` phase-batches them into one
    dense convolution (TPU-native, beyond-paper).  Both are exact.
    ``stride > 1`` uses the output-class schedule (:func:`stride_class_schedule`)
    — ``(d/gcd(s,d))**2`` classes, each a strided VALID dense conv.

    ``phase_sharding`` (a hashable ``NamedSharding``, DESIGN.md §13) constrains
    the folded phase-batch axis of the batched strategy — the d**2 phase blocks
    are independent, so GSPMD distributes them like data.  Static, so meshed
    and un-meshed callers never share a trace-cache entry.
    """
    d = dilation
    if strategy not in ("ragged", "batched"):
        raise ValueError(f"unknown strategy {strategy!r}")
    if d == 1:
        return dilated_conv2d_reference(x, w, 1, stride)
    if stride != 1:
        return _dilated_strided_decomposed(x, w, d, stride, strategy,
                                           phase_sharding=phase_sharding)
    k = w.shape[0]
    pad = same_pad(k)
    if strategy == "ragged":
        blocks = phase_split(x, d)
        outs = [
            [
                lax.conv_general_dilated(
                    b, w, window_strides=(1, 1), padding=[(pad, pad), (pad, pad)],
                    dimension_numbers=_DIMS,
                )
                for b in row
            ]
            for row in blocks
        ]
        n, h, w_, _ = x.shape
        return phase_stitch(outs, (n, h, w_, w.shape[-1]))
    if strategy == "batched":
        n, h, w_, _ = x.shape
        xb, _, _ = _phase_to_batch(x, d)
        if phase_sharding is not None:
            xb = lax.with_sharding_constraint(xb, phase_sharding)
        yb = lax.conv_general_dilated(
            xb, w, window_strides=(1, 1), padding=[(pad, pad), (pad, pad)],
            dimension_numbers=_DIMS,
        )
        return _batch_to_phase(yb, d, n, h, w_)
    raise ValueError(f"unknown strategy {strategy!r}")


# ---------------------------------------------------------------------------
# MAC counting (drives the cycle model and the paper-claim benchmarks)
# ---------------------------------------------------------------------------

def macs_dense(h: int, w: int, cin: int, cout: int, k: int, dilation: int = 1,
               stride: int = 1) -> int:
    """MACs of the *naive dense* execution: enlarged kernel incl. zeros."""
    ke = effective_kernel_size(k, dilation)
    oh = strided_out_size(h, k, dilation, stride)
    ow = strided_out_size(w, k, dilation, stride)
    return oh * ow * cin * cout * ke * ke


def macs_nonzero(h: int, w: int, cin: int, cout: int, k: int,
                 stride: int = 1) -> int:
    """Ideal sparse MACs: only the k*k nonzero taps (interior approximation)."""
    oh, ow = -(-h // stride), -(-w // stride)
    return oh * ow * cin * cout * k * k


def macs_decomposed(h: int, w: int, cin: int, cout: int, k: int, dilation: int,
                    stride: int = 1) -> int:
    """MACs actually issued by the decomposition == nonzero MACs (exact)."""
    del dilation  # decomposition issues exactly the nonzero MACs
    return macs_nonzero(h, w, cin, cout, k, stride)
