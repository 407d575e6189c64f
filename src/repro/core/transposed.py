"""Weight decomposition for transposed convolutions (paper §II-C).

A transposed convolution with stride ``s`` zero-inserts ``s - 1`` zeros between
adjacent input elements and then runs a dense ``k x k`` correlation.  For the
output pixel at ``(y, x)`` only kernel taps with
``ky ≡ (p - y) (mod s)`` and ``kx ≡ (p - x) (mod s)`` land on real (non-inserted)
input — so the ``k x k`` weight decomposes exactly into ``s**2`` parity
sub-kernels that correlate *directly with the un-upsampled input*.

For the paper's case (``s=2, k=3, p=1``) the four sub-kernels are the four
corners (2x2), the horizontal endpoints (1x2), the vertical endpoints (2x1) and
the center (1x1) — Fig. 6.

Conventions (NHWC / HWIO, cross-correlation, no kernel flip):

    U = zero_insert(x, s)                  # (H-1)*s + 1 per spatial dim
    O[y, x] = sum_{ky,kx} W[ky,kx] * U_pad[y + ky, x + kx]
    with U_pad = pad(U, (p_lo, p_hi))      # output size (H-1)*s + p_lo + p_hi - k + 2

``p_hi = p_lo + output_padding`` recovers the usual framework semantics
(e.g. ENet's 2x upsampling uses s=2, k=3, p_lo=1, output_padding=1 -> O = 2H).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs

_DIMS = ("NHWC", "HWIO", "NHWC")


def out_size(h: int, s: int, k: int, p_lo: int, p_hi: int) -> int:
    return (h - 1) * s + p_lo + p_hi - k + 2


def zero_insert_input(x: jax.Array, s: int) -> jax.Array:
    """Explicitly materialise the zero-inserted input (Fig. 5, naive path)."""
    if s == 1:
        return x
    n, h, w_, c = x.shape
    u = jnp.zeros((n, (h - 1) * s + 1, (w_ - 1) * s + 1, c), x.dtype)
    return u.at[:, ::s, ::s, :].set(x)


def transposed_conv2d_reference(
    x: jax.Array, w: jax.Array, stride: int, padding: int, output_padding: int = 0
) -> jax.Array:
    """XLA oracle via ``lhs_dilation`` (zero-insertion fused into the conv)."""
    k = w.shape[0]
    p_lo, p_hi = padding, padding + output_padding
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=[(p_lo, p_hi), (p_lo, p_hi)],
        lhs_dilation=(stride, stride), dimension_numbers=_DIMS,
    )


def transposed_conv2d_naive(
    x: jax.Array, w: jax.Array, stride: int, padding: int, output_padding: int = 0
) -> jax.Array:
    """Dense execution over the explicitly zero-inserted input (naive path)."""
    u = zero_insert_input(x, stride)
    p_lo, p_hi = padding, padding + output_padding
    return lax.conv_general_dilated(
        u, w, window_strides=(1, 1), padding=[(p_lo, p_hi), (p_lo, p_hi)],
        dimension_numbers=_DIMS,
    )


def parity_taps(k: int, s: int, p_lo: int, r: int) -> list[int]:
    """Kernel taps (one spatial dim) that hit real input for output parity r."""
    return [t for t in range(k) if (t - p_lo + r) % s == 0]


def decompose_weight(w, s: int, p_lo: int):
    """Split an HWIO kernel into the ``s**2`` parity sub-kernels (Fig. 6).

    Returns ``{(ry, rx): (sub_kernel, row_offsets, col_offsets)}`` where the
    offsets are the *input* indices (relative to the output block index) each
    tap reads: ``offset = (r + t - p_lo) // s`` for tap ``t``.
    Parities whose tap set is empty (possible when ``k < s``) map to ``None``.
    """
    k = w.shape[0]
    out = {}
    for ry in range(s):
        for rx in range(s):
            tr = parity_taps(k, s, p_lo, ry)
            tc = parity_taps(k, s, p_lo, rx)
            if not tr or not tc:
                out[(ry, rx)] = None
                continue
            sub = w[jnp.array(tr)][:, jnp.array(tc)]
            ro = [(ry + t - p_lo) // s for t in tr]
            co = [(rx + t - p_lo) // s for t in tc]
            out[(ry, rx)] = (sub, ro, co)
    return out


@partial(jax.jit, static_argnames=("stride", "padding", "output_padding",
                                   "phase_sharding"))
def transposed_conv2d_decomposed(
    x: jax.Array, w: jax.Array, stride: int, padding: int,
    output_padding: int = 0, phase_sharding=None,
) -> jax.Array:
    """The paper's method: per-parity sub-kernel correlation, no zero-insert.

    Each parity output plane is a small dense VALID correlation of the (padded)
    input with its sub-kernel; the ``s**2`` planes interleave into the output.
    MACs issued == nonzero MACs of the naive execution (exact skip).

    ``phase_sharding`` (hashable ``NamedSharding``, DESIGN.md §13) constrains
    each parity plane's correlation input on the batch axis — the s**2 parity
    sub-problems are independent and batch-parallel.  Static, so meshed and
    un-meshed callers never share a trace-cache entry.
    """
    s, k = stride, w.shape[0]
    if s == 1:
        return transposed_conv2d_reference(x, w, 1, padding, output_padding)
    n, h, w_in, _ = x.shape
    cout = w.shape[-1]
    p_lo = padding
    oh = out_size(h, s, k, p_lo, p_lo + output_padding)
    ow = out_size(w_in, s, k, p_lo, p_lo + output_padding)
    out = jnp.zeros((n, oh, ow, cout), x.dtype)

    subs = decompose_weight(w, s, p_lo)
    for (ry, rx), entry in subs.items():
        # number of outputs in this parity plane
        nyr = len(range(ry, oh, s))
        nxr = len(range(rx, ow, s))
        if nyr == 0 or nxr == 0:
            continue
        if entry is None:  # parity never touched by any tap -> zeros
            continue
        sub, ro, co = entry
        # output plane index b reads input rows b + ro[0] .. b + ro[-1]
        # -> VALID correlate input padded by (-ro[0]) on top/left and whatever
        #    the last plane index needs on bottom/right.
        pad_top, pad_left = -ro[0], -co[0]
        need_bot = (nyr - 1) + ro[-1] - (h - 1)   # last input row needed minus available
        need_rgt = (nxr - 1) + co[-1] - (w_in - 1)
        with jax.named_scope(obs.LAYOUT_PAD):
            xp = jnp.pad(
                x,
                (
                    (0, 0),
                    (max(pad_top, 0), max(need_bot, 0)),
                    (max(pad_left, 0), max(need_rgt, 0)),
                    (0, 0),
                ),
            )
            # crop if offsets start inside the input (pad_top < 0)
            xp = xp[:, max(-pad_top, 0):, max(-pad_left, 0):, :]
        if phase_sharding is not None:
            xp = lax.with_sharding_constraint(xp, phase_sharding)
        plane = lax.conv_general_dilated(
            xp, sub, window_strides=(1, 1), padding="VALID", dimension_numbers=_DIMS,
        )
        with jax.named_scope(obs.LAYOUT_PARITY_INTERLEAVE):
            out = out.at[:, ry::s, rx::s, :].set(plane[:, :nyr, :nxr, :])
    return out


# ---------------------------------------------------------------------------
# MAC counting
# ---------------------------------------------------------------------------

def macs_naive(h: int, w: int, cin: int, cout: int, k: int, s: int,
               p_lo: int, p_hi: int) -> int:
    """MACs of dense execution over the zero-inserted input (incl. zeros)."""
    oh, ow = out_size(h, s, k, p_lo, p_hi), out_size(w, s, k, p_lo, p_hi)
    return oh * ow * cin * cout * k * k


def macs_decomposed_transposed(h: int, w: int, cin: int, cout: int, k: int,
                               s: int, p_lo: int, p_hi: int) -> int:
    """Exact MACs issued by the decomposition (sum over parity planes)."""
    oh, ow = out_size(h, s, k, p_lo, p_hi), out_size(w, s, k, p_lo, p_hi)
    total = 0
    for ry in range(s):
        for rx in range(s):
            tr = len(parity_taps(k, s, p_lo, ry))
            tc = len(parity_taps(k, s, p_lo, rx))
            nyr = len(range(ry, oh, s))
            nxr = len(range(rx, ow, s))
            total += nyr * nxr * tr * tc * cin * cout
    return total
