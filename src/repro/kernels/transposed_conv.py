"""Decomposed transposed-convolution Pallas kernel (paper §II-C, Fig. 6/9).

Implements the paper's weight decomposition for *arbitrary* ``(kernel,
stride, output_padding)``: a transposed convolution with stride ``s``
decomposes into ``s*s`` parity sub-convolutions, and the per-parity tap
schedule — which kernel taps land on real (non-zero-inserted) input for each
output parity, and at which input offset — is generated programmatically from
``(k, s, padding)`` (the ``ceil(k/s) x ceil(k/s)`` sub-kernel assignment of
paper Fig. 6).  The kernel computes all parity planes in a single pass over
each input tile — the TPU analogue of Fig. 9's schedule where all ``k*k``
weights share one input broadcast.  No zero-inserted input is ever
materialised; MACs issued == nonzero MACs.

Output is produced as ``s*s`` parity planes ``(N, s*s, Hb, Wb, Cout)`` and
interleaved into ``(N, OH, OW, Cout)`` by a reshape/transpose in the wrapper
(a layout op on TPU).

The row halo (input rows past the tile edge needed by positive tap offsets)
is assembled without overlapping BlockSpecs by passing the input twice — the
current row tile and the next — and concatenating in VMEM; negative offsets
(taps reading rows *before* the block index, which appear whenever
``padding >= s``) are absorbed by shifting the whole input down with a pad.

An optional fused epilogue (:mod:`repro.kernels.epilogue`, DESIGN.md §7) is
applied per parity plane on the fp32 accumulator — including the identically
zero planes of ``k < s`` parities, whose *epilogue* output (BN shift,
residual) is not zero.  The residual operand is de-interleaved into the same
parity-plane layout by the wrapper (a layout op).

See DESIGN.md §3 for the schedule derivation.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels import tiling_policy
from repro.kernels.epilogue import (EpilogueSpec, apply_reference, apply_tile,
                                    pack_args)
from repro.kernels.util import resolve_interpret

_NO_EP = EpilogueSpec()


def parity_schedule(k: int, s: int, p_lo: int) -> list[list[tuple[int, int]]]:
    """Per-parity tap schedule for one spatial dim (paper §II-C, Fig. 6).

    Output pixel ``y = s*b + r`` (block ``b``, parity ``r``) reads kernel tap
    ``t`` iff ``(t - p_lo + r) % s == 0``, from input index ``b + off`` with
    ``off = (r + t - p_lo) // s``.  Returns ``[(t, off), ...]`` per parity
    ``r``; a parity's list is empty when no tap hits it (possible for
    ``k < s`` — that output plane is identically zero).
    """
    return [
        [(t, (r + t - p_lo) // s) for t in range(k) if (t - p_lo + r) % s == 0]
        for r in range(s)
    ]


def _tconv_kernel(x_cur, x_nxt, w, *rest, spec: EpilogueSpec, th: int,
                  wb: int, sched, shift: int, halo: int):
    """Fused all-parity step: every live tap shares one input window."""
    out = rest[-1]
    ep_refs = rest[:-1]
    xw = x_cur[0]
    if halo > 0:
        xw = jnp.concatenate([xw, x_nxt[0][:halo]], axis=0)
    cin = xw.shape[-1]
    tc = out.shape[-1]

    def tap(oy, ox, wt):
        rows = xw[oy : oy + th, ox : ox + wb, :]
        return jax.lax.dot_general(
            rows.reshape(th * wb, cin), wt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    planes = []
    idx = 0
    for rtaps in sched:
        for ctaps in sched:
            acc = None
            for ty, oy in rtaps:
                for tx, ox in ctaps:
                    v = tap(oy + shift, ox + shift, w[ty, tx])
                    acc = v if acc is None else acc + v
            if acc is None:         # empty tap set (k < s): zero conv plane
                acc = jnp.zeros((th * wb, tc), jnp.float32)
            if not spec.empty:
                args = tuple(r[0][idx] if name == "residual" else r[...]
                             for name, r in zip(spec.slots, ep_refs))
                acc = apply_tile(spec, acc, args, flat=th * wb)
            planes.append(acc)
            idx += 1
    s2 = len(planes)
    out[0] = jnp.stack(planes, axis=0).reshape(s2, th, wb, tc).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=(
    "stride", "padding", "output_padding", "th", "tc", "interpret",
    "epilogue"))
def transposed_conv2d(x: jax.Array, w: jax.Array, *, stride: int = 2,
                      padding: int | None = None, output_padding: int = 1,
                      th: int = 8, tc: int = 128,
                      interpret: bool | None = None,
                      epilogue: EpilogueSpec | None = None,
                      scale: jax.Array | None = None,
                      shift: jax.Array | None = None,
                      alpha: jax.Array | None = None,
                      residual: jax.Array | None = None) -> jax.Array:
    """Fused decomposed transposed conv for arbitrary ``(k, stride)``.

    Differentiable: a ``jax.custom_vjp`` routes the input-gradient through
    the strided dense engine (the adjoint of upsampling is downsampling) and
    the weight-gradient through tap-gather correlations
    (:mod:`repro.core.adjoints`, DESIGN.md §6); the fused-epilogue path
    differentiates by adjoint re-entry (``adjoints.fused_epilogue_bwd``).

    Args:
      x: (N, H, W, Cin).   w: (k, k, Cin, Cout), square.
      stride: upsampling factor ``s >= 1``.
      padding: low-side pad of the zero-inserted input; ``None`` -> (k-1)//2.
      output_padding: extra high-side output size (``p_hi = padding + it``).
      th: output *block* rows per tile.  tc: Cout tile width.
      interpret: None -> auto (interpret on CPU), or an explicit override.
      epilogue: optional :class:`EpilogueSpec` fused per parity plane
        (DESIGN.md §7), with operands ``scale``/``shift``/``alpha``/
        ``residual`` to match.
    Returns:
      (N, OH, OW, Cout) with ``OH = (H-1)*s + p_lo + p_hi - k + 2``.
    """
    interpret = resolve_interpret(interpret)
    kh, kw = w.shape[0], w.shape[1]
    if kh != kw:
        raise ValueError(f"square kernels only, got {kh}x{kw}")
    p_lo = (kh - 1) // 2 if padding is None else padding
    spec = _NO_EP if epilogue is None else epilogue
    eps = pack_args(spec, scale=scale, shift=shift, alpha=alpha,
                    residual=residual)
    if stride == 1:
        # no zero-insertion -> plain dense correlation with (p_lo, p_hi) pads
        p_hi = p_lo + output_padding
        y = jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1),
            padding=[(p_lo, p_hi), (p_lo, p_hi)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return apply_reference(spec, y, eps)
    if spec.empty:
        return _tconv_vjp(x, w, stride, p_lo, output_padding, th, tc,
                          interpret)
    return _tconv_ep_vjp(x, w, eps, spec, stride, p_lo, output_padding, th,
                         tc, interpret)


@jax.named_scope(obs.LAYOUT_PAD)
def _residual_to_planes(res: jax.Array, s: int, hb: int, wb: int, rows_p: int,
                        cout_p: int) -> jax.Array:
    """De-interleave an (N, OH, OW, C) residual into padded parity planes.

    Inverse of the wrapper's output interleave: plane ``s*ry + rx`` at block
    ``(b, c)`` holds ``res[:, s*b + ry, s*c + rx, :]`` — a reshape/transpose
    layout op, then pad to the kernel's blocked extents.
    """
    n, oh, ow, cout = res.shape
    rp = jnp.pad(res, ((0, 0), (0, hb * s - oh), (0, wb * s - ow), (0, 0)))
    rp = rp.reshape(n, hb, s, wb, s, cout).transpose(0, 2, 4, 1, 3, 5)
    rp = rp.reshape(n, s * s, hb, wb, cout)
    return jnp.pad(rp, ((0, 0), (0, 0), (0, rows_p - hb), (0, 0),
                        (0, cout_p - cout)))


def _tconv_raw(x: jax.Array, w: jax.Array, eps: tuple, spec: EpilogueSpec,
               s: int, p_lo: int, output_padding: int, th: int, tc: int,
               interpret: bool) -> jax.Array:
    n, h, w_in, cin = x.shape
    k, _, _, cout = w.shape
    p_hi = p_lo + output_padding
    oh = (h - 1) * s + p_lo + p_hi - k + 2
    ow = (w_in - 1) * s + p_lo + p_hi - k + 2
    if oh <= 0 or ow <= 0:
        raise ValueError(f"degenerate output {oh}x{ow} for input {h}x{w_in}")
    hb, wb = math.ceil(oh / s), math.ceil(ow / s)  # block rows/cols per parity

    sched = parity_schedule(k, s, p_lo)
    offs = [o for taps in sched for _, o in taps]
    shift = max(0, -min(offs))      # absorb negative offsets by shifting input
    halo = max(offs) + shift        # rows needed past the current tile

    th = max(min(th, hb), halo)     # next-tile concat must cover the halo
    n_row_tiles = math.ceil(hb / th)
    tc = min(tc, cout)
    n_cout_tiles = math.ceil(cout / tc)
    cout_p = n_cout_tiles * tc

    # rows: one extra tile keeps the next-tile BlockSpec in bounds
    rows_p = max((n_row_tiles + 1) * th, h + shift)
    rows_p = math.ceil(rows_p / th) * th
    cols_p = max(wb + halo, w_in + shift)
    with jax.named_scope(obs.LAYOUT_PAD):
        xp = jnp.pad(x, ((0, 0), (shift, rows_p - h - shift),
                         (shift, cols_p - w_in - shift), (0, 0)))
        wp = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, cout_p - cout)))

    # grid order (batch, cout tile, row tile): the row stream is innermost —
    # the pipeline double-buffers consecutive input tiles (halo pair advances
    # one block per step) while the weight tile stays resident per cout tile
    grid = (n, n_cout_tiles, n_row_tiles)
    x_cur = pl.BlockSpec((1, th, cols_p, cin), lambda b, c, i: (b, i, 0, 0))
    x_nxt = pl.BlockSpec((1, th, cols_p, cin), lambda b, c, i: (b, i + 1, 0, 0))
    w_spec = pl.BlockSpec((k, k, cin, tc), lambda b, c, i: (0, 0, 0, c))
    out_spec = pl.BlockSpec((1, s * s, th, wb, tc), lambda b, c, i: (b, 0, i, 0, c))

    # epilogue operands: channel vectors tiled on the cout axis, the residual
    # de-interleaved to parity-plane layout and blocked like the output
    from repro.kernels.conv2d import _chan_operand

    ep_in, ep_specs = [], []
    for name, v in zip(spec.slots, eps):
        if name == "residual":
            if v.shape != (n, oh, ow, cout):
                raise ValueError(f"residual shape {v.shape} != output "
                                 f"{(n, oh, ow, cout)}")
            ep_in.append(_residual_to_planes(v, s, hb, wb,
                                             n_row_tiles * th, cout_p))
            ep_specs.append(pl.BlockSpec((1, s * s, th, wb, tc),
                                         lambda b, c, i: (b, 0, i, 0, c)))
        else:
            ep_in.append(_chan_operand(v, cout, cout_p))
            ep_specs.append(pl.BlockSpec((1, tc), lambda b, c, i: (0, c)))

    planes = pl.pallas_call(
        functools.partial(_tconv_kernel, spec=spec, th=th, wb=wb, sched=sched,
                          shift=shift, halo=halo),
        grid=grid,
        in_specs=[x_cur, x_nxt, w_spec, *ep_specs],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n, s * s, n_row_tiles * th, wb, cout_p), x.dtype),
        # batch/cout steps independent; sequential row stream -> Mosaic
        # overlaps each tile's DMA with the previous tile's MXU work
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=tiling_policy.VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xp, xp, wp, *ep_in)

    with jax.named_scope(obs.LAYOUT_CROP):
        planes = planes[:, :, :hb, :, :cout]               # (N, s*s, Hb, Wb, C)
    # interleave parities: out[n, s*b+ry, s*c+rx] = planes[n, s*ry+rx, b, c]
    with jax.named_scope(obs.LAYOUT_PARITY_INTERLEAVE):
        planes = planes.reshape(n, s, s, hb, wb, cout)
        out = planes.transpose(0, 3, 1, 4, 2, 5).reshape(n, hb * s, wb * s,
                                                          cout)
    with jax.named_scope(obs.LAYOUT_CROP):
        return out[:, :oh, :ow, :]


def _tconv_impl(x: jax.Array, w: jax.Array, s: int, p_lo: int,
                output_padding: int, th: int, tc: int,
                interpret: bool) -> jax.Array:
    return _tconv_raw(x, w, (), _NO_EP, s, p_lo, output_padding, th, tc,
                      interpret)


# ---------------------------------------------------------------------------
# Custom VJP (DESIGN.md §6): the input-gradient of a transposed conv IS a
# strided dense convolution — it routes through the dense Pallas engine; the
# weight-gradient is a batched tap-gather correlation on the MXU.
# ---------------------------------------------------------------------------

_tconv_vjp = jax.custom_vjp(_tconv_impl, nondiff_argnums=(2, 3, 4, 5, 6, 7))


def _tconv_fwd(x, w, s, p_lo, output_padding, th, tc, interpret):
    return _tconv_impl(x, w, s, p_lo, output_padding, th, tc, interpret), (x, w)


def _tconv_bwd(s, p_lo, output_padding, th, tc, interpret, res, g):
    from repro.core import adjoints
    from repro.kernels.conv2d import conv2d_phase_planes

    x, w = res
    k = w.shape[0]
    p_hi = p_lo + output_padding

    def conv_fn(gp, wf, stride):
        return conv2d_phase_planes(gp, wf, stride=stride, th=th, tc=tc,
                                   interpret=interpret)

    dx = adjoints.tconv_dx(g, w, s, p_lo, p_hi, conv_fn)
    dw = adjoints.tconv_dw(x, g, k, s, p_lo, p_hi)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_tconv_vjp.defvjp(_tconv_fwd, _tconv_bwd)


# ---------------------------------------------------------------------------
# Fused-epilogue VJP (DESIGN.md §7): adjoint re-entry through the §6 rules.
# ---------------------------------------------------------------------------

def _tconv_ep_impl(x, w, eps, spec, s, p_lo, output_padding, th, tc,
                   interpret):
    return _tconv_raw(x, w, eps, spec, s, p_lo, output_padding, th, tc,
                      interpret)


_tconv_ep_vjp = jax.custom_vjp(_tconv_ep_impl,
                               nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))


def _tconv_ep_fwd(x, w, eps, spec, s, p_lo, output_padding, th, tc, interpret):
    y = _tconv_ep_impl(x, w, eps, spec, s, p_lo, output_padding, th, tc,
                       interpret)
    return y, (x, w, eps)


def _tconv_ep_bwd(spec, s, p_lo, output_padding, th, tc, interpret, res, g):
    from repro.core import adjoints

    x, w, eps = res

    def conv_apply(xx, ww):
        return _tconv_vjp(xx, ww, s, p_lo, output_padding, th, tc, interpret)

    return adjoints.fused_epilogue_bwd(conv_apply, spec, x, w, eps, g)


_tconv_ep_vjp.defvjp(_tconv_ep_fwd, _tconv_ep_bwd)
