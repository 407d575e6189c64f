"""Dense 2-D convolution Pallas kernel — the MXU workhorse after decomposition.

The paper's decomposition reduces dilated/transposed convolutions to *dense*
convolutions; this kernel is the TPU execution engine for those.  It computes
an NHWC convolution as a sum of ``kh*kw`` shifted implicit-GEMM taps, keeping
the MXU contraction on ``Cin`` and the lane dimension on a ``Cout`` tile.
Rectangular kernels (``kh != kw`` — ENet's 5x1/1x5 asymmetric pair) are
first-class: the tap loops, pads and halo are all per-dim.

Tiling (per grid step): one batch element, ``TH`` output rows x full output
width, one ``TC``-wide ``Cout`` tile.  The row halo is assembled *without
overlapping BlockSpecs* by passing the input twice — the current row tile
and the next row tile (standard Pallas halo idiom).

A strided conv (``stride > 1``) has two forms, which give the same bits:

* **strided loads** (``_conv_kernel_strided``): the blocks are ``s * TH``
  rows of the padded NHWC input, and tap ``(dy, dx)`` is loaded straight
  from the block refs with ``pl.ds(start, size, stride=s)`` — its last
  ``dy // s`` rows from the next row tile.  Mosaic takes a strided *ref*
  load, where it refuses a strided slice of a loaded *value*, but only of
  fp32 data with at most 128 channels (:func:`_strided_loads_lower`).
* **phase planes** (``_conv_kernel``): the padded input is first split
  into ``s**2`` phase planes (a layout op), so every tap reads a
  unit-stride window of one plane; the halo is ``(kh - 1) // s`` phase
  rows, concatenated in VMEM.

Forward-only use — the primal of the custom VJPs, which is what a jitted
inference forward runs — takes the strided loads where they lower: the
phase split of a 3-channel image is a lane-sparse transpose, the largest
XLA op of a segmentation frame.  The VJPs' fwd rules, and the strided dense
conv inside the transposed conv's backward, keep the phase planes: with
strided loads on those paths too, the ENet-512 train step measured 11%
slower on a TPU v5e (397.7 -> 442.5 ms), a regression not yet placed.  At
``stride == 1`` the two forms coincide, and the phase split is the identity.

An optional fused epilogue (:mod:`repro.kernels.epilogue`, DESIGN.md §7) —
folded BN scale/shift, PReLU, residual add — is applied to the fp32
accumulator tile while it is still in VMEM, removing up to three elementwise
HBM passes per convolution.

VMEM per step ~ x_tile(2 * s*s * TH * Wq * Cin) + w(kh*kw*Cin*TC) +
out(TH*W*TC) in either form, each padded to the (sublane, 128-lane) register
tile; the kernel compiles with ``tiling_policy.VMEM_LIMIT_BYTES`` of scoped
VMEM.  The grid runs the row stream innermost with ``dimension_semantics``
declared, so Mosaic's pipeliner double-buffers the input halo pair (next
tile's DMA overlaps the current tile's MXU work) while the weight tile stays
resident for a whole ``Cout``-tile pass; ``tiling_policy.footprint_bytes``
mirrors these blocks, the larger of the two forms' sets, when the autotuner
scores candidates (DESIGN.md §12).

Mixed precision (DESIGN.md §12): bf16 inputs accumulate in fp32 — every tap
GEMM issues with ``preferred_element_type=jnp.float32``, the fused epilogue
applies to the fp32 accumulator, and only the final output cast returns to
the input dtype.  The VJPs keep fp32 tap-correlation accumulation and cast
``dx``/``dw`` back to the primal dtypes.
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
from repro.kernels.epilogue import EpilogueSpec, apply_tile, pack_args
from repro.kernels.util import resolve_interpret

_NO_EP = EpilogueSpec()


def _accumulate(tap, w, *, kh: int, kw: int, rows: int, tc: int):
    """Sum the ``kh*kw`` tap GEMMs in tap order, in fp32: ``tap(dy, dx)``
    is the (TH, W_out, Cin) input window that meets ``w[dy, dx]``."""
    acc = jnp.zeros((rows, tc), jnp.float32)
    for dy in range(kh):
        for dx in range(kw):
            xs = tap(dy, dx)
            acc += jax.lax.dot_general(
                xs.reshape(rows, xs.shape[-1]), w[dy, dx],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    return acc


def _store(acc, out, ep_refs, spec: EpilogueSpec, th: int, w_out: int):
    """Apply the fused epilogue to the fp32 tile and write it out."""
    if not spec.empty:
        args = tuple(r[0] if name == "residual" else r[...]
                     for name, r in zip(spec.slots, ep_refs))
        acc = apply_tile(spec, acc, args, flat=th * w_out)
    out[0] = acc.reshape(th, w_out, out.shape[-1]).astype(out.dtype)


def _conv_kernel(x_cur, x_nxt, w, *rest, spec: EpilogueSpec, th: int,
                 kh: int, kw: int, stride: int, w_out: int, halo: int):
    """One (batch, row-tile, cout-tile) grid step, phase-plane form.

    The input arrives split into ``stride**2`` phase planes, so tap
    ``(dy, dx)`` of a strided conv is a unit-stride window of plane
    ``(dy % s, dx % s)`` at offset ``(dy // s, dx // s)``.  Every conv at
    stride 1 runs this body (one plane); a strided one runs it under a
    VJP's fwd rule and inside the transposed conv's backward (module doc).
    """
    s = stride
    # assemble the window: TH phase rows + halo rows from the next tile
    xw = x_cur[0]
    if halo > 0:
        xw = jnp.concatenate([xw, x_nxt[0][:, :halo]], axis=1)

    def tap(dy, dx):
        oy, ox = dy // s, dx // s
        return xw[(dy % s) * s + dx % s, oy:oy + th, ox:ox + w_out, :]

    acc = _accumulate(tap, w, kh=kh, kw=kw, rows=th * w_out,
                      tc=rest[-1].shape[-1])
    _store(acc, rest[-1], rest[:-1], spec, th, w_out)


def _conv_kernel_strided(x_cur, x_nxt, w, *rest, spec: EpilogueSpec,
                         th: int, kh: int, kw: int, stride: int, w_out: int):
    """One (batch, row-tile, cout-tile) grid step, strided-load form.

    The blocks are ``s * TH`` rows of the padded input.  Output row ``t`` of
    tap ``(dy, dx)`` reads padded row ``s*t + dy``: rows ``t < TH - dy//s``
    lie in this tile, the last ``dy // s`` at row ``dy % s`` of the next
    tile on.  Both parts are strided loads from the refs, so the tap window
    holds the very values the phase-plane form reads, and the GEMMs give
    the same bits.
    """
    s = stride

    def tap(dy, dx):
        n_nxt = dy // s
        cols = pl.ds(dx, w_out, stride=s)
        parts = []
        if n_nxt < th:
            parts.append(x_cur[0, pl.ds(dy, th - n_nxt, stride=s), cols])
        if n_nxt:
            parts.append(x_nxt[0, pl.ds(dy % s, n_nxt, stride=s), cols])
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

    acc = _accumulate(tap, w, kh=kh, kw=kw, rows=th * w_out,
                      tc=rest[-1].shape[-1])
    _store(acc, rest[-1], rest[:-1], spec, th, w_out)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "th", "tc", "interpret", "epilogue"),
)
def conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1,
           padding: str | int = "SAME", th: int = 8, tc: int = 128,
           interpret: bool | None = None,
           epilogue: EpilogueSpec | None = None,
           scale: jax.Array | None = None, shift: jax.Array | None = None,
           alpha: jax.Array | None = None,
           residual: jax.Array | None = None) -> jax.Array:
    """Pallas dense convolution. NHWC x HWIO -> NHWC.  Differentiable: a
    ``jax.custom_vjp`` routes the input-gradient through the transposed-conv
    engine and the weight-gradient through tap-gather correlations
    (:mod:`repro.core.adjoints`, DESIGN.md §6); the fused-epilogue path
    differentiates by adjoint re-entry (``adjoints.fused_epilogue_bwd``).

    Args:
      x: (N, H, W, Cin).
      w: (kh, kw, Cin, Cout) — rectangular kernels supported.
      stride: spatial stride (1 or 2 used in this repo).
      padding: "SAME", "VALID" or an explicit symmetric int.
      th: output rows per tile.  tc: Cout tile width (lane dim, 128 on MXU).
      interpret: None -> auto (interpret on CPU), or an explicit override.
      epilogue: optional :class:`EpilogueSpec` fused into the kernel; the
        spec's operands (``scale``/``shift``/``alpha``/``residual``) must be
        passed to match (DESIGN.md §7).
    """
    interpret = resolve_interpret(interpret)
    kh, kw = w.shape[0], w.shape[1]
    if isinstance(padding, int):
        pads = ((padding, padding), (padding, padding))
    elif padding == "SAME":
        pads = (((kh - 1) // 2, kh // 2), ((kw - 1) // 2, kw // 2))
    else:  # VALID
        pads = ((0, 0), (0, 0))
    spec = _NO_EP if epilogue is None else epilogue
    if spec.empty:
        return _conv2d_vjp(x, w, stride, pads, th, tc, interpret)
    eps = pack_args(spec, scale=scale, shift=shift, alpha=alpha,
                    residual=residual)
    return _conv2d_ep_vjp(x, w, eps, spec, stride, pads, th, tc, interpret)


def _chan_operand(v: jax.Array, cout: int, cout_p: int) -> jax.Array:
    """Broadcast a scalar/per-channel operand to a padded (1, cout_p) row."""
    from repro.kernels.epilogue import _chanvec

    with jax.named_scope(obs.LAYOUT_PAD):
        return jnp.pad(_chanvec(v, cout), (0, cout_p - cout)).reshape(
            1, cout_p)


def _strided_loads_lower(dtype, cin: int) -> bool:
    """Whether Mosaic lowers the strided-load form: it takes a strided ref
    load only of 32-bit data, from a block whose channels fit one 128-lane
    tile (refused on a TPU v5e compile: "Strided load with non 32-bit
    data", "The last dim size is not 128").  Elsewhere the phase planes
    run."""
    return jnp.dtype(dtype).itemsize == 4 and cin <= tiling_policy.LANES


def _conv2d_raw(x: jax.Array, w: jax.Array, eps: tuple, spec: EpilogueSpec,
                stride: int, pads: tuple[tuple[int, int], tuple[int, int]],
                th: int, tc: int, interpret: bool, *,
                strided_loads: bool) -> jax.Array:
    """The conv as one ``pallas_call``; ``strided_loads`` picks the form of
    a strided conv (module doc) and is moot at ``stride == 1``."""
    n, h, w_in, cin = x.shape
    kh, kw, _, cout = w.shape
    s = stride
    ph, pw = pads
    h_out = (h + ph[0] + ph[1] - kh) // s + 1
    w_out = (w_in + pw[0] + pw[1] - kw) // s + 1

    # phase-split geometry: output row t, tap dy reads padded input row
    # s*t + dy = s*(t + dy//s) + dy%s, i.e. row t + dy//s of phase dy%s
    halo = (kh - 1) // s
    th = min(th, h_out)
    th = max(th, halo)      # the next row tile must cover the halo
    n_row_tiles = math.ceil(h_out / th)
    h_out_p = n_row_tiles * th
    tc = min(tc, cout)
    n_cout_tiles = math.ceil(cout / tc)
    cout_p = n_cout_tiles * tc

    # phase rows: every row tile plus one extra tile that the next-tile
    # BlockSpec reads for the halo; phase cols: w_out plus the column halo
    rows_q = h_out_p + th
    cols_q = w_out + (kw - 1) // s
    with jax.named_scope(obs.LAYOUT_PAD):
        xp = jnp.pad(
            x,
            ((0, 0), (ph[0], max(s * rows_q - h - ph[0], 0)),
             (pw[0], max(s * cols_q - w_in - pw[0], 0)), (0, 0)),
        )[:, :s * rows_q, :s * cols_q, :]
        wp = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, cout_p - cout)))
    if strided_loads and s > 1 and _strided_loads_lower(x.dtype, cin):
        # (N, s*Rq, s*Cq, Cin) as it is: s*th padded rows a row tile
        kernel = functools.partial(_conv_kernel_strided, spec=spec, th=th,
                                   kh=kh, kw=kw, stride=s, w_out=w_out)
        x_block = (1, s * th, s * cols_q, cin)
        x_index = lambda b, i: (b, i, 0, 0)
    else:
        # (N, s*Rq, s*Cq, Cin) -> (N, s*s, Rq, Cq, Cin): a layout op
        # (identity for s == 1) that turns every strided tap into a
        # unit-stride window
        with jax.named_scope(obs.LAYOUT_PHASE_SPLIT):
            xp = xp.reshape(n, rows_q, s, cols_q, s, cin).transpose(
                0, 2, 4, 1, 3, 5)
            xp = xp.reshape(n, s * s, rows_q, cols_q, cin)
        kernel = functools.partial(_conv_kernel, spec=spec, th=th, kh=kh,
                                   kw=kw, stride=s, w_out=w_out, halo=halo)
        x_block = (1, s * s, th, cols_q, cin)
        x_index = lambda b, i: (b, 0, i, 0, 0)

    # grid order (batch, cout tile, row tile): the row stream is innermost,
    # so the pipeline double-buffers consecutive input row tiles (the halo
    # pair advances by one block per step) while the weight tile's block
    # index is unchanged across the whole inner stream and stays resident
    grid = (n, n_cout_tiles, n_row_tiles)
    x_spec_cur = pl.BlockSpec(x_block, lambda b, c, i: x_index(b, i))
    x_spec_nxt = pl.BlockSpec(x_block, lambda b, c, i: x_index(b, i + 1))
    w_spec = pl.BlockSpec((kh, kw, cin, tc), lambda b, c, i: (0, 0, 0, c))
    out_spec = pl.BlockSpec((1, th, w_out, tc), lambda b, c, i: (b, i, 0, c))

    # epilogue operands: channel vectors as padded (1, cout_p) rows tiled on
    # the cout grid axis; the residual blocked exactly like the output
    ep_in, ep_specs = [], []
    for name, v in zip(spec.slots, eps):
        if name == "residual":
            if v.shape != (n, h_out, w_out, cout):
                raise ValueError(f"residual shape {v.shape} != output "
                                 f"{(n, h_out, w_out, cout)}")
            with jax.named_scope(obs.LAYOUT_PAD):
                ep_in.append(jnp.pad(v, ((0, 0), (0, h_out_p - h_out),
                                         (0, 0), (0, cout_p - cout))))
            ep_specs.append(pl.BlockSpec((1, th, w_out, tc),
                                         lambda b, c, i: (b, i, 0, c)))
        else:
            ep_in.append(_chan_operand(v, cout, cout_p))
            ep_specs.append(pl.BlockSpec((1, tc), lambda b, c, i: (0, c)))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec_cur, x_spec_nxt, w_spec, *ep_specs],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n, h_out_p, w_out, cout_p), x.dtype),
        # batch/cout steps are independent; the row stream is sequential so
        # Mosaic's pipeliner overlaps each tile's DMA with the previous
        # tile's MXU work (double-buffered VMEM streams)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=tiling_policy.VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xp, xp, wp, *ep_in)
    with jax.named_scope(obs.LAYOUT_CROP):
        return out[:, :h_out, :, :cout]


def _conv2d_impl(x: jax.Array, w: jax.Array, stride: int,
                 pads: tuple[tuple[int, int], tuple[int, int]],
                 th: int, tc: int, interpret: bool) -> jax.Array:
    # the primal: what runs where no gradient is taken
    return _conv2d_raw(x, w, (), _NO_EP, stride, pads, th, tc, interpret,
                       strided_loads=True)


@functools.partial(jax.jit, static_argnames=("stride", "th", "tc",
                                             "interpret"))
def conv2d_phase_planes(x: jax.Array, w: jax.Array, *, stride: int, th: int,
                        tc: int, interpret: bool) -> jax.Array:
    """A VALID dense conv in the phase-plane form, for a conv that a
    backward rule runs (the transposed conv's input-gradient): there it
    keeps the form that the training step has measured (module doc)."""
    return _conv2d_raw(x, w, (), _NO_EP, stride, ((0, 0), (0, 0)), th, tc,
                       interpret, strided_loads=False)


# ---------------------------------------------------------------------------
# Custom VJP (DESIGN.md §6): the input-gradient of a strided dense conv IS a
# transposed convolution — it routes through the weight-decomposition engine
# (the fused Pallas transposed-conv kernel); the weight-gradient is a batched
# tap-gather correlation on the MXU.
# ---------------------------------------------------------------------------

_conv2d_vjp = jax.custom_vjp(_conv2d_impl, nondiff_argnums=(2, 3, 4, 5, 6))


def _conv2d_fwd(x, w, stride, pads, th, tc, interpret):
    y = _conv2d_raw(x, w, (), _NO_EP, stride, pads, th, tc, interpret,
                    strided_loads=False)
    return y, (x, w)


@jax.named_scope(obs.GRAD_DX)
def _dx_lax(g, w, stride, pads, h, w_in):
    """Fallback input-gradient (rectangular kernels / exotic pads): the same
    adjoint expressed as one lhs-dilated lax convolution."""
    from repro.core.adjoints import flip_io

    kh, kw = w.shape[0], w.shape[1]
    (pl_h, _), (pl_w, _) = pads
    hg, wg = g.shape[1], g.shape[2]
    ph_h = h - (hg - 1) * stride - 1 + pl_h - (kh - 1)
    ph_w = w_in - (wg - 1) * stride - 1 + pl_w - (kw - 1)
    return jax.lax.conv_general_dilated(
        g, flip_io(w), window_strides=(1, 1),
        padding=[(kh - 1 - pl_h, kh - 1 + ph_h), (kw - 1 - pl_w, kw - 1 + ph_w)],
        lhs_dilation=(stride, stride),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _conv2d_bwd(stride, pads, th, tc, interpret, res, g):
    from repro.core import adjoints

    x, w = res
    kh, kw, _, _ = w.shape
    (pl_h, _), (pl_w, _) = pads
    n, h, w_in, _ = x.shape
    if kh == kw and pl_h == pl_w and kh - 1 - pl_h >= 0:
        from repro.kernels.transposed_conv import transposed_conv2d as _tconv

        def tconv_fn(gg, wf, s, p_lo, op):
            return _tconv(gg, wf, stride=s, padding=p_lo, output_padding=op,
                          th=th, tc=tc, interpret=interpret)

        dx = adjoints.dense_conv_dx(g, w, stride, pl_h, h, w_in, tconv_fn)
    else:
        dx = _dx_lax(g, w, stride, pads, h, w_in)
    dw = adjoints.dense_conv_dw(x, g, kh, kw, stride, pl_h, pl_w)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_conv2d_vjp.defvjp(_conv2d_fwd, _conv2d_bwd)


# ---------------------------------------------------------------------------
# Fused-epilogue VJP (DESIGN.md §7): the backward differentiates the
# composition conv∘epilogue by re-entry — the conv cotangent flows through
# the §6 adjoints above, the epilogue gradients are elementwise fp32 ops.
# ---------------------------------------------------------------------------

def _conv2d_ep_impl(x, w, eps, spec, stride, pads, th, tc, interpret):
    return _conv2d_raw(x, w, eps, spec, stride, pads, th, tc, interpret,
                       strided_loads=True)


_conv2d_ep_vjp = jax.custom_vjp(_conv2d_ep_impl,
                                nondiff_argnums=(3, 4, 5, 6, 7, 8))


def _conv2d_ep_fwd(x, w, eps, spec, stride, pads, th, tc, interpret):
    y = _conv2d_raw(x, w, eps, spec, stride, pads, th, tc, interpret,
                    strided_loads=False)
    return y, (x, w, eps)


def _conv2d_ep_bwd(spec, stride, pads, th, tc, interpret, res, g):
    from repro.core import adjoints

    x, w, eps = res

    def conv_apply(xx, ww):
        return _conv2d_vjp(xx, ww, stride, pads, th, tc, interpret)

    return adjoints.fused_epilogue_bwd(conv_apply, spec, x, w, eps, g)


_conv2d_ep_vjp.defvjp(_conv2d_ep_fwd, _conv2d_ep_bwd)
