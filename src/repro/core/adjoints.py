"""Adjoint (VJP) machinery for the decomposition engine (DESIGN.md §6).

The paper's central symmetry also governs gradients:

* the input-gradient of a **strided dense** convolution *is* a transposed
  convolution (stride ``s``, flipped/IO-transposed kernel) — route it through
  the weight-decomposition engine;
* the input-gradient of a **transposed** convolution *is* a strided dense
  convolution — route it through the dense engine;
* the input-gradient of a **dilated** convolution (stride 1) is a dilated
  convolution with the same step and the flipped kernel — route it through
  the input-decomposition engine;
* every **weight-gradient** is a batched correlation over strided input
  gathers — ``k**2`` tap slices contracted on the MXU, the same phase/parity
  gather the forward decomposition uses, never touching inserted zeros.

This module holds the engine-agnostic pieces: the kernel flip, the tap-gather
weight-gradient correlation, and the padding arithmetic that maps each
forward geometry to its adjoint geometry.  The Pallas kernels register
``jax.custom_vjp`` rules built from these (see ``repro.kernels``); the XLA
paths in :mod:`repro.core.dilated` / :mod:`repro.core.transposed` are lax
compositions and differentiate natively.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs


def flip_io(w: jax.Array) -> jax.Array:
    """Spatially flip an HWIO kernel and swap its in/out channels.

    ``flip_io(w)[ky, kx, co, ci] == w[k-1-ky, k-1-kx, ci, co]`` — the kernel
    of every input-gradient convolution.
    """
    return w[::-1, ::-1].swapaxes(2, 3)


def tap_correlation(a: jax.Array, b: jax.Array, kh: int, kw: int, *,
                    stride: int = 1, tap_step: int = 1) -> jax.Array:
    """Batched tap-gather correlation: the universal weight-gradient form.

    ``T[ty, tx, ca, cb] = sum_{n,oy,ox} a[n,oy,ox,ca] *
    b[n, stride*oy + tap_step*ty, stride*ox + tap_step*tx, cb]``.

    Each tap is one strided gather of ``b`` (a phase slice — no inserted
    zeros are ever read) contracted against ``a`` as a single
    ``(N*OH*OW, Ca) x (N*OH*OW, Cb)`` matmul on the MXU.  ``b`` must be
    pre-padded so every index is in range: extent
    ``>= tap_step*(k-1) + stride*(OH-1) + 1`` per spatial dim.
    """
    n, oh, ow, ca = a.shape
    cb = b.shape[-1]
    af = a.reshape(n * oh * ow, ca)
    rows = []
    for ty in range(kh):
        cols = []
        for tx in range(kw):
            bs = jax.lax.slice(
                b,
                (0, tap_step * ty, tap_step * tx, 0),
                (n, tap_step * ty + stride * (oh - 1) + 1,
                 tap_step * tx + stride * (ow - 1) + 1, cb),
                (1, stride, stride, 1),
            )
            cols.append(jax.lax.dot_general(
                af, bs.reshape(n * oh * ow, cb), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
        rows.append(jnp.stack(cols))
    return jnp.stack(rows)  # (k, k, Ca, Cb)


def _pad_to(x: jax.Array, lo_h: int, hi_h: int, lo_w: int, hi_w: int) -> jax.Array:
    """Pad (positive) or crop (negative) the spatial dims of an NHWC array."""
    x = x[:, max(-lo_h, 0): x.shape[1] - max(-hi_h, 0),
          max(-lo_w, 0): x.shape[2] - max(-hi_w, 0), :]
    return jnp.pad(x, ((0, 0), (max(lo_h, 0), max(hi_h, 0)),
                       (max(lo_w, 0), max(hi_w, 0)), (0, 0)))


# ---------------------------------------------------------------------------
# dense convolution  y = conv(x, w; stride s, pads (pl, ph) per dim)
# ---------------------------------------------------------------------------

@jax.named_scope(obs.GRAD_DX)
def dense_conv_dx(g: jax.Array, w: jax.Array, stride: int, p_lo: int,
                  h: int, w_in: int, tconv_fn) -> jax.Array:
    """Input-gradient of a strided dense conv == a transposed convolution.

    ``dx[i] = sum_t g[(i + p_lo - t)/s] w[t]`` (divisible terms only) — the
    weight-decomposition engine applied to the cotangent with the flipped
    kernel, low pad ``k-1-p_lo``, output padding chosen so the output extent
    recovers ``(h, w_in)`` (extra high-side rows are gradients w.r.t. the
    forward zero-pad and are cropped).

    ``tconv_fn(g, wf, stride, padding, output_padding)`` is the transposed
    engine of the active backend.
    """
    k = w.shape[0]
    hg, wg = g.shape[1], g.shape[2]
    op_h = h - (hg - 1) * stride - k + 2 * p_lo
    op_w = w_in - (wg - 1) * stride - k + 2 * p_lo
    op = max(0, op_h, op_w)
    dx = tconv_fn(g, flip_io(w), stride, k - 1 - p_lo, op)
    return dx[:, :h, :w_in, :]


@jax.named_scope(obs.GRAD_DW)
def dense_conv_dw(x: jax.Array, g: jax.Array, kh: int, kw: int, stride: int,
                  p_lo_h: int, p_lo_w: int) -> jax.Array:
    """Weight-gradient of a dense conv: ``kh*kw`` strided tap gathers of x."""
    n, h, w_in, _ = x.shape
    _, oh, ow, _ = g.shape
    need_h = (kh - 1) + stride * (oh - 1) + 1
    need_w = (kw - 1) + stride * (ow - 1) + 1
    xp = _pad_to(x, p_lo_h, need_h - h - p_lo_h, p_lo_w, need_w - w_in - p_lo_w)
    t = tap_correlation(g, xp, kh, kw, stride=stride)     # (kh, kw, Cout, Cin)
    return t.transpose(0, 1, 3, 2)


# ---------------------------------------------------------------------------
# transposed convolution  y = tconv(x, w; stride s, pads (p_lo, p_hi))
# ---------------------------------------------------------------------------

def _tconv_grad_pad(g: jax.Array, k: int, p_lo: int, p_hi: int) -> jax.Array:
    """Pad the tconv cotangent to ``(k-1-p_lo, k-1-p_hi)`` per spatial dim.

    Shared by the input- and weight-gradients below; negative amounts
    (``p_hi > k-1``, large ``output_padding``) crop instead.
    """
    return _pad_to(g, k - 1 - p_lo, k - 1 - p_hi, k - 1 - p_lo, k - 1 - p_hi)


@jax.named_scope(obs.GRAD_DX)
def tconv_dx(g: jax.Array, w: jax.Array, stride: int, p_lo: int, p_hi: int,
             conv_fn) -> jax.Array:
    """Input-gradient of a transposed conv == a strided dense convolution.

    ``dx[i] = sum_t g[s*i + p_lo - t] w[t]`` — the dense engine at stride
    ``s`` over the padded cotangent with the flipped kernel; the output
    extent is exactly the forward input extent (no crop needed).

    ``conv_fn(gp, wf, stride)`` is a VALID strided dense conv of the active
    backend.
    """
    k = w.shape[0]
    return conv_fn(_tconv_grad_pad(g, k, p_lo, p_hi), flip_io(w), stride)


@jax.named_scope(obs.GRAD_DW)
def tconv_dw(x: jax.Array, g: jax.Array, k: int, stride: int, p_lo: int,
             p_hi: int) -> jax.Array:
    """Weight-gradient of a transposed conv: tap gathers of the cotangent.

    ``dw[t] = sum_i x[i] g[s*i + p_lo - t]`` — with the cotangent padded as
    in :func:`tconv_dx` the gather index becomes ``s*i + (k-1-t)``: the dense
    tap correlation at flipped tap order.
    """
    gp = _tconv_grad_pad(g, k, p_lo, p_hi)
    t = tap_correlation(x, gp, k, k, stride=stride)       # (k, k, Cin, Cout)
    return t[::-1, ::-1]


# ---------------------------------------------------------------------------
# dilated convolution  y = conv(x, w; dilation d, SAME, stride 1)
# ---------------------------------------------------------------------------

@jax.named_scope(obs.GRAD_DX)
def dilated_conv_dx(g: jax.Array, w: jax.Array, dilation: int,
                    dilated_fn) -> jax.Array:
    """Input-gradient of a SAME dilated conv == the same dilated conv.

    With symmetric SAME padding ``p = d*(k-1)/2`` (odd ``k``), the adjoint
    is exactly the dilated engine applied to the cotangent with the flipped
    kernel — same step, same padding.  ``dilated_fn(g, wf, d)`` is the
    dilated engine of the active backend.
    """
    return dilated_fn(g, flip_io(w), dilation)


@jax.named_scope(obs.GRAD_DW)
def dilated_conv_dw(x: jax.Array, g: jax.Array, k: int, dilation: int) -> jax.Array:
    """Weight-gradient of a SAME dilated conv: tap gathers at step ``d``.

    ``dw[t] = sum_o g[o] x[o - p + d*t]`` — the taps stride the input at the
    dilation step, i.e. each tap reads one phase block (the same gather the
    forward input decomposition performs).
    """
    d = dilation
    p = d * (k - 1) // 2
    xp = _pad_to(x, p, p, p, p)
    t = tap_correlation(g, xp, k, k, tap_step=d)          # (k, k, Cout, Cin)
    return t.transpose(0, 1, 3, 2)


# ---------------------------------------------------------------------------
# fused epilogues (DESIGN.md §7)
# ---------------------------------------------------------------------------

def fused_epilogue_bwd(conv_apply, spec, x, w, eps, g):
    """Backward pass of a fused conv+epilogue kernel by adjoint re-entry.

    The fused forward computes ``E(conv(x, w))`` with ``E`` the elementwise
    epilogue; its pullback is the pullback of the *composition* — so the
    backward differentiates ``apply_reference(spec, conv_apply(x, w), eps)``
    with ``jax.vjp``.  ``conv_apply`` is the engine's own differentiable
    (epilogue-free) kernel, so the conv cotangent re-enters the decomposition
    adjoints of DESIGN.md §6 with fp32 accumulators, while the BN/PReLU/
    residual gradients are cheap elementwise jnp ops computed in fp32.

    The pre-epilogue conv output is *recomputed* here rather than saved by
    the forward — saving it would mean a second HBM write per tile, undoing
    exactly the traffic the fusion removes.

    Returns ``(dx, dw, deps)`` with ``deps`` matching the ``eps`` tuple.
    """
    from repro.kernels import epilogue as _ep

    def f(x, w, eps):
        return _ep.apply_reference(spec, conv_apply(x, w), eps)

    _, vjp = jax.vjp(f, x, w, eps)
    return vjp(g)


__all__ = [
    "flip_io", "tap_correlation", "dense_conv_dx", "dense_conv_dw",
    "tconv_dx", "tconv_dw", "dilated_conv_dx", "dilated_conv_dw",
    "fused_epilogue_bwd",
]
