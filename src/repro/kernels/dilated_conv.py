"""Decomposed dilated-convolution Pallas pipeline (paper §II-B, Fig. 4/8).

TPU-native execution of the paper's input decomposition: the ``d**2`` phase
blocks are stacked on the *batch* axis by a pure layout transform (XLA
reshape/transpose — no FLOPs), then ONE dense Pallas convolution processes
all phases at full MXU occupancy, and the outputs interleave back.  This is
the phase-batched strategy recorded as a beyond-paper optimization in
DESIGN.md §2b: where the paper schedules ragged blocks sequentially on PE
blocks, a wide MXU prefers a single batched dense conv.

``stride > 1`` generalizes the same pipeline: outputs group into
``(d/gcd(s,d))**2`` classes (see :func:`repro.core.dilated.stride_class_schedule`),
each class's phase window is extracted by a layout slice, and all class
windows batch into ONE strided VALID Pallas convolution.

The dense conv is the :mod:`repro.kernels.conv2d` Pallas kernel, so the whole
dilated path runs through the same engine the paper's hardware would use.
Fused epilogues (DESIGN.md §7) ride the same pipeline: because the phase
transform is a pure relabeling of output pixels, the per-channel BN/PReLU
ops commute with it, and the residual is carried through the *same* phase
transform so the add happens inside the dense kernel.  The strided
output-class path applies the epilogue after the stitch instead — its class
windows have uneven output extents, so a per-window residual transform
would not be a pure relabeling (recorded fallback, numerics identical).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.conv2d import conv2d as _dense_conv
from repro.kernels.epilogue import EpilogueSpec, apply_reference, pack_args
from repro.kernels.util import resolve_interpret

_NO_EP = EpilogueSpec()


@functools.partial(jax.jit,
                   static_argnames=("dilation", "stride", "th", "tc",
                                    "interpret", "epilogue"))
def dilated_conv2d(x: jax.Array, w: jax.Array, dilation: int, *,
                   stride: int = 1, th: int = 8, tc: int = 128,
                   interpret: bool | None = None,
                   epilogue: EpilogueSpec | None = None,
                   scale: jax.Array | None = None,
                   shift: jax.Array | None = None,
                   alpha: jax.Array | None = None,
                   residual: jax.Array | None = None) -> jax.Array:
    """SAME dilated convolution via phase decomposition + dense Pallas conv.

    Differentiable on all paths: the stride-1 path registers a
    ``jax.custom_vjp`` exploiting the adjoint symmetry — the input-gradient
    of a dilated conv is the same dilated conv with the flipped kernel, so
    it re-enters this engine; the weight-gradient is a tap-gather correlation
    at step ``d`` (:mod:`repro.core.adjoints`, DESIGN.md §6).  The ``d = 1``
    and strided paths are compositions over the dense Pallas kernel and
    differentiate through its VJP — as does the fused-epilogue path, whose
    epilogue runs inside the dense kernel on the phase-batched layout.

    Args:
      x: (N, H, W, Cin).   w: (k, k, Cin, Cout) compact kernel.
      dilation: step d = D + 1.
      stride: output stride s (output extent ``ceil(H/s)``).
      interpret: None -> auto (interpret on CPU), or an explicit override.
      epilogue: optional :class:`EpilogueSpec` (DESIGN.md §7) with operands
        ``scale``/``shift``/``alpha``/``residual`` to match.
    Returns:
      (N, ceil(H/s), ceil(W/s), Cout).
    """
    interpret = resolve_interpret(interpret)
    d, s = dilation, stride
    spec = _NO_EP if epilogue is None else epilogue
    eps = pack_args(spec, scale=scale, shift=shift, alpha=alpha,
                    residual=residual)
    ep_kw = dict(zip(spec.slots, eps))
    if d == 1:
        return _dense_conv(x, w, stride=s, padding="SAME", th=th, tc=tc,
                           interpret=interpret, epilogue=epilogue, **ep_kw)
    if s != 1:
        y = _strided(x, w, d, s, th=th, tc=tc, interpret=interpret)
        return apply_reference(spec, y, eps)
    if not spec.empty or w.shape[0] % 2 == 0:
        # the fused-epilogue path composes through the dense kernel's
        # epilogue VJP; even kernels pad SAME asymmetrically — the symmetry
        # adjoint below assumes odd-k symmetric padding, so they too
        # differentiate compositionally through the dense kernel's VJP
        return _dilated_impl(x, w, d, th, tc, interpret, spec=spec, eps=eps)
    return _dilated_vjp(x, w, d, th, tc, interpret)


@jax.named_scope(obs.LAYOUT_PHASE_SPLIT)
def _phase_to_batch(x: jax.Array, d: int) -> jax.Array:
    """Pad H, W to multiples of ``d`` and stack phases on the batch axis."""
    n, h, w_in, c = x.shape
    hp, wp = math.ceil(h / d) * d, math.ceil(w_in / d) * d
    xpad = jnp.pad(x, ((0, 0), (0, hp - h), (0, wp - w_in), (0, 0)))
    xb = xpad.reshape(n, hp // d, d, wp // d, d, c)
    return xb.transpose(2, 4, 0, 1, 3, 5).reshape(d * d * n, hp // d,
                                                  wp // d, c)


def _dilated_impl(x: jax.Array, w: jax.Array, d: int, th: int, tc: int,
                  interpret: bool, spec: EpilogueSpec = _NO_EP,
                  eps: tuple = ()) -> jax.Array:
    n, h, w_in, cin = x.shape
    cout = w.shape[-1]
    hp, wp = math.ceil(h / d) * d, math.ceil(w_in / d) * d
    # phases -> batch: (N, H/d, d, W/d, d, C) -> (d*d*N, H/d, W/d, C)
    xb = _phase_to_batch(x, d)

    # per-channel epilogue ops commute with the phase relabeling; the
    # residual rides the identical transform so the add fuses in-kernel
    # (its zero pad-up rows land in the cropped region below)
    ep_kw = dict(zip(spec.slots, eps))
    if "residual" in ep_kw:
        ep_kw["residual"] = _phase_to_batch(ep_kw["residual"], d)
    yb = _dense_conv(xb, w, padding="SAME", th=th, tc=tc, interpret=interpret,
                     epilogue=spec if not spec.empty else None, **ep_kw)

    # batch -> phases, then interleave and crop the pad-up rows/cols
    with jax.named_scope(obs.LAYOUT_PHASE_STITCH):
        yb = yb.reshape(d, d, n, hp // d, wp // d, cout)
        y = yb.transpose(2, 3, 0, 4, 1, 5).reshape(n, hp, wp, cout)
    with jax.named_scope(obs.LAYOUT_CROP):
        return y[:, :h, :w_in, :]


# ---------------------------------------------------------------------------
# Custom VJP (DESIGN.md §6): the input-gradient of a SAME dilated conv IS the
# same dilated conv with the flipped kernel — the adjoint re-enters this
# engine; the weight-gradient gathers taps at step ``d`` (one phase block
# per tap) and contracts on the MXU.
# ---------------------------------------------------------------------------

def _dilated_plain(x, w, d, th, tc, interpret):
    # custom_vjp binds default kwargs as operands — keep the vjp'd function's
    # signature free of the epilogue extras
    return _dilated_impl(x, w, d, th, tc, interpret)


_dilated_vjp = jax.custom_vjp(_dilated_plain, nondiff_argnums=(2, 3, 4, 5))


def _dilated_fwd(x, w, d, th, tc, interpret):
    return _dilated_impl(x, w, d, th, tc, interpret), (x, w)


def _dilated_bwd(d, th, tc, interpret, res, g):
    from repro.core import adjoints

    x, w = res

    def dilated_fn(gg, wf, dd):
        return _dilated_impl(gg, wf, dd, th, tc, interpret)

    dx = adjoints.dilated_conv_dx(g, w, d, dilated_fn)
    dw = adjoints.dilated_conv_dw(x, g, w.shape[0], d)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_dilated_vjp.defvjp(_dilated_fwd, _dilated_bwd)


def _strided(x: jax.Array, w: jax.Array, d: int, s: int, *, th: int, tc: int,
             interpret: bool) -> jax.Array:
    """Class-batched strided-dilated path: q*q class windows, ONE strided conv.

    Shares the schedule/window/stitch implementation with the XLA path —
    only the dense conv engine differs.
    """
    from repro.core.dilated import _dilated_strided_decomposed

    def conv_fn(xb, wt, sb):
        return _dense_conv(xb, wt, stride=sb, padding="VALID", th=th, tc=tc,
                           interpret=interpret)

    return _dilated_strided_decomposed(x, w, d, s, "batched", conv_fn)
