"""The references' one convolution and one matmul, at a stated precision.

``"highest"`` multiplies in float32.  ``"high"`` is three bfloat16 passes,
the precision at which the ``correct`` comparison's control runs.  On a TPU
that is XLA's own ``Precision.HIGH`` (forward and gradient products alike).
Elsewhere it is written out, since the CPU ignores the flag: each operand is
split into a bfloat16 head and tail (held in float32, whose products of
bfloat16 values are exact) and ``hi*hi + hi*lo + lo*hi`` is accumulated in
float32, in the forward product and in both products of its gradient.
(On a TPU the written-out form is no control: XLA folds it back into one
bfloat16 pass, 1.06e-2 off on an ENet-512 frame where ``Precision.HIGH`` is
8.2e-5 off, on a TPU v5e.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

DIMS = ("NHWC", "HWIO", "NHWC")
HIGHEST = lax.Precision.HIGHEST


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _three(op, a, b):
    (ah, al), (bh, bl) = _split(a), _split(b)
    return op(ah, bh) + op(ah, bl) + op(al, bh)


def _three_pass(op):
    """The bilinear ``op`` (at float32) as three bfloat16 passes, forward
    and backward."""

    @jax.custom_vjp
    def f(a, b):
        return _three(op, a, b)

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        da = lambda gg, bb: jax.vjp(lambda u: op(u, bb), a)[1](gg)[0]
        db = lambda gg, aa: jax.vjp(lambda v: op(aa, v), b)[1](gg)[0]
        return _three(da, g, b), _three(db, g, a)

    f.defvjp(fwd, bwd)
    return f


def _at(op, precision: str):
    """``op(prec)`` is the bilinear op at an XLA precision."""
    if precision == "highest":
        return op(HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown reference precision {precision!r}")
    if jax.default_backend() == "tpu":
        return op(lax.Precision.HIGH)
    return _three_pass(op(HIGHEST))


def conv(x, w, *, stride=1, pads, rhs_dil=1, precision="highest"):
    def op(prec):
        return lambda a, b: lax.conv_general_dilated(
            a, b, window_strides=(stride, stride), padding=pads,
            rhs_dilation=(rhs_dil, rhs_dil), dimension_numbers=DIMS,
            precision=prec)

    return _at(op, precision)(x, w)


def dot(a, b, precision="highest"):
    op = lambda prec: lambda u, v: jnp.dot(u, v, precision=prec)
    return _at(op, precision)(a, b)


def zero_insert(x, s: int):
    """``s - 1`` zero rows and columns between the pixels of ``x`` (NHWC):
    the input of a stride-``s`` transposed convolution written out.  (XLA's
    ``lhs_dilation`` form gives the same forward, but on a TPU its kernel
    gradient came out about 2% off the float32 one at ``HIGHEST``, where
    this form and three program paths agree, on a TPU v5e.)"""
    n, h, w, c = x.shape
    up = jnp.zeros((n, s * (h - 1) + 1, s * (w - 1) + 1, c), x.dtype)
    return up.at[:, ::s, ::s].set(x)


def same_pads(kh: int, kw: int):
    """SAME padding as the program pads it: the extra row goes below."""
    return [((kh - 1) // 2, kh // 2), ((kw - 1) // 2, kw // 2)]
