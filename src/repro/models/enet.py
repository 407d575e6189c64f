"""ENet segmentation network in JAX, built on the paper's decomposition.

Every dilated convolution runs through ``core.dilated`` (input decomposition)
and every transposed convolution through ``core.transposed`` (weight
decomposition) — the technique is the execution engine, not a demo.  Layer
inventory matches ``core.enet_spec`` (the cycle-model workload table).

Every BN/PReLU/residual that used to follow a convolution as separate
elementwise HBM passes is emitted as a *fused epilogue spec* instead
(DESIGN.md §7): BN is carried in folded ``scale``/``shift`` form
(``common.fold_bn``), PReLU and the bottleneck residual add ride the same
kernel output pass.  The 5x1/1x5 asymmetric pair runs through the engine's
rectangular-kernel dense path (no more silent lax fallback under
``backend='pallas'``).

This is the paper's own workload: ``examples/train_enet.py`` trains it end to
end on synthetic Cityscapes-like data.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.decompose import conv2d
from repro.kernels.epilogue import EpilogueSpec
from repro.models.common import bn_init as _bn_init
from repro.models.common import conv_init, fold_bn

# the two epilogue shapes ENet uses: BN+PReLU after reduce/mid convs, and
# BN + residual-add + PReLU closing every bottleneck
_EP_BN_ACT = EpilogueSpec(bn=True, prelu=True)
_EP_BN_RES_ACT = EpilogueSpec(bn=True, prelu=True, residual="pre_act")


def _conv_init(key, k: int, cin: int, cout: int, dtype=jnp.float32):
    return conv_init(key, k, k, cin, cout, dtype)


def _bottleneck_init(key, c: int, kind: str = "regular", cin: int | None = None,
                     asym: int = 5, dtype=jnp.float32) -> dict:
    cin = c if cin is None else cin
    ci = max(c // 4, 1)
    ks = jax.random.split(key, 6)
    p = {"a1": jnp.full((1,), 0.25, dtype), "a2": jnp.full((1,), 0.25, dtype),
         "a3": jnp.full((1,), 0.25, dtype),
         "bn1": _bn_init(ci, dtype), "bn2": _bn_init(ci, dtype),
         "bn3": _bn_init(c, dtype)}
    # folded BN does not re-normalise per batch, so the residual cascade
    # would double activation variance per bottleneck; zero-init the closing
    # scale (ResNet "zero-init residual") so each block starts as identity
    p["bn3"]["g"] = jnp.zeros((c,), dtype)
    if kind == "down":
        p["reduce"] = _conv_init(ks[0], 2, cin, ci, dtype)
        p["conv"] = _conv_init(ks[1], 3, ci, ci, dtype)
    elif kind == "up":
        p["reduce"] = _conv_init(ks[0], 1, cin, ci, dtype)
        p["deconv"] = _conv_init(ks[1], 3, ci, ci, dtype)
        p["skip"] = _conv_init(ks[3], 1, cin, c, dtype)
    elif kind == "asym":
        p["reduce"] = _conv_init(ks[0], 1, cin, ci, dtype)
        p["conv_v"] = (jax.random.normal(ks[1], (asym, 1, ci, ci), jnp.float32)
                       * (2.0 / (asym * ci)) ** 0.5).astype(dtype)
        p["conv_h"] = (jax.random.normal(ks[4], (1, asym, ci, ci), jnp.float32)
                       * (2.0 / (asym * ci)) ** 0.5).astype(dtype)
    else:  # regular / dilated
        p["reduce"] = _conv_init(ks[0], 1, cin, ci, dtype)
        p["conv"] = _conv_init(ks[1], 3, ci, ci, dtype)
    p["expand"] = _conv_init(ks[2], 1, ci, c, dtype)
    return p


def _bottleneck(p: dict, x: jax.Array, kind: str, c: int, dilation: int = 1,
                decomposed: bool = True, strategy: str = "batched",
                backend: str = "xla", interpret: bool | None = None,
                compute_dtype=None) -> jax.Array:
    """kind: regular | dilated | asym | down | up."""
    kw = dict(backend=backend, interpret=interpret, compute_dtype=compute_dtype)
    s1, b1 = fold_bn(p["bn1"])
    ep1 = dict(epilogue=_EP_BN_ACT, scale=s1, shift=b1, alpha=p["a1"])
    if kind == "down":
        h = conv2d(x, p["reduce"], stride=2, padding=0, **kw, **ep1)
        skip = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                     (1, 2, 2, 1), "VALID")
        pad_c = c - x.shape[-1]
        skip = jnp.pad(skip, ((0, 0), (0, 0), (0, 0), (0, pad_c)))
    elif kind == "up":
        h = conv2d(x, p["reduce"], **kw, **ep1)
        skip = conv2d(x, p["skip"], **kw)
        # nearest-neighbour unpool stand-in for max-unpool indices
        skip = jnp.repeat(jnp.repeat(skip, 2, axis=1), 2, axis=2)
    else:
        h = conv2d(x, p["reduce"], **kw, **ep1)
        skip = x

    s2, b2 = fold_bn(p["bn2"])
    ep2 = dict(epilogue=_EP_BN_ACT, scale=s2, shift=b2, alpha=p["a2"])
    if kind == "asym":
        # 5x1/1x5 pair: rectangular kernels through the engine's dense path
        # (SAME pads one dim only); BN2/PReLU fuse into the second conv
        h = conv2d(h, p["conv_v"], **kw)
        h = conv2d(h, p["conv_h"], **kw, **ep2)
    elif kind == "up":
        h = conv2d(h, p["deconv"], stride=2, transposed=True,
                   output_padding=1, decomposed=decomposed, **kw, **ep2)
    elif kind == "dilated":
        h = conv2d(h, p["conv"], dilation=dilation, decomposed=decomposed,
                   strategy=strategy, **kw, **ep2)
    else:
        h = conv2d(h, p["conv"], **kw, **ep2)

    # expand projection closes the bottleneck: BN3, +skip, PReLU — one pass
    s3, b3 = fold_bn(p["bn3"])
    return conv2d(h, p["expand"], epilogue=_EP_BN_RES_ACT, scale=s3,
                  shift=b3, alpha=p["a3"], residual=skip, **kw)


# stage layout: (name, kind, channels, dilation)
_STAGE2 = [("reg", 1), ("dil", 2), ("asym", 1), ("dil", 4),
           ("reg", 1), ("dil", 8), ("asym", 1), ("dil", 16)]


def init_params(key, num_classes: int = 19, dtype=jnp.float32) -> dict:
    ks = iter(jax.random.split(key, 64))
    p = {"initial": _conv_init(next(ks), 3, 3, 13, dtype)}
    p["b1_0"] = _bottleneck_init(next(ks), 64, "down", cin=16, dtype=dtype)
    for i in range(1, 5):
        p[f"b1_{i}"] = _bottleneck_init(next(ks), 64, dtype=dtype)
    p["b2_0"] = _bottleneck_init(next(ks), 128, "down", cin=64, dtype=dtype)
    for stage in (2, 3):
        for i, (kind, _) in enumerate(_STAGE2, start=1):
            p[f"b{stage}_{i}"] = _bottleneck_init(
                next(ks), 128, "asym" if kind == "asym" else "regular",
                dtype=dtype)
    p["b4_0"] = _bottleneck_init(next(ks), 64, "up", cin=128, dtype=dtype)
    for i in range(1, 3):
        p[f"b4_{i}"] = _bottleneck_init(next(ks), 64, dtype=dtype)
    p["b5_0"] = _bottleneck_init(next(ks), 16, "up", cin=64, dtype=dtype)
    p["b5_1"] = _bottleneck_init(next(ks), 16, dtype=dtype)
    p["fullconv"] = _conv_init(next(ks), 3, 16, num_classes, dtype)
    return p


@functools.partial(jax.jit,
                   static_argnames=("decomposed", "strategy", "backend",
                                    "interpret", "compute_dtype"))
def forward(params: dict, x: jax.Array, decomposed: bool = True,
            strategy: str = "batched", backend: str = "xla",
            interpret: bool | None = None,
            compute_dtype: str | None = None) -> jax.Array:
    """x: (N, H, W, 3) -> logits (N, H, W, classes).

    ``backend='pallas'`` executes every conv through the fused Pallas engine
    (:mod:`repro.kernels`) instead of composed XLA convs — including the 1x1
    reduce/expand projections, the stem/head, and the rectangular 5x1/1x5
    asymmetric pair — so a pallas forward is all-pallas, with BN/PReLU/
    residual epilogues fused into the kernels (DESIGN.md §7).  The whole
    forward is differentiable on both backends (DESIGN.md §6).
    ``interpret`` is the Pallas interpret-mode override (``None``: interpret
    on CPU only; ``False`` insists on compiled kernels).

    ``compute_dtype`` (e.g. ``"bf16"``; static — pass the string form) casts
    the input once and every conv per-layer, so activations flow in the
    compute dtype end to end while params stay fp32 masters and the kernels
    accumulate in fp32 (DESIGN.md §12); the logits come back in it.
    """
    cd = compute_dtype
    if cd is not None:
        from repro.kernels.util import canon_dtype

        x = x.astype(canon_dtype(cd))
    kw = dict(backend=backend, interpret=interpret, compute_dtype=cd)
    bkw = dict(decomposed=decomposed, **kw)
    h = conv2d(x, params["initial"], stride=2, **kw)
    pool = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")
    h = jnp.concatenate([h, pool], axis=-1)          # (N, H/2, W/2, 16)

    h = _bottleneck(params["b1_0"], h, "down", 64, **bkw)
    for i in range(1, 5):
        h = _bottleneck(params[f"b1_{i}"], h, "regular", 64, **bkw)
    h = _bottleneck(params["b2_0"], h, "down", 128, **bkw)
    for stage in (2, 3):
        for i, (kind, d) in enumerate(_STAGE2, start=1):
            k = {"reg": "regular", "dil": "dilated", "asym": "asym"}[kind]
            h = _bottleneck(params[f"b{stage}_{i}"], h, k, 128, dilation=d,
                            strategy=strategy, **bkw)
    h = _bottleneck(params["b4_0"], h, "up", 64, **bkw)
    for i in range(1, 3):
        h = _bottleneck(params[f"b4_{i}"], h, "regular", 64, **bkw)
    h = _bottleneck(params["b5_0"], h, "up", 16, **bkw)
    h = _bottleneck(params["b5_1"], h, "regular", 16, **bkw)
    return conv2d(h, params["fullconv"], stride=2, transposed=True,
                  output_padding=1, decomposed=decomposed, **kw)
