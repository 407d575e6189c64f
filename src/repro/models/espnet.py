"""ESPNet segmentation network built on the paper's decomposition.

ESPNet (Mehta et al., "ESPNet: Efficient Spatial Pyramid of Dilated
Convolutions for Semantic Segmentation", ECCV 2018, arXiv:1803.06815) as the
authors' code builds it (``github.com/sacmehta/ESPNet``, ``train/Model.py``,
class ``ESPNet``), with ``p = alpha2 = 2`` and ``q = alpha3 = 8``.  Its ESP
module is a spatial pyramid of dilated convolutions: a reduce to ``n``
channels, then ``K = 5`` parallel 3x3 branches at dilations 1, 2, 4, 8, 16
(the d=1 branch to ``n1 = cout - 4n`` channels, the others to
``n = cout // 5``), fused hierarchically (HFF: ``d2``, ``d2+d4``,
``d2+d4+d8``, ``d2+d4+d8+d16``), concatenated behind ``d1``, added to the
module's input (in the encoder's stride-1 modules), and closed by BN and a
per-channel PReLU.  Every dilated branch runs through the input decomposition
(:mod:`repro.core.dilated`) and the decoder's three 2x2 stride-2 upsamplers
through the weight decomposition (:mod:`repro.core.transposed`).

Network (``C`` classes, ``BR`` = BN then PReLU)::

    level1   = CBR3x3s2(x, 3 -> 16)                        H/2
    inp1     = avgpool3s2(x); inp2 = avgpool3s2(inp1)      input reinforcement
    b1       = BR(cat[level1, inp1])                       19
    l2_0     = DownSamplerB(b1, 19 -> 64)                  H/4
    l2       = alpha2 x ESP(64 -> 64)
    b2       = BR(cat[l2, l2_0, inp2])                     131
    l3_0     = DownSamplerB(b2, 131 -> 128)                H/8
    l3       = alpha3 x ESP(128 -> 128)
    b3       = BR(cat[l3_0, l3])                           256
    x3       = deconv2x2s2(BN(conv1x1(b3, 256 -> C)))      H/4
    y        = ESP(BR(cat[conv1x1(b2, 131 -> C), x3]), 2C -> C), no residual
    y        = BR(deconv2x2s2(y))                          H/2
    y        = CBR3x3(cat[y, b1], 19 + C -> C)
    logits   = deconv2x2s2(y)                              H

A DownSamplerB reduces with a 3x3 stride-2 convolution and runs stride-1
branches on the reduced map, with no residual.  Every convolution goes
through :func:`repro.core.decompose.conv2d`; BN/PReLU that follows a single
convolution rides its fused epilogue (DESIGN.md §7).  What follows an HFF
merge or a concat runs as the folded-BN oracle in one elementwise pass,
under the ``esp.merge`` (each ESP module's sums, concat, residual and BR)
and ``esp.reinforce`` (the input pools and the concats that take them)
scopes of :mod:`repro.obs`.

Departures from the authors' code: BN is carried folded, as the affine
``y * g + b`` (evaluation form; a trained checkpoint folds its statistics
and eps 1e-3 into ``g`` and ``b``); the decoder's ESP splits ``C`` five
ways, so ``C >= 5``.

The forward is differentiable on both backends (DESIGN.md §6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.decompose import conv2d
from repro.kernels.epilogue import EpilogueSpec, apply_reference
from repro.models.common import bn_init, conv_init, tconv_init

ESP_DILATIONS = (1, 2, 4, 8, 16)   # K = 5 pyramid branches (d = 2**k)

_EP_BN = EpilogueSpec(bn=True)
_EP_BN_ACT = EpilogueSpec(bn=True, prelu=True)


def esp_widths(cout: int) -> tuple[int, int]:
    """``(n, n1)``: the width of the reduce and of the d>1 branches, and of
    the d=1 branch, so that the five branches concatenate to ``cout``."""
    n = cout // len(ESP_DILATIONS)
    if n == 0:
        raise ValueError(f"an ESP module needs at least "
                         f"{len(ESP_DILATIONS)} output channels, got {cout}")
    return n, cout - (len(ESP_DILATIONS) - 1) * n


def _br_init(c: int, dtype=jnp.float32, gain: float = 1.0) -> dict:
    """BN (folded affine) and a per-channel PReLU slope."""
    p = bn_init(c, dtype)
    p["g"] = p["g"] * gain
    p["a"] = jnp.full((c,), 0.25, dtype)
    return p


def _br_args(p: dict) -> dict:
    return dict(scale=p["g"], shift=p["b"], alpha=p["a"])


def _br(p: dict, y: jax.Array) -> jax.Array:
    """BN + PReLU after a merge: the folded-BN oracle in one pass."""
    return apply_reference(_EP_BN_ACT, y, (p["g"], p["b"], p["a"]))


def _esp_init(key, cin: int, cout: int, *, down: bool = False,
              add: bool = True, dtype=jnp.float32) -> dict:
    """Parameters of an ESP module (``down``: a DownSamplerB; ``add``: with
    the residual)."""
    n, n1 = esp_widths(cout)
    ks = jax.random.split(key, len(ESP_DILATIONS) + 1)
    k = 3 if down else 1
    # folded BN does not re-normalise per batch: the HFF sums and the
    # residual grow the concat's variance ~ (n1 + 10 n)/cout + 1, so the
    # closing gain starts at the inverse root and the stack stays at unit
    # activation scale
    var = (n1 + 10 * n) / cout + int(add)
    p = {"reduce": conv_init(ks[0], k, k, cin, n, dtype),
         "br": _br_init(cout, dtype, gain=var ** -0.5)}
    for kk, d in zip(ks[1:], ESP_DILATIONS):
        p[f"d{d}"] = conv_init(kk, 3, 3, n, n1 if d == 1 else n, dtype)
    return p


def _esp(p: dict, x: jax.Array, *, down: bool = False, add: bool = True,
         decomposed: bool = True, strategy: str = "batched",
         **kw) -> jax.Array:
    """ESP module: reduce -> K parallel dilated branches -> HFF -> concat
    (-> + input where ``add``) -> BR.  ``down``: a 3x3 stride-2 reduce
    (DownSamplerB); otherwise a 1x1 reduce."""
    h = conv2d(x, p["reduce"], stride=2 if down else 1, **kw)
    outs = [conv2d(h, p[f"d{d}"], dilation=d, decomposed=decomposed,
                   strategy=strategy, **kw) for d in ESP_DILATIONS]
    with jax.named_scope(obs.ESP_MERGE):
        acc, fused = outs[1], [outs[0], outs[1]]
        for o in outs[2:]:      # HFF: cumulative sums de-grid the pyramid
            acc = acc + o
            fused.append(acc)
        y = jnp.concatenate(fused, axis=-1)
        if add:
            y = y + x
        return _br(p["br"], y)


def _avgpool3s2(x: jax.Array) -> jax.Array:
    """``AvgPool2d(3, stride=2, padding=1)`` with ``count_include_pad``:
    the window sum over 9 everywhere, the zero border included."""
    s = jax.lax.reduce_window(x, jnp.zeros((), x.dtype), jax.lax.add,
                              (1, 3, 3, 1), (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
    return s / 9


def init_params(key, num_classes: int = 20, alpha2: int = 2, alpha3: int = 8,
                dtype=jnp.float32) -> dict:
    c = num_classes
    ks = iter(jax.random.split(key, 12 + alpha2 + alpha3))
    c1, c2 = 16 + 3, 128 + 3        # the image (3 channels) reinforces both
    p = {"level1": conv_init(next(ks), 3, 3, 3, 16, dtype),
         "level1_br": _br_init(16, dtype), "b1": _br_init(c1, dtype),
         "l2_0": _esp_init(next(ks), c1, 64, down=True, add=False,
                           dtype=dtype),
         "b2": _br_init(c2, dtype),
         "l3_0": _esp_init(next(ks), c2, 128, down=True, add=False,
                           dtype=dtype),
         "b3": _br_init(256, dtype)}
    for i in range(alpha2):
        p[f"l2_{i + 1}"] = _esp_init(next(ks), 64, 64, dtype=dtype)
    for i in range(alpha3):
        p[f"l3_{i + 1}"] = _esp_init(next(ks), 128, 128, dtype=dtype)
    p["cls3"] = conv_init(next(ks), 1, 1, 256, c, dtype)
    p["cls3_bn"] = bn_init(c, dtype)
    p["up3"] = tconv_init(next(ks), 2, 2, c, c, dtype=dtype)
    p["cls2"] = conv_init(next(ks), 1, 1, c2, c, dtype)
    p["comb_br"] = _br_init(2 * c, dtype)
    p["comb"] = _esp_init(next(ks), 2 * c, c, add=False, dtype=dtype)
    p["up2"] = tconv_init(next(ks), 2, 2, c, c, dtype=dtype)
    p["up2_br"] = _br_init(c, dtype)
    p["fuse"] = conv_init(next(ks), 3, 3, c1 + c, c, dtype)
    p["fuse_br"] = _br_init(c, dtype)
    p["up1"] = tconv_init(next(ks), 2, 2, c, c, dtype=dtype)
    return p


@functools.partial(jax.jit,
                   static_argnames=("decomposed", "strategy", "backend",
                                    "interpret", "alpha2", "alpha3",
                                    "compute_dtype"))
def forward(params: dict, x: jax.Array, decomposed: bool = True,
            strategy: str = "batched", backend: str = "xla",
            interpret: bool | None = None, alpha2: int = 2, alpha3: int = 8,
            compute_dtype: str | None = None) -> jax.Array:
    """x: (N, H, W, 3) -> logits (N, H, W, classes).  H, W divisible by 8.

    ``interpret`` is the Pallas interpret-mode override (``None``: interpret
    on CPU only; ``False`` insists on compiled kernels).
    ``compute_dtype`` (static, e.g. ``"bf16"``): activations flow in the
    compute dtype through every conv while params stay fp32 masters
    (DESIGN.md §12).
    """
    cd = compute_dtype
    if cd is not None:
        from repro.kernels.util import canon_dtype

        x = x.astype(canon_dtype(cd))
    kw = dict(backend=backend, interpret=interpret, compute_dtype=cd)
    ekw = dict(decomposed=decomposed, strategy=strategy, **kw)
    tkw = dict(stride=2, transposed=True, padding=1, output_padding=0,
               decomposed=decomposed, **kw)
    level1 = conv2d(x, params["level1"], stride=2, epilogue=_EP_BN_ACT,
                    **_br_args(params["level1_br"]), **kw)       # H/2, 16
    with jax.named_scope(obs.ESP_REINFORCE):
        inp1 = _avgpool3s2(x)
        inp2 = _avgpool3s2(inp1)
        b1 = _br(params["b1"], jnp.concatenate([level1, inp1], -1))

    l2_0 = _esp(params["l2_0"], b1, down=True, add=False, **ekw)  # H/4, 64
    h = l2_0
    for i in range(alpha2):
        h = _esp(params[f"l2_{i + 1}"], h, **ekw)
    with jax.named_scope(obs.ESP_REINFORCE):
        b2 = _br(params["b2"], jnp.concatenate([h, l2_0, inp2], -1))

    l3_0 = _esp(params["l3_0"], b2, down=True, add=False, **ekw)  # H/8, 128
    h = l3_0
    for i in range(alpha3):
        h = _esp(params[f"l3_{i + 1}"], h, **ekw)
    b3 = _br(params["b3"], jnp.concatenate([l3_0, h], -1))       # 256

    bn3 = params["cls3_bn"]
    x3 = conv2d(b3, params["cls3"], epilogue=_EP_BN, scale=bn3["g"],
                shift=bn3["b"], **kw)                            # H/8, C
    x3 = conv2d(x3, params["up3"], **tkw)                        # H/4
    x2 = conv2d(b2, params["cls2"], **kw)
    y = _br(params["comb_br"], jnp.concatenate([x2, x3], -1))
    y = _esp(params["comb"], y, add=False, **ekw)
    y = conv2d(y, params["up2"], epilogue=_EP_BN_ACT,
               **_br_args(params["up2_br"]), **tkw)              # H/2
    y = conv2d(jnp.concatenate([y, b1], -1), params["fuse"],
               epilogue=_EP_BN_ACT, **_br_args(params["fuse_br"]), **kw)
    return conv2d(y, params["up1"], **tkw)                       # H
