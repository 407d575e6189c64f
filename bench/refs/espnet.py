"""Plain float32 reference of ESPNet, and the weights the benchmark gives it.

The reference imports nothing of the program.  Every convolution is one
``lax.conv_general_dilated`` (``rhs_dilation`` for the dilated branches, an
explicitly zero-inserted input for the transposed ones: the zero-laden
forms, no decomposition).  The architecture is ESPNet (Mehta et al.,
arXiv:1803.06815) as the authors' code builds it (``sacmehta/ESPNet``,
``train/Model.py``, class ``ESPNet``, p = alpha2, q = alpha3):

* ``CBR``: conv (no bias), BN, PReLU with a slope per channel; ``BR``: BN,
  PReLU; SAME padding ``(k - 1) // 2`` (times d for a dilated conv);
* the ESP module: a 1x1 reduce to ``n = cout // 5``, five 3x3 branches at
  d = 1, 2, 4, 8, 16 (d=1 to ``cout - 4n``, the others to ``n``), the sums
  ``d2``, ``+d4``, ``+d8``, ``+d16`` concatenated behind ``d1``, the input
  added (encoder modules), BR; a DownSamplerB reduces with a 3x3 stride-2
  conv and adds nothing;
* input reinforcement: ``AvgPool2d(3, 2, padding=1)`` with the padding
  counted (the sum over 9), once and twice;
* the decoder's upsamplers: ``ConvTranspose2d(C, C, 2, stride=2)``, here a
  zero-inserted input and a 2x2 convolution padded 1 on each side.

Departures, as the program's: batch norm is the learnable affine
``y * g + b`` (folded, no statistics).  ``precision`` is that of
:mod:`bench.refs.lax_conv`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.refs.lax_conv import conv, zero_insert

DILATIONS = (1, 2, 4, 8, 16)


def _widths(cout: int) -> tuple[int, int]:
    n = cout // 5
    return n, cout - 4 * n


# ----------------------------------------------------------------- weights --

def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5


def _br_params(key, c, gain=1.0):
    kg, kb, ka = jax.random.split(key, 3)
    return {"g": gain * (1.0 + 0.1 * jax.random.normal(kg, (c,), jnp.float32)),
            "b": 0.1 * jax.random.normal(kb, (c,), jnp.float32),
            "a": jax.random.uniform(ka, (c,), jnp.float32, 0.1, 0.4)}


def _esp_params(key, cin, cout, *, down, add):
    n, n1 = _widths(cout)
    ks = jax.random.split(key, 7)
    k = 3 if down else 1
    # the HFF sums and the residual grow the concat's second moment to
    # ~ (n1 + 10 n) / cout (+ 1 with the residual); the BN gain takes it
    # back to ~1, so that 8 residual modules keep unit scale and each
    # branch's share of the output stays as large as it started
    gain = ((n1 + 10 * n) / cout + (1.0 if add else 0.0)) ** -0.5
    p = {"reduce": _he(ks[0], (k, k, cin, n), k * k * cin),
         "br": _br_params(ks[1], cout, gain)}
    for kk, d in zip(ks[2:], DILATIONS):
        p[f"d{d}"] = _he(kk, (3, 3, n, n1 if d == 1 else n), 9 * n)
    return p


def make_params(cfg: dict, key) -> dict:
    """Every weight from ``key``, in float32, as one traceable function
    (the harness jits it, so the weights are made on the device)."""
    a2, a3, c = cfg["alpha2"], cfg["alpha3"], cfg["num_classes"]
    c_in = cfg["in_channels"]
    c1, c2 = 16 + c_in, 128 + c_in
    ks = iter(jax.random.split(key, 20 + a2 + a3))
    p = {"level1": _he(next(ks), (3, 3, c_in, 16), 9 * c_in),
         "level1_br": _br_params(next(ks), 16),
         "b1": _br_params(next(ks), c1),
         "l2_0": _esp_params(next(ks), c1, 64, down=True, add=False),
         "b2": _br_params(next(ks), c2),
         "l3_0": _esp_params(next(ks), c2, 128, down=True, add=False),
         "b3": _br_params(next(ks), 256)}
    for i in range(1, a2 + 1):
        p[f"l2_{i}"] = _esp_params(next(ks), 64, 64, down=False, add=True)
    for i in range(1, a3 + 1):
        p[f"l3_{i}"] = _esp_params(next(ks), 128, 128, down=False, add=True)
    bn3 = _br_params(next(ks), c)
    p["cls3"] = _he(next(ks), (1, 1, 256, c), 256)
    p["cls3_bn"] = {"g": bn3["g"], "b": bn3["b"]}
    # a 2x2 stride-2 transposed conv gives each output pixel one tap
    p["up3"] = _he(next(ks), (2, 2, c, c), c)
    p["cls2"] = _he(next(ks), (1, 1, c2, c), c2)
    p["comb_br"] = _br_params(next(ks), 2 * c)
    p["comb"] = _esp_params(next(ks), 2 * c, c, down=False, add=False)
    p["up2"] = _he(next(ks), (2, 2, c, c), c)
    p["up2_br"] = _br_params(next(ks), c)
    p["fuse"] = _he(next(ks), (3, 3, c1 + c, c), 9 * (c1 + c))
    p["fuse_br"] = _br_params(next(ks), c)
    p["up1"] = _he(next(ks), (2, 2, c, c), c)
    return p


# ----------------------------------------------------------------- forward --

def _bn(y, p):
    return y * p["g"] + p["b"]


def _br(y, p):
    y = _bn(y, p)
    return jnp.where(y >= 0, y, p["a"] * y)


def _pad(k, d=1):
    q = d * (k - 1) // 2
    return [(q, q), (q, q)]


def esp(p, x, *, down: bool, add: bool, precision: str = "highest"):
    """An ESP module (``down``: DownSamplerB)."""
    k = p["reduce"].shape[0]
    h = conv(x, p["reduce"], stride=2 if down else 1, pads=_pad(k),
             precision=precision)
    br = {d: conv(h, p[f"d{d}"], pads=_pad(3, d), rhs_dil=d,
                  precision=precision) for d in DILATIONS}
    add1 = br[2]
    add2 = add1 + br[4]
    add3 = add2 + br[8]
    add4 = add3 + br[16]
    y = jnp.concatenate([br[1], add1, add2, add3, add4], axis=-1)
    if add:
        y = y + x
    return _br(y, p["br"])


def avgpool3s2(x):
    """``AvgPool2d(3, stride=2, padding=1)``, ``count_include_pad=True``."""
    s = lax.reduce_window(x, 0.0, lax.add, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    return s / 9.0


def deconv2x2s2(x, w, precision: str = "highest"):
    """``ConvTranspose2d(C, C, 2, stride=2, padding=0)``: 2H x 2W."""
    return conv(zero_insert(x, 2), w, pads=[(1, 1), (1, 1)],
                precision=precision)


def forward(cfg: dict, params: dict, x, precision: str = "highest"):
    """x: (N, H, W, 3) float32 -> logits (N, H, W, classes)."""
    p = params
    kw = dict(precision=precision)
    level1 = _br(conv(x, p["level1"], stride=2, pads=_pad(3), **kw),
                 p["level1_br"])
    inp1 = avgpool3s2(x)
    inp2 = avgpool3s2(inp1)
    b1 = _br(jnp.concatenate([level1, inp1], -1), p["b1"])
    l2_0 = esp(p["l2_0"], b1, down=True, add=False, **kw)
    h = l2_0
    for i in range(1, cfg["alpha2"] + 1):
        h = esp(p[f"l2_{i}"], h, down=False, add=True, **kw)
    b2 = _br(jnp.concatenate([h, l2_0, inp2], -1), p["b2"])
    l3_0 = esp(p["l3_0"], b2, down=True, add=False, **kw)
    h = l3_0
    for i in range(1, cfg["alpha3"] + 1):
        h = esp(p[f"l3_{i}"], h, down=False, add=True, **kw)
    b3 = _br(jnp.concatenate([l3_0, h], -1), p["b3"])
    x3 = _bn(conv(b3, p["cls3"], pads=_pad(1), **kw), p["cls3_bn"])
    x3 = deconv2x2s2(x3, p["up3"], **kw)
    x2 = conv(b2, p["cls2"], pads=_pad(1), **kw)
    y = _br(jnp.concatenate([x2, x3], -1), p["comb_br"])
    y = esp(p["comb"], y, down=False, add=False, **kw)
    y = _br(deconv2x2s2(y, p["up2"], **kw), p["up2_br"])
    y = _br(conv(jnp.concatenate([y, b1], -1), p["fuse"], pads=_pad(3), **kw),
            p["fuse_br"])
    return deconv2x2s2(y, p["up1"], **kw)
