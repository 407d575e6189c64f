"""Plain float32 reference of the ENet the repository serves and trains,
and the weights the benchmark gives it.

The reference imports nothing of the program.  Every convolution is one
``lax.conv_general_dilated`` (``rhs_dilation`` for the dilated layers, an
explicitly zero-inserted input for the transposed ones: the zero-laden
forms, no decomposition).  The architecture is ENet (Paszke et al., arXiv:1606.02147)
as the repository builds it, departures included:

* batch norm is the learnable affine ``y * g + b`` (folded, no statistics);
* a downsampling bottleneck's skip is a 2x2 max-pool and a zero channel pad;
* an upsampling bottleneck's skip is a 1x1 projection repeated 2x2
  (nearest neighbour in place of max-unpooling);
* the closing ``expand`` convolution adds the skip before its PReLU;
* the head is a 3x3 stride-2 transposed convolution (``output_padding`` 1).

``precision`` is that of :mod:`bench.refs.lax_conv`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.refs.lax_conv import conv, same_pads, zero_insert

#: (kind, dilation) of the bottlenecks of stages 2 and 3
STAGE2 = (("regular", 1), ("dilated", 2), ("asym", 1), ("dilated", 4),
          ("regular", 1), ("dilated", 8), ("asym", 1), ("dilated", 16))


def _prelu(y, a):
    return jnp.where(y >= 0, y, a * y)


def _maxpool2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1),
                             "VALID")


# ----------------------------------------------------------------- weights --

def _stages(cfg: dict):
    """(name, kind, cin, c, dilation) of every bottleneck, in order."""
    c0, c1, c2, c3, c4 = cfg["stage_channels"]
    out = [("b1_0", "down", c0, c1, 1)]
    out += [(f"b1_{i}", "regular", c1, c1, 1) for i in range(1, 5)]
    out.append(("b2_0", "down", c1, c2, 1))
    for stage in (2, 3):
        out += [(f"b{stage}_{i}", kind, c2, c2, d)
                for i, (kind, d) in enumerate(STAGE2, start=1)]
    out.append(("b4_0", "up", c2, c3, 1))
    out += [(f"b4_{i}", "regular", c3, c3, 1) for i in range(1, 3)]
    out.append(("b5_0", "up", c3, c4, 1))
    out.append(("b5_1", "regular", c4, c4, 1))
    return out


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5


def _bn(key, c, g_mean, g_std):
    kg, kb = jax.random.split(key)
    return {"g": g_mean + g_std * jax.random.normal(kg, (c,), jnp.float32),
            "b": 0.1 * jax.random.normal(kb, (c,), jnp.float32)}


def _bottleneck_params(key, kind, cin, c, asym):
    ci = c // 4
    ks = jax.random.split(key, 10)
    alpha = lambda k: jax.random.uniform(k, (1,), jnp.float32, 0.1, 0.4)
    # the closing scale is small and centred on 0, so each block adds a
    # little to its skip and ENet's 27 residual steps keep unit scale
    p = {"a1": alpha(ks[0]), "a2": alpha(ks[1]), "a3": alpha(ks[2]),
         "bn1": _bn(ks[3], ci, 1.0, 0.1), "bn2": _bn(ks[4], ci, 1.0, 0.1),
         "bn3": _bn(ks[5], c, 0.0, 0.2)}
    if kind == "down":
        p["reduce"] = _he(ks[6], (2, 2, cin, ci), 4 * cin)
        p["conv"] = _he(ks[7], (3, 3, ci, ci), 9 * ci)
    elif kind == "up":
        p["reduce"] = _he(ks[6], (1, 1, cin, ci), cin)
        p["deconv"] = _he(ks[7], (3, 3, ci, ci), 9 * ci // 4)
        p["skip"] = _he(ks[9], (1, 1, cin, c), cin)
    elif kind == "asym":
        p["reduce"] = _he(ks[6], (1, 1, cin, ci), cin)
        p["conv_v"] = _he(ks[7], (asym, 1, ci, ci), asym * ci)
        p["conv_h"] = _he(ks[9], (1, asym, ci, ci), asym * ci)
    else:
        p["reduce"] = _he(ks[6], (1, 1, cin, ci), cin)
        p["conv"] = _he(ks[7], (3, 3, ci, ci), 9 * ci)
    p["expand"] = _he(ks[8], (1, 1, ci, c), ci)
    return p


def make_params(cfg: dict, key) -> dict:
    """Every weight from ``key``, in float32, as one traceable function
    (the harness jits it, so the weights are made on the device)."""
    stages = _stages(cfg)
    ks = jax.random.split(key, len(stages) + 2)
    c_in, c0 = cfg["in_channels"], cfg["stage_channels"][0]
    p = {"initial": _he(ks[0], (3, 3, c_in, c0 - c_in), 9 * c_in)}
    for k, (name, kind, cin, c, _) in zip(ks[1:], stages):
        p[name] = _bottleneck_params(k, kind, cin, c, cfg["asym_kernel"])
    c4, classes = cfg["stage_channels"][-1], cfg["num_classes"]
    p["fullconv"] = _he(ks[-1], (3, 3, c4, classes), 9 * c4 // 4)
    return p


# ----------------------------------------------------------------- forward --

def _bottleneck(p, x, kind, c, d, precision):
    cv = functools.partial(conv, precision=precision)

    def ep(y, bn, a, res=None):
        y = y * bn["g"] + bn["b"]
        return _prelu(y if res is None else y + res, a)

    if kind == "down":
        h = ep(cv(x, p["reduce"], stride=2, pads=[(0, 0), (0, 0)]),
               p["bn1"], p["a1"])
        skip = _maxpool2(x)
        skip = jnp.pad(skip, ((0, 0), (0, 0), (0, 0), (0, c - x.shape[-1])))
    elif kind == "up":
        h = ep(cv(x, p["reduce"], pads=same_pads(1, 1)), p["bn1"], p["a1"])
        skip = cv(x, p["skip"], pads=same_pads(1, 1))
        skip = jnp.repeat(jnp.repeat(skip, 2, axis=1), 2, axis=2)
    else:
        h = ep(cv(x, p["reduce"], pads=same_pads(1, 1)), p["bn1"], p["a1"])
        skip = x
    if kind == "asym":
        kv = p["conv_v"].shape[0]
        h = cv(h, p["conv_v"], pads=same_pads(kv, 1))
        h = cv(h, p["conv_h"], pads=same_pads(1, kv))
    elif kind == "up":
        h = cv(zero_insert(h, 2), p["deconv"], pads=[(1, 2), (1, 2)])
    elif kind == "dilated":
        h = cv(h, p["conv"], pads=[(d, d), (d, d)], rhs_dil=d)
    else:
        h = cv(h, p["conv"], pads=same_pads(3, 3))
    h = ep(h, p["bn2"], p["a2"])
    return ep(cv(h, p["expand"], pads=same_pads(1, 1)), p["bn3"], p["a3"],
              res=skip)


def forward(cfg: dict, params: dict, x, precision: str = "highest"):
    """x: (N, H, W, 3) float32 -> logits (N, H, W, classes)."""
    h = conv(x, params["initial"], stride=2, pads=same_pads(3, 3),
             precision=precision)
    h = jnp.concatenate([h, _maxpool2(x)], axis=-1)
    for name, kind, _, c, d in _stages(cfg):
        h = _bottleneck(params[name], h, kind, c, d, precision)
    return conv(zero_insert(h, 2), params["fullconv"], pads=[(1, 2), (1, 2)],
                precision=precision)


# ---------------------------------------------------------------- training --

def loss(cfg: dict, params: dict, image, label, precision: str = "highest"):
    """Mean per-pixel negative log-likelihood of ``label``."""
    logits = forward(cfg, params, image, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, label[..., None], axis=-1))


def grads(cfg: dict, params: dict, image, label, *, rows: int,
          precision: str = "highest"):
    """(loss, gradient) of the batch mean, taken ``rows`` rows at a time so
    that the reference fits beside nothing else on the device."""
    n = image.shape[0]
    if n % rows:
        raise ValueError(f"batch {n} is not a multiple of {rows} rows")
    vg = _value_and_grad(cfg_key(cfg), precision)
    total_l, total_g = 0.0, None
    for i in range(0, n, rows):
        l, g = vg(params, image[i:i + rows], label[i:i + rows])
        total_l = total_l + l
        total_g = g if total_g is None else jax.tree.map(jnp.add, total_g, g)
    k = n // rows
    return total_l / k, jax.tree.map(lambda a: a / k, total_g)


def cfg_key(cfg: dict) -> tuple:
    """The hashable part of ``cfg`` that shapes the network."""
    return (tuple(cfg["stage_channels"]), cfg["in_channels"],
            cfg["num_classes"], cfg["asym_kernel"])


@functools.lru_cache(maxsize=4)
def _value_and_grad(key: tuple, precision: str):
    cfg = {"stage_channels": list(key[0]), "in_channels": key[1],
           "num_classes": key[2], "asym_kernel": key[3]}
    return jax.jit(jax.value_and_grad(
        lambda p, x, y: loss(cfg, p, x, y, precision)))


def adamw(params, grads_, state, opt: dict):
    """One AdamW step with global-norm clipping, as the configuration
    states the optimizer.  ``state`` is ``(step, mu, nu)``; returns
    ``(params', state', clipped gradient)``."""
    step, mu, nu = state
    step = step + 1
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads_)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    b1, b2 = opt["b1"], opt["b2"]
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    g = jax.tree.map(lambda a: a * scale, grads_)
    mu = jax.tree.map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
    nu = jax.tree.map(lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)
    new = jax.tree.map(
        lambda w, m, v: w - opt["lr"] * ((m / c1) / (jnp.sqrt(v / c2)
                                                     + opt["eps"])
                                         + opt["weight_decay"] * w),
        params, mu, nu)
    return new, (step, mu, nu), g


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return (0, zeros, jax.tree.map(jnp.zeros_like, params))
