"""Plain float32 reference of the DCGAN generator the repository serves,
and the weights the benchmark gives it.

The generator of pytorch/examples ``dcgan/main.py`` (Radford et al.,
arXiv:1511.06434) at ``nz`` latents and ``ngf`` base width: a projection of
the latent to 4x4 x (8 ngf) (PyTorch writes it as a 4x4 transposed
convolution of a 1x1 input, which is the same linear map), then k=4, s=2
transposed convolutions (PyTorch ``padding=1``: here a stride-2 zero insertion with
2 rows of padding on each side of the zero-inserted input) that halve the channels, each followed by
batch norm and ReLU, and a tanh head to 3 channels.  Batch norm is the
affine ``y * g + b`` (evaluation mode with its statistics folded in).  The
request's latent is ``normal(PRNGKey(seed), (nz,))``, the serving API's
contract.  Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.refs.lax_conv import conv, dot, zero_insert


def latent(seed, nz: int):
    return jax.random.normal(jax.random.PRNGKey(seed), (nz,), jnp.float32)


@functools.partial(jax.jit, static_argnums=1)
def latents(seeds, nz: int):
    """``latent`` of each of ``seeds`` (int32), in one call."""
    return jax.vmap(lambda s: latent(s, nz))(seeds)


def _widths(cfg: dict) -> list[int]:
    n_up = {64: 4, 128: 5}[cfg["image_size"]]
    c = cfg["ngf"] * cfg["image_size"] // 8
    return [c // 2 ** i for i in range(n_up)]


def make_params(cfg: dict, key) -> dict:
    """Every weight from ``key``, in float32 (the harness jits this)."""
    widths = _widths(cfg)
    nz, nc = cfg["nz"], cfg["nc"]
    ks = jax.random.split(key, 2 * len(widths) + 1)
    bn = lambda k, c: {
        "g": 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(k, 0), (c,)),
        "b": 0.1 * jax.random.normal(jax.random.fold_in(k, 1), (c,))}
    he = lambda k, shape, fan_in: (jax.random.normal(k, shape, jnp.float32)
                                   * (2.0 / fan_in) ** 0.5)
    p = {"proj": he(ks[0], (nz, 16 * widths[0]), nz),
         "proj_bn": bn(ks[1], widths[0])}
    for i in range(1, len(widths)):
        cin, cout = widths[i - 1], widths[i]
        p[f"up{i}"] = he(ks[2 * i], (4, 4, cin, cout), 4 * cin)
        p[f"bn{i}"] = bn(ks[2 * i + 1], cout)
    p["head"] = he(ks[-1], (4, 4, widths[-1], nc), 4 * widths[-1])
    return p


def forward(cfg: dict, params: dict, z, precision: str = "highest"):
    """z: (N, nz) -> images (N, size, size, nc) in (-1, 1)."""
    relu_bn = lambda y, bn: jnp.maximum(y * bn["g"] + bn["b"], 0.0)
    h = dot(z, params["proj"], precision)
    h = relu_bn(h.reshape(z.shape[0], 4, 4, -1), params["proj_bn"])
    up = lambda x, w: conv(zero_insert(x, 2), w, pads=[(2, 2), (2, 2)],
                           precision=precision)
    i = 1
    while f"up{i}" in params:
        h = relu_bn(up(h, params[f"up{i}"]), params[f"bn{i}"])
        i += 1
    return jnp.tanh(up(h, params["head"]))
