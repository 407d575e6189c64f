"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: each case lowers an ENet-512 or ESPNet-1024x512 layer
through the engine entry point (``repro.core.decompose.conv2d``,
``backend="pallas"``, ``interpret=False``) with shapes placed on one
device of a described ``v5e:2x2`` topology, and compiles it with the TPU
compiler that ships with JAX.  That is where Mosaic refuses what interpret
mode accepts: strided value slices, unaligned reshapes, blocks that
overflow scoped VMEM.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports every
test file.  These tests stay in this one file so that they share it.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.decompose import conv2d
from repro.kernels.epilogue import EpilogueSpec

_BN_ACT = EpilogueSpec(bn=True, prelu=True)

#: (id, x shape, w shape, conv2d kwargs, epilogue) — ENet at 512x512
_CASES = [
    ("stem-3x3s2-cin3", (1, 512, 512, 3), (3, 3, 3, 13), dict(stride=2),
     None),
    ("b1.0-reduce-2x2s2", (1, 256, 256, 16), (2, 2, 16, 16),
     dict(stride=2, padding=0), _BN_ACT),
    ("b2.8-dilated-d16", (1, 64, 64, 32), (3, 3, 32, 32),
     dict(dilation=16), _BN_ACT),
    ("b2.3-asym-5x1", (1, 64, 64, 32), (5, 1, 32, 32), {}, None),
    ("b2.3-asym-1x5", (1, 64, 64, 32), (1, 5, 32, 32), {}, _BN_ACT),
    ("fullconv-tconv-16to19", (1, 256, 256, 16), (3, 3, 16, 19),
     dict(stride=2, transposed=True, output_padding=1), None),
    # ESPNet at 1024x512 (20 classes): odd widths, non-square maps, the
    # d=16 branches' 4x8 and 8x16 phase planes, the k = s = 2 upsamplers
    ("espnet-l2.0-reduce-3x3s2-19to12", (1, 256, 512, 19), (3, 3, 19, 12),
     dict(stride=2), None),
    ("espnet-l2-d16-12ch", (1, 128, 256, 12), (3, 3, 12, 12),
     dict(dilation=16), None),
    ("espnet-l3-d16-25ch", (1, 64, 128, 25), (3, 3, 25, 25),
     dict(dilation=16), None),
    ("espnet-l3.0-reduce-3x3s2-131to25", (1, 128, 256, 131),
     (3, 3, 131, 25), dict(stride=2), None),
    ("espnet-up1-tconv-2x2s2", (1, 256, 512, 20), (2, 2, 20, 20),
     dict(stride=2, transposed=True, padding=1, output_padding=0), None),
    ("espnet-fuse-3x3-39to20", (1, 256, 512, 39), (3, 3, 39, 20), {},
     _BN_ACT),
]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layer(x_shape, w_shape, kw, epilogue):
    """The layer as ENet calls it (default tiles, compiled kernels)."""
    cout = w_shape[3]

    def f(x, w):
        ep = {}
        if epilogue is not None:
            ep = dict(epilogue=epilogue, scale=jnp.ones((cout,)),
                      shift=jnp.zeros((cout,)), alpha=jnp.full((1,), 0.25))
        return conv2d(x, w, backend="pallas", interpret=False, th=8, tc=128,
                      **kw, **ep)
    return f


@pytest.mark.parametrize("x_shape,w_shape,kw,epilogue",
                         [c[1:] for c in _CASES], ids=[c[0] for c in _CASES])
def test_forward_compiles_for_v5e(one_chip, x_shape, w_shape, kw, epilogue):
    f = _layer(x_shape, w_shape, kw, epilogue)
    compiled = jax.jit(f).lower(_spec(x_shape, one_chip),
                                _spec(w_shape, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the Pallas kernel


def test_custom_vjp_compiles_for_v5e(one_chip):
    """The backward of the d=16 dilated layer: the input-gradient re-enters
    the dilated engine, the weight-gradient is a tap-gather correlation."""
    x_shape, w_shape, kw, _ = _CASES[2][1:]
    f = _layer(x_shape, w_shape, kw, None)
    grad = jax.grad(lambda x, w: jnp.sum(f(x, w) ** 2), argnums=(0, 1))
    compiled = jax.jit(grad).lower(_spec(x_shape, one_chip),
                                   _spec(w_shape, one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
