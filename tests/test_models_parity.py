"""Full-model backend parity: ENet and ESPNet forward/backward agree across
``backend='xla'``, ``backend='pallas'`` and the naive (``decomposed=False``)
baseline within fp32 tolerance.

Tiny inputs keep the pallas-interpret paths fast enough for tier-1; the
model-level pallas *gradient* parity (the expensive double pass) is marked
``slow`` — the kernel-level gradients are pinned in ``test_gradients.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.models import enet, espnet

_HW = 16   # divisible by 8: both nets downsample 3x and upsample back
# five ESPNet classes: the decoder's ESP splits them over its five branches
_CLASSES = {"enet": 4, "espnet": 5}


@pytest.fixture(scope="module")
def enet_setup():
    params = enet.init_params(jax.random.PRNGKey(0),
                              num_classes=_CLASSES["enet"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, _HW, _HW, 3))
    return params, x


@pytest.fixture(scope="module")
def espnet_setup():
    params = espnet.init_params(jax.random.PRNGKey(2),
                                num_classes=_CLASSES["espnet"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, _HW, _HW, 3))
    return params, x


def _forwards(model, params, x):
    y_dec = model.forward(params, x)                        # xla, decomposed
    y_naive = model.forward(params, x, decomposed=False)    # zero-laden
    y_pal = model.forward(params, x, backend="pallas")      # fused kernels
    return y_dec, y_naive, y_pal


@pytest.mark.parametrize("which", ["enet", "espnet"])
def test_forward_three_way_parity(which, enet_setup, espnet_setup):
    model, (params, x) = ((enet, enet_setup) if which == "enet"
                          else (espnet, espnet_setup))
    y_dec, y_naive, y_pal = _forwards(model, params, x)
    assert y_dec.shape == (1, _HW, _HW, _CLASSES[which])
    # batch norm over a tiny batch amplifies fp32 accumulation-order noise
    # through the depth of the net (per-op exactness is pinned at 1e-5 in
    # test_kernels/test_gradients) — bound the *relative* error so a real
    # decomposition/schedule bug (O(1) mismatch) still fails loudly
    assert_allclose(np.asarray(y_dec), np.asarray(y_naive),
                    rtol=1e-3, atol=1e-3)
    d, p = np.asarray(y_dec), np.asarray(y_pal)
    rel = np.linalg.norm(p - d) / np.linalg.norm(d)
    assert rel < 5e-3, rel
    assert np.abs(p - d).max() < 0.05 * np.abs(d).max()


def _loss(model, params, x, backend):
    logits = model.forward(params, x, backend=backend)
    lab = jnp.zeros(logits.shape[:3], jnp.int32)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(lp, lab[..., None], -1))


@pytest.mark.parametrize("which", ["enet", "espnet"])
def test_grad_runs_xla(which, enet_setup, espnet_setup):
    """jax.grad of a scalar loss through the whole net (xla backend)."""
    model, (params, x) = ((enet, enet_setup) if which == "enet"
                          else (espnet, espnet_setup))
    loss, grads = jax.value_and_grad(
        lambda p: _loss(model, p, x, "xla"))(params)
    assert np.isfinite(float(loss))
    norms = jax.tree_util.tree_map(lambda g: float(jnp.linalg.norm(g)), grads)
    flat = jax.tree_util.tree_leaves(norms)
    assert all(np.isfinite(n) for n in flat)
    assert any(n > 0 for n in flat)


def test_grad_runs_pallas_espnet(espnet_setup):
    """jax.grad through the full ESPNet on the pallas backend (custom VJPs
    of all three fused kernels fire: dense, dilated incl. strided, tconv)."""
    params, x = espnet_setup
    lx, gx = jax.value_and_grad(lambda p: _loss(espnet, p, x, "xla"))(params)
    lp, gp = jax.value_and_grad(lambda p: _loss(espnet, p, x, "pallas"))(params)
    assert float(lx) == pytest.approx(float(lp), rel=1e-4)
    # per-leaf gradient parity (batch-norm over tiny batches amplifies fp32
    # noise through the depth of the net — tolerance is loose but bounded)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gx)):
        assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-2, atol=5e-3)


@pytest.mark.slow
def test_grad_runs_pallas_enet(enet_setup):
    """jax.grad through the full ENet on the pallas backend."""
    params, x = enet_setup
    lx, _ = jax.value_and_grad(lambda p: _loss(enet, p, x, "xla"))(params)
    lp, gp = jax.value_and_grad(lambda p: _loss(enet, p, x, "pallas"))(params)
    assert float(lx) == pytest.approx(float(lp), rel=1e-4)
    flat = [float(jnp.linalg.norm(g)) for g in jax.tree_util.tree_leaves(gp)]
    assert all(np.isfinite(n) for n in flat) and any(n > 0 for n in flat)
