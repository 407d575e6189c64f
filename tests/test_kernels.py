"""Per-Pallas-kernel validation: shape/dtype sweeps vs pure-jnp oracles.

All kernels run in interpret mode (CPU executes the kernel body; BlockSpec
tiling and grid semantics are fully exercised).
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore
from numpy.testing import assert_allclose

from repro.kernels import conv2d as dense, ops, ref
from repro.kernels.epilogue import EpilogueSpec, apply_reference, pack_args


def _pair(key, xshape, wshape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    return (jax.random.normal(k1, xshape, dtype),
            jax.random.normal(k2, wshape, dtype))


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- conv2d ---

CONV_CASES = [
    # (n, h, w, cin, cout, k, stride, padding)
    (2, 16, 16, 8, 16, 3, 1, "SAME"),
    (1, 17, 13, 3, 5, 3, 1, "SAME"),
    (1, 16, 16, 4, 8, 2, 2, "VALID"),
    (2, 32, 32, 8, 13, 3, 2, "SAME"),
    (1, 8, 8, 16, 32, 1, 1, "SAME"),
    (1, 12, 20, 3, 7, 5, 1, "SAME"),
]


@pytest.mark.parametrize("case", CONV_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_kernel(case, dtype):
    n, h, w, cin, cout, k, s, pad = case
    x, wt = _pair(n * h + w, (n, h, w, cin), (k, k, cin, cout), dtype)
    got = ops.conv2d(x, wt, stride=s, padding=pad)
    want = ref.conv2d_ref(x, wt, stride=s, padding=pad)
    assert got.shape == want.shape
    assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                    **_tol(dtype))


@pytest.mark.parametrize("ks", [(5, 1), (1, 5), (2, 3), (4, 1)])
@pytest.mark.parametrize("stride", [1, 2])
def test_conv2d_kernel_rectangular(ks, stride):
    """Rectangular kernels (ENet's 5x1/1x5 asymmetric pair) are first-class:
    per-dim SAME pads, per-dim tap loops, per-dim halo."""
    kh, kw = ks
    x, wt = _pair(kh * 7 + kw, (1, 14, 11, 3), (kh, kw, 3, 5), jnp.float32)
    got = ops.conv2d(x, wt, stride=stride, padding="SAME")
    want = ref.conv2d_ref(x, wt, stride=stride, padding="SAME")
    assert got.shape == want.shape
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ------------------------------------- strided dense conv: the two forms ---

def _kernels(fn, *args) -> collections.Counter:
    """The Pallas kernels in ``fn``'s jaxpr, by the name of their body; a
    dense conv's phase-plane body is ``planes`` where it holds more than
    one plane (a strided conv) and ``unit`` at stride 1."""
    found = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                body = eqn.params["jaxpr"]
                name = body.debug_info.func_name
                if name == "_conv_kernel":
                    name = "planes" if body.invars[0].aval.shape[1] > 1 \
                        else "unit"
                found[name] += 1
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(sub, jcore.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jcore.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


#: (id, x shape, (kh, kw, cin, cout), stride, ((ph_lo, ph_hi), (pw_lo, pw_hi)))
STRIDED_CASES = [
    ("enet-stem-odd", (1, 17, 15, 3), (3, 3, 3, 13), 2, ((1, 1), (1, 1))),
    ("enet-stem-even", (1, 16, 16, 3), (3, 3, 3, 13), 2, ((1, 1), (1, 1))),
    ("espnet-level1-wide", (1, 16, 40, 3), (3, 3, 3, 16), 2,
     ((1, 1), (1, 1))),
    ("reduce-2x2s2-cin16", (1, 16, 16, 16), (2, 2, 16, 16), 2,
     ((0, 0), (0, 0))),
    ("reduce-3x3s2-cin19", (1, 16, 24, 19), (3, 3, 19, 12), 2,
     ((1, 1), (1, 1))),
    ("rect-5x1s2", (1, 14, 11, 3), (5, 1, 3, 5), 2, ((2, 2), (0, 0))),
]

_BN_PRELU_RES = EpilogueSpec(bn=True, prelu=True, residual="post_act")


@pytest.mark.parametrize("epilogue", [False, True], ids=["plain", "bn-prelu-res"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("xs,ws,s,pads", [c[1:] for c in STRIDED_CASES],
                         ids=[c[0] for c in STRIDED_CASES])
def test_strided_loads_equal_phase_planes(xs, ws, s, pads, dtype, epilogue):
    """The strided-load form gives the phase-plane form's bits, and both
    match the oracle.  Mosaic takes strided loads of 32-bit data only, so a
    bf16 conv keeps the phase planes in either form."""
    x, w = _pair(sum(xs) + sum(ws), xs, ws, dtype)
    cout = ws[3]
    spec, eps = dense._NO_EP, ()
    if epilogue:
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(cout), 3)
        y_ref = ref.conv2d_ref(x, w, stride=s, padding=list(pads))
        spec = _BN_PRELU_RES
        eps = pack_args(spec, scale=1 + 0.1 * jax.random.normal(k1, (cout,)),
                        shift=jax.random.normal(k2, (cout,)),
                        alpha=jnp.full((1,), 0.25),
                        residual=jax.random.normal(k3, y_ref.shape, dtype))

    def form(strided_loads):
        return lambda x, w: dense._conv2d_raw(
            x, w, eps, spec, s, pads, 4, 128, True,
            strided_loads=strided_loads)

    strided, planes = form(True), form(False)
    want_body = "_conv_kernel_strided" if dtype == jnp.float32 else "planes"
    assert _kernels(strided, x, w) == {want_body: 1}
    assert _kernels(planes, x, w) == {"planes": 1}
    got = strided(x, w)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(planes(x, w), np.float32))
    want = apply_reference(spec, ref.conv2d_ref(x, w, stride=s,
                                                padding=list(pads)), eps)
    assert got.shape == want.shape
    assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                    **_tol(dtype))


def test_forward_takes_strided_loads_and_training_keeps_phase_planes():
    """Forward-only use lowers the strided-load kernel; under ``jax.grad``
    the fwd rules and the backward rules lower only the phase planes, so a
    training step keeps the form it has been measured with."""
    from repro.models import enet

    x, w = _pair(3, (1, 16, 16, 3), (3, 3, 3, 13), jnp.float32)
    conv = lambda x, w: ops.conv2d(x, w, stride=2, padding="SAME")
    loss = lambda x, w: jnp.sum(conv(x, w) ** 2)
    assert _kernels(jax.jit(conv), x, w) == {"_conv_kernel_strided": 1}
    grad = _kernels(jax.jit(jax.grad(loss, argnums=(0, 1))), x, w)
    assert grad["planes"] == 1 and "_conv_kernel_strided" not in grad

    params = enet.init_params(jax.random.PRNGKey(0), num_classes=5)
    img = jnp.zeros((1, 32, 32, 3), jnp.float32)
    fwd = _kernels(lambda p, x: enet.forward(p, x, backend="pallas"),
                   params, img)
    # the stem and the two downsampling bottlenecks' 2x2 reduce
    assert fwd["_conv_kernel_strided"] == 3 and "planes" not in fwd
    enet_loss = lambda p, x: jnp.sum(enet.forward(p, x, backend="pallas"))
    grad = _kernels(jax.grad(enet_loss), params, img)
    assert grad["planes"] >= 3 and "_conv_kernel_strided" not in grad

    # the transposed conv's input-gradient is a strided dense conv
    xt, wt = _pair(4, (1, 8, 8, 6), (3, 3, 6, 9), jnp.float32)
    tloss = lambda x, w: jnp.sum(ops.transposed_conv2d(x, w, stride=2) ** 2)
    grad = _kernels(jax.grad(tloss, argnums=(0, 1)), xt, wt)
    assert grad["planes"] == 1 and grad["_tconv_kernel"] == 1
    assert "_conv_kernel_strided" not in grad


# --------------------------------------------------------- dilated conv ---

@pytest.mark.parametrize("dilation", [1, 2, 3, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dilated_kernel(dilation, dtype):
    x, wt = _pair(dilation, (1, 24, 20, 6), (3, 3, 6, 10), dtype)
    got = ops.dilated_conv2d(x, wt, dilation)
    want = ref.dilated_conv2d_ref(x, wt, dilation)
    assert got.shape == want.shape == (1, 24, 20, 10)
    assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                    **_tol(dtype))


def test_dilated_kernel_enet_shapes():
    """The actual ENet translation-stage shapes (64x64, 32ch, D=1,3,7,15)."""
    for D in [1, 3, 7, 15]:
        x, wt = _pair(D, (1, 64, 64, 8), (3, 3, 8, 8), jnp.float32)
        got = ops.dilated_conv2d(x, wt, D + 1)
        want = ref.dilated_conv2d_ref(x, wt, D + 1)
        assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


# ------------------------------------------------------ transposed conv ---

@pytest.mark.parametrize("hw", [(4, 4), (8, 8), (13, 7), (16, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_transposed_kernel(hw, dtype):
    h, w = hw
    x, wt = _pair(h * w, (2, h, w, 6), (3, 3, 6, 9), dtype)
    got = ops.transposed_conv2d(x, wt, stride=2)
    want = ref.transposed_conv2d_ref(x, wt, stride=2, padding=1,
                                     output_padding=1)
    assert got.shape == want.shape == (2, 2 * h, 2 * w, 9)
    assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                    **_tol(dtype))


def test_transposed_kernel_matches_core_decomposition():
    """Pallas fused path == composable jnp decomposition == oracle."""
    from repro.core.transposed import transposed_conv2d_decomposed

    x, wt = _pair(0, (1, 8, 8, 4), (3, 3, 4, 4), jnp.float32)
    a = ops.transposed_conv2d(x, wt, stride=2)
    b = transposed_conv2d_decomposed(x, wt, 2, 1, 1)
    assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k,s", [(2, 2), (4, 2), (5, 3), (3, 4)])
def test_transposed_kernel_general_ks(k, s):
    """The fused kernel serves any (k, s) via the programmatic schedule."""
    x, wt = _pair(k * s, (1, 6, 9, 4), (k, k, 4, 6), jnp.float32)
    got = ops.transposed_conv2d(x, wt, stride=s)
    want = ref.transposed_conv2d_ref(x, wt, stride=s, padding=(k - 1) // 2,
                                     output_padding=1)
    assert got.shape == want.shape
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d,s", [(2, 2), (3, 2), (4, 2)])
def test_dilated_kernel_strided(d, s):
    """Phase-batched Pallas path with an output stride (class schedule)."""
    from repro.core.dilated import dilated_conv2d_reference

    x, wt = _pair(d * 7 + s, (1, 18, 14, 4), (3, 3, 4, 6), jnp.float32)
    got = ops.dilated_conv2d(x, wt, d, stride=s)
    want = dilated_conv2d_reference(x, wt, d, s)
    assert got.shape == want.shape
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- matmul ---

@pytest.mark.parametrize("mnk", [(16, 16, 16), (128, 128, 128),
                                 (100, 60, 36), (256, 512, 128), (1, 128, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel(mnk, dtype):
    m, n, k = mnk
    a, b = _pair(m + n + k, (m, k), (k, n), dtype)
    got = ops.matmul(a, b)
    want = ref.matmul_ref(a, b)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-4, atol=1e-4)
    assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                    **tol)


# ------------------------------------------------------- flash attention ---

@pytest.mark.parametrize("shape", [(1, 2, 128, 64), (2, 4, 100, 32),
                                   (1, 1, 257, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(shape, causal):
    b, h, s, d = shape
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(k1, shape, jnp.float32)
    k = jax.random.normal(k2, shape, jnp.float32)
    v = jax.random.normal(k3, shape, jnp.float32)
    got = ops.attention(q, k, v, causal=causal)
    want = ref.attention_ref(q, k, v, causal=causal)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    shape = (1, 2, 64, 64)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, shape, jnp.bfloat16)
    k = jax.random.normal(k2, shape, jnp.bfloat16)
    v = jax.random.normal(k3, shape, jnp.bfloat16)
    got = ops.attention(q, k, v)
    want = ref.attention_ref(q, k, v)
    assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                    rtol=3e-2, atol=3e-2)
