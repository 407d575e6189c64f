"""ESPNet (``repro.models.espnet``) against the benchmark's plain reference
(``bench/refs/espnet.py``, loaded by path; it imports nothing of the
program), on both backends, at 32x64 with 5 classes on the CPU.  There the
level-3 maps are 4x8 and a d=16 branch reads only its centre tap, so one
level-3 module also runs on a 40x72 map, where its taps at +-16 land in
bounds.

Each case compares the program's module with the reference's on the
reference's seeded weights (``make_params``, the benchmark's recipe) by the
widest gap over the widest reference value.  ``TOL`` is 2e-5: float32
products in another order, through up to 30 layers, read under 1e-6 here
(Pallas in interpret mode and XLA alike), while a wrong width split, HFF
order, residual or padding moves a result by 1e-2 or more, and dropping one
d=16 branch of one level-3 module moves the logits by ~6e-2 (keeping only
its centre tap moves the 40x72 module's output by ~0.45).
"""

import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decompose import conv2d
from repro.models import espnet

ROOT = pathlib.Path(__file__).resolve().parents[1]
TOL = 2e-5
H, W, CLASSES = 32, 64, 5
CFG = {"alpha2": 2, "alpha3": 8, "num_classes": CLASSES, "in_channels": 3}


def _load_reference():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    spec = importlib.util.spec_from_file_location(
        "bench_refs_espnet", ROOT / "bench" / "refs" / "espnet.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: ref.make_params(CFG, k))(jax.random.PRNGKey(7))


def _gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _x(shape, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def test_esp_widths_split_cout_five_ways():
    """n = cout // 5 for the reduce and the d>1 branches; the d=1 branch
    takes the rest (the published 12/16, 25/28 and, at 20 classes, 4/4)."""
    assert espnet.esp_widths(64) == (12, 16)
    assert espnet.esp_widths(128) == (25, 28)
    assert espnet.esp_widths(20) == (4, 4)
    assert espnet.esp_widths(5) == (1, 1)
    with pytest.raises(ValueError):
        espnet.esp_widths(4)


#: (module, input shape, down, add): an encoder ESP (residual), a level-3
#: ESP on a 40x72 map (there the d=16 taps at +-16 land in bounds), a
#: DownSamplerB (3x3 stride-2 reduce, no residual) and the decoder's ESP
#: (2C -> C, no residual)
_MODULES = {
    "esp": ("l2_1", (1, 16, 32, 64), False, True),
    "esp-level3": ("l3_1", (1, 40, 72, 128), False, True),
    "downsampler": ("l3_0", (1, 16, 32, 131), True, False),
    "decoder": ("comb", (1, 8, 16, 2 * CLASSES), False, False),
}


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("which", list(_MODULES))
def test_esp_module_matches_reference(params, which, backend):
    name, shape, down, add = _MODULES[which]
    p, x = params[name], _x(shape)
    got = jax.jit(lambda p, x: espnet._esp(p, x, down=down, add=add,
                                          backend=backend))(p, x)
    want = ref.esp(p, x, down=down, add=add)
    assert _gap(got, want) <= TOL
    # the residual is there where the module adds it, and only there
    n, n1 = espnet.esp_widths(p["br"]["g"].shape[0])
    assert got.shape[-1] == n1 + 4 * n
    if add:
        assert _gap(got, ref.esp(p, x, down=down, add=False)) > 100 * TOL


def test_esp_module_hff_order(params):
    """The concat is [d1, d2, d2+d4, d2+d4+d8, d2+d4+d8+d16]: with every
    branch but d16 zeroed, only the last n channels differ from the BR of
    zero."""
    p = dict(params["l2_1"])
    for d in (1, 2, 4, 8):
        p[f"d{d}"] = jnp.zeros_like(p[f"d{d}"])
    x = _x((1, 16, 32, 64))
    y = espnet._esp(p, x, add=False)
    base = espnet._esp(p | {"d16": jnp.zeros_like(p["d16"])}, x, add=False)
    n, _ = espnet.esp_widths(64)
    moved = np.any(np.asarray(y != base), axis=(0, 1, 2))
    assert not moved[:-n].any() and moved[-n:].all()


def test_input_reinforcement_divides_by_nine(params):
    """``AvgPool2d(3, 2, padding=1)`` counts the zero border: the corner
    output is the sum of its four in-bounds pixels over 9."""
    x = _x((1, H, W, 3))
    got = espnet._avgpool3s2(x)
    assert got.shape == (1, H // 2, W // 2, 3)
    assert _gap(got, ref.avgpool3s2(x)) <= TOL
    corner = np.asarray(x)[0, :2, :2].sum(axis=(0, 1)) / 9
    np.testing.assert_allclose(np.asarray(got)[0, 0, 0], corner, rtol=1e-6)
    twice = espnet._avgpool3s2(got)
    assert _gap(twice, ref.avgpool3s2(ref.avgpool3s2(x))) <= TOL


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_decoder_deconv_is_exact_2x(params, backend):
    """The decoder's ``ConvTranspose2d(C, C, 2, stride=2)``: the engine's
    k=2, s=2 parity schedule (padding 1, output padding 0) doubles each
    side, as the zero-inserted reference does."""
    x = _x((1, 8, 16, CLASSES))
    got = conv2d(x, params["up1"], stride=2, transposed=True, padding=1,
                 output_padding=0, backend=backend)
    assert got.shape == (1, 16, 32, CLASSES)
    assert _gap(got, ref.deconv2x2s2(x, params["up1"])) <= TOL


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_forward_matches_reference(params, backend):
    x = _x((1, H, W, 3), seed=2)
    got = espnet.forward(params, x, backend=backend)
    assert got.shape == (1, H, W, CLASSES)
    assert _gap(got, ref.forward(CFG, params, x)) <= TOL


def test_dropping_a_d16_branch_moves_the_logits_past_tolerance(params):
    """The comparison sees the widest branch: zeroing the d=16 branch of
    one level-3 module moves the logits well past ``TOL``."""
    x = _x((1, H, W, 3), seed=3)
    want = ref.forward(CFG, params, x)
    module = params["l3_4"] | {"d16": jnp.zeros_like(params["l3_4"]["d16"])}
    got = espnet.forward(params | {"l3_4": module}, x)
    assert _gap(got, want) > 100 * TOL


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_d16_off_centre_taps_move_a_level3_module_past_tolerance(
        params, backend):
    """At 32x64 the level-3 maps are 4x8 and a d=16 branch reads only its
    centre tap.  On a 40x72 level-3 map its taps at +-16 land in bounds:
    keeping only the centre tap of one module's d=16 branch moves the
    module's output well past ``TOL``."""
    p, x = params["l3_1"], _x((1, 40, 72, 128), seed=4)
    w = p["d16"]
    centre = jnp.zeros_like(w).at[1, 1].set(w[1, 1])
    want = ref.esp(p, x, down=False, add=True)
    got = jax.jit(lambda p, x: espnet._esp(p, x, add=True,
                                          backend=backend))(
        p | {"d16": centre}, x)
    assert _gap(got, want) > 100 * TOL
