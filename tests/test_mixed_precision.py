"""Mixed-precision (bf16) contract tests (DESIGN.md §12).

Four claims under test:

* **engine parity** — every decomposition engine (dense / dilated / tconv)
  run with ``compute_dtype="bf16"`` returns bf16 outputs within the
  documented tolerance of the fp32 run (forward: 5% of the output range;
  gradients: 10% relative L2), on both backends, and the two backends
  agree with each other *in* bf16.
* **loss scaling** — the dynamic scaler backs off and skips on non-finite
  gradients, grows after the interval, clamps at its bounds, and a skipped
  recipe step leaves params + optimizer state bit-identical.
* **tiling policy** — the analytic score is dtype- and epilogue-aware,
  over-budget candidates never win, and the policy's timed set always
  contains ``DEFAULT_TILES`` — so a tune() under the policy can never do
  worse than the baseline tiling, and agrees with the exhaustive sweep
  whenever the sweep's winner is in the policy set.
* **dtype plumbing** — ``compute_dtype`` aliases resolve in one place
  (``canon_dtype``), model forwards and the DDIM gen step return bf16 for
  bf16 compute, and the generative server serves a bf16 lane end to end.
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decompose import conv2d
from repro.kernels import autotune as at
from repro.kernels import tiling_policy as tp
from repro.kernels.epilogue import EpilogueSpec
from repro.kernels.util import canon_dtype
from repro.launch import train_recipes
from repro.launch.steps import make_gen_step
from repro.models import dcgan, enet, espnet, unet_decoder
from repro.optim import DynamicLossScale, select_tree

# the benchmarks package lives at the repo root (pytest's pythonpath only
# covers src/); one module-level insert serves the policy-vs-sweep test
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

#: documented bf16-vs-fp32 tolerances (DESIGN.md §12): forward outputs
#: within 5% of the fp32 output range, gradients within 10% relative L2
FWD_RTOL = 0.05
GRAD_RTOL = 0.10

#: (kind, conv2d kwargs) for the three decomposition engines
ENGINES = (
    ("dense", dict()),
    ("dilated", dict(dilation=2)),
    ("tconv", dict(transposed=True, stride=2)),
)


def _xw(cin=4, cout=8, hw=10, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (1, hw, hw, cin), jnp.float32)
    w = jax.random.normal(k2, (3, 3, cin, cout), jnp.float32) * 0.3
    return x, w


def _assert_fwd_close(out16, ref32, rtol=FWD_RTOL):
    assert out16.dtype == jnp.bfloat16
    diff = jnp.max(jnp.abs(out16.astype(jnp.float32) - ref32))
    scale = jnp.max(jnp.abs(ref32))
    assert bool(jnp.isfinite(out16.astype(jnp.float32)).all())
    assert float(diff) <= rtol * float(scale) + 1e-3, \
        f"bf16 drifted {float(diff):.4f} vs range {float(scale):.4f}"


# ------------------------------------------------------------ engines ------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("kind,kw", ENGINES, ids=[k for k, _ in ENGINES])
def test_engine_bf16_forward_parity(kind, kw, backend):
    """bf16 in -> bf16 out, within tolerance of fp32, on both backends."""
    x, w = _xw()
    ref = conv2d(x, w, backend=backend, **kw)
    out = conv2d(x, w, backend=backend, compute_dtype="bf16", **kw)
    assert ref.dtype == jnp.float32          # fp32 path untouched
    _assert_fwd_close(out, ref)


@pytest.mark.parametrize("kind,kw", ENGINES, ids=[k for k, _ in ENGINES])
def test_engine_bf16_grad_parity(kind, kw):
    """Gradients through the bf16 pallas engines track the fp32 gradients
    (fp32 accumulators keep the backward pass from compounding rounding)."""
    x, w = _xw()

    def loss(w_, cd):
        out = conv2d(x, w_, backend="pallas", compute_dtype=cd, **kw)
        return jnp.mean(jnp.square(out.astype(jnp.float32)))

    g32 = jax.grad(lambda w_: loss(w_, None))(w)
    g16 = jax.grad(lambda w_: loss(w_, "bf16"))(w)
    assert g16.dtype == jnp.float32          # grads land on the fp32 master
    assert bool(jnp.isfinite(g16).all())
    rel = jnp.linalg.norm(g16 - g32) / (jnp.linalg.norm(g32) + 1e-9)
    assert float(rel) <= GRAD_RTOL, f"grad drift {float(rel):.4f}"


@pytest.mark.parametrize("kind,kw", ENGINES, ids=[k for k, _ in ENGINES])
def test_engine_bf16_cross_backend_parity(kind, kw):
    """pallas-bf16 and xla-bf16 agree — same decomposition, fp32 accum."""
    x, w = _xw(seed=1)
    a = conv2d(x, w, backend="pallas", compute_dtype="bf16", **kw)
    b = conv2d(x, w, backend="xla", compute_dtype="bf16", **kw)
    assert a.dtype == b.dtype == jnp.bfloat16
    diff = jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
    scale = jnp.max(jnp.abs(b.astype(jnp.float32)))
    assert float(diff) <= 0.02 * float(scale) + 1e-3


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("kind,kw", (
    ("dense", dict(stride=2)),
    ("dilated", dict(dilation=3)),
    ("tconv", dict(transposed=True, stride=2, output_padding=1)),
    ("tconv", dict(transposed=True, stride=3)),
), ids=["dense-s2", "dilated-d3", "tconv-s2op1", "tconv-s3"])
def test_engine_bf16_parity_full_grid(kind, kw, backend):
    """Wider geometry grid for the same parity claim (slow lane)."""
    x, w = _xw(cin=8, cout=16, hw=24, seed=2)
    ref = conv2d(x, w, backend=backend, **kw)
    out = conv2d(x, w, backend=backend, compute_dtype="bf16", **kw)
    _assert_fwd_close(out, ref)


# ------------------------------------------------------- dtype plumbing ----

def test_canon_dtype_aliases():
    assert canon_dtype(None) is None
    assert canon_dtype("bf16") == jnp.bfloat16
    assert canon_dtype("bfloat16") == jnp.bfloat16
    assert canon_dtype("fp32") == jnp.float32
    assert canon_dtype(jnp.bfloat16) == jnp.bfloat16
    with pytest.raises(ValueError):
        canon_dtype("int7")


def test_model_forwards_return_bf16():
    """compute_dtype="bf16" pins the output dtype of every workload model
    while the fp32 master params are left untouched."""
    key = jax.random.PRNGKey(0)
    img = jax.random.normal(key, (1, 16, 16, 3), jnp.float32)

    p = enet.init_params(key, num_classes=4)
    out = enet.forward(p, img, compute_dtype="bf16")
    assert out.dtype == jnp.bfloat16 and out.shape[-1] == 4
    assert p["initial"].dtype == jnp.float32

    p = espnet.init_params(key, num_classes=5)    # the decoder ESP needs 5
    out = espnet.forward(p, img, compute_dtype="bf16")
    assert out.dtype == jnp.bfloat16 and out.shape[-1] == 5

    p = dcgan.init_params(key, size=64, nz=8, ngf=8)
    out = dcgan.forward(p, jax.random.normal(key, (2, 8)),
                        compute_dtype="bf16")
    assert out.dtype == jnp.bfloat16 and out.shape == (2, 64, 64, 3)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


def test_gen_step_keeps_lane_dtype():
    """A bf16 diffusion lane stays bf16-resident across DDIM ticks, and the
    inactive-slot freeze is bitwise in bf16 too."""
    params = unet_decoder.init_denoiser_params(jax.random.PRNGKey(0),
                                               widths=(8, 8))
    step = jax.jit(make_gen_step(compute_dtype="bf16"))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16, 3),
                          jnp.float32).astype(jnp.bfloat16)
    x0 = np.asarray(x.astype(jnp.float32))
    batch = {"t": jnp.array([500, 400], jnp.int32),
             "t_next": jnp.array([250, -1], jnp.int32),
             "active": jnp.array([True, False])}
    y = step(params, x, batch)
    assert y.dtype == jnp.bfloat16
    yf = np.asarray(y.astype(jnp.float32))
    assert np.isfinite(yf).all()
    np.testing.assert_array_equal(yf[1], x0[1])     # frozen slot
    assert not np.array_equal(yf[0], x0[0])          # active slot advanced


def test_gen_server_serves_bf16_lane():
    """End-to-end: a GenServer built with compute_dtype="bf16" drains
    requests to finite images and round-trips the dtype through snapshots."""
    from repro.launch.serve_gen import GenServer

    params = unet_decoder.init_denoiser_params(jax.random.PRNGKey(0),
                                               widths=(8, 8))
    srv = GenServer(batch=2, unet_widths=(8, 8), unet_hw=4,
                    params={"unet_dec": params}, compute_dtype="bf16")
    rids = [srv.submit("unet_dec", steps=2, seed=i) for i in range(2)]
    images = srv.run()
    for rid in rids:
        assert np.isfinite(np.asarray(images[rid], np.float32)).all()
    # admission estimates fall back to the fp32 calibration fit for bf16
    est = srv.admission_estimate("unet_dec", steps=2)
    assert est is None or est > 0
    assert srv._snapshot_config()["compute_dtype"] == "bf16"


# ---------------------------------------------------------- loss scaler ----

def test_loss_scale_backoff_and_growth():
    sc = DynamicLossScale(init_scale=8.0, growth_interval=2)
    st = sc.init()
    assert float(st.scale) == 8.0
    st = sc.update(st, jnp.asarray(False))            # overflow: backoff
    assert float(st.scale) == 4.0 and int(st.good_steps) == 0
    st = sc.update(st, jnp.asarray(True))             # 1 good step: hold
    assert float(st.scale) == 4.0 and int(st.good_steps) == 1
    st = sc.update(st, jnp.asarray(True))             # interval hit: grow
    assert float(st.scale) == 8.0 and int(st.good_steps) == 0


def test_loss_scale_clamps():
    sc = DynamicLossScale(init_scale=1.0, min_scale=1.0, max_scale=2.0,
                          growth_interval=1)
    st = sc.init()
    st = sc.update(st, jnp.asarray(False))
    assert float(st.scale) == 1.0                     # floor holds
    st = sc.update(st, jnp.asarray(True))
    st = sc.update(st, jnp.asarray(True))
    assert float(st.scale) == 2.0                     # ceiling holds


def test_loss_scale_round_trip_and_finiteness():
    sc = DynamicLossScale(init_scale=2.0 ** 10)
    st = sc.init()
    grads = {"a": jnp.array([1e-3, -2.0]), "b": jnp.array([[0.5]])}
    scaled = jax.tree_util.tree_map(lambda g: g * st.scale, grads)
    back = sc.unscale(st, scaled)
    for k in grads:
        np.testing.assert_allclose(back[k], grads[k], rtol=1e-6)
    assert bool(sc.all_finite(grads))
    assert not bool(sc.all_finite({"a": jnp.array([1.0, jnp.nan])}))
    assert not bool(sc.all_finite({"a": jnp.array([jnp.inf])}))
    assert bool(sc.all_finite({}))                    # empty tree is finite


def test_select_tree_is_bitwise():
    a = {"w": jnp.array([1.0, 2.0])}
    b = {"w": jnp.array([3.0, 4.0])}
    np.testing.assert_array_equal(
        select_tree(jnp.asarray(False), a, b)["w"], b["w"])
    np.testing.assert_array_equal(
        select_tree(jnp.asarray(True), a, b)["w"], a["w"])


# -------------------------------------------------------------- recipes ----

def _seg_batch(key, classes=5, hw=16):
    k1, k2 = jax.random.split(key)
    return {"image": jax.random.normal(k1, (1, hw, hw, 3), jnp.float32),
            "label": jax.random.randint(k2, (1, hw, hw), 0, classes)}


def test_recipe_bf16_step_matches_fp32():
    """One ESPNet step in bf16 lands near the fp32 step: same loss (5%) and
    gradient norm (10%), no skip, untouched scale."""
    key = jax.random.PRNGKey(0)
    params = espnet.init_params(key, num_classes=5)
    batch = _seg_batch(jax.random.PRNGKey(1))
    losses, gnorms = {}, {}
    for cd in (None, "bf16"):
        step = train_recipes.make_train_step("espnet", compute_dtype=cd)
        state, metrics = step(train_recipes.init_state(params), batch)
        assert float(metrics["skipped"]) == 0.0
        assert float(metrics["scale"]) == DynamicLossScale().init_scale
        assert bool(jnp.isfinite(metrics["loss"]))
        losses[cd], gnorms[cd] = (float(metrics["loss"]),
                                  float(metrics["grad_norm"]))
        # masters stay fp32 through the update
        assert state.params["level1"].dtype == jnp.float32
    assert abs(losses["bf16"] / losses[None] - 1) <= FWD_RTOL
    assert abs(gnorms["bf16"] / gnorms[None] - 1) <= GRAD_RTOL


def test_recipe_skips_on_nonfinite_batch():
    """A NaN batch must not move params, optimizer state, or the AdamW step
    counter — the scaler backs off and reports the skip."""
    key = jax.random.PRNGKey(0)
    params = espnet.init_params(key, num_classes=5)
    state0 = train_recipes.init_state(params)
    batch = _seg_batch(jax.random.PRNGKey(1))
    batch["image"] = batch["image"].at[0, 0, 0, 0].set(jnp.nan)
    step = train_recipes.make_train_step("espnet", compute_dtype="bf16")
    state1, metrics = step(state0, batch)
    assert float(metrics["skipped"]) == 1.0
    assert float(metrics["grad_norm"]) == 0.0
    assert float(metrics["scale"]) == DynamicLossScale().init_scale / 2
    for p0, p1 in zip(jax.tree_util.tree_leaves(state0.params),
                      jax.tree_util.tree_leaves(state1.params)):
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    for o0, o1 in zip(jax.tree_util.tree_leaves(state0.opt),
                      jax.tree_util.tree_leaves(state1.opt)):
        np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))


def test_recipe_dcgan_bf16_smoke():
    key = jax.random.PRNGKey(0)
    params = dcgan.init_params(key, size=64, nz=8, ngf=8)
    batch = {"z": jax.random.normal(key, (2, 8)),
             "target": jnp.zeros((2, 64, 64, 3), jnp.float32)}
    step = train_recipes.make_train_step("dcgan", compute_dtype="bf16")
    state, metrics = step(train_recipes.init_state(params), batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert float(metrics["skipped"]) == 0.0
    with pytest.raises(ValueError):
        train_recipes.make_train_step("vgg")


# -------------------------------------------------------- tiling policy ----

_POLICY_GEOM = dict(x_shape=(1, 64, 64, 16), w_shape=(3, 3, 16, 64))


def test_footprint_is_dtype_and_epilogue_aware():
    fp32 = tp.footprint_bytes("dense", **_POLICY_GEOM, th=8, tc=64)
    bf16 = tp.footprint_bytes("dense", **_POLICY_GEOM, th=8, tc=64,
                              dtype=jnp.bfloat16)
    assert bf16 < fp32                  # halved streams; fp32 acc shared
    fused = tp.footprint_bytes("dense", **_POLICY_GEOM, th=8, tc=64,
                               epilogue=EpilogueSpec(residual="post_act"))
    assert fused > fp32                 # the residual streams a second block
    # occupancy is a fraction, and bf16's deeper sublane packing never helps
    # a tile that fp32 already fills
    occ = tp.mxu_occupancy("dense", **_POLICY_GEOM, th=8, tc=64)
    assert 0 < occ <= 1.0


def test_footprint_counts_register_tile_padding():
    """The ENet-512 stem (3x3/s2, 3 channels): its input block
    (4 phases, 8 rows, 257 cols, 3 ch) sits in VMEM as (264, 128) tiles —
    4 MiB per buffer, which is what the TPU compiler counts — not the
    ~100 KB its element count suggests."""
    geom = dict(x_shape=(1, 512, 512, 3), w_shape=(3, 3, 3, 13), th=8,
                tc=128, stride=2)
    x_block = tp.padded_bytes((4, 8, 257, 3), jnp.float32)
    assert x_block == 4 * 8 * 264 * 128 * 4
    assert tp.padded_bytes((4, 8, 257, 3), jnp.bfloat16) == \
        4 * 8 * 272 * 128 * 2
    fp = tp.footprint_bytes("dense", **geom)
    assert fp >= 4 * x_block            # cur + next, double-buffered
    # over the policy budget and the 16 MiB default scoped VMEM, inside
    # the limit the kernels compile with
    assert tp.VMEM_BUDGET_BYTES < 16 * 2 ** 20 < fp < tp.VMEM_LIMIT_BYTES
    small = tp.footprint_bytes("dense", **_POLICY_GEOM, th=8, tc=64)
    assert small <= tp.VMEM_BUDGET_BYTES


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_footprint_covers_both_strided_conv_forms(dtype):
    """The ENet-512 stem (3x3/s2, 3 channels, 256 output cols) runs as phase
    planes under a gradient and with strided loads in an inference forward;
    the tile table keys on geometry, so its score covers the blocks of
    either form, counted here by hand."""
    geom = dict(x_shape=(1, 512, 512, 3), w_shape=(3, 3, 3, 13), th=8,
                tc=128, stride=2, dtype=dtype)
    b = tp.itemsize(dtype)
    sub = 8 if dtype == jnp.float32 else 16
    pad = lambda n: -(-n // sub) * sub
    lane = 128 * b
    # weight (3, 3, 3->sub, 13->128), output (8, 256, 13->128), fp32 acc
    rest = 2 * (9 * pad(3) * lane + 8 * 256 * lane) + 8 * 256 * 128 * 4
    # phase planes: a cur/next pair of (4 planes, 8 rows, 257 cols) blocks,
    # double-buffered, and the (4, 9, 257) window they are joined into
    planes = 2 * 2 * (4 * 8 * pad(257) * lane) + 4 * 9 * pad(257) * lane
    # strided loads: a cur/next pair of (16 rows, 514 cols) blocks
    rows = 2 * 2 * (16 * pad(514) * lane)
    fp = tp.footprint_bytes("dense", **geom)
    assert fp >= planes + rest
    assert fp >= rows + rest


def test_rank_marks_over_budget_candidates_inf():
    cands = [(4, 64), (8, 64), (8, 128)]
    ranked = tp.rank("dense", **_POLICY_GEOM, cands=cands, vmem_budget=1)
    assert all(math.isinf(s) for s, _ in ranked)
    # and top_candidates degrades to the full sweep rather than guessing
    assert tp.top_candidates("dense", **_POLICY_GEOM, cands=cands,
                             vmem_budget=1) == cands
    with pytest.raises(ValueError):
        tp.rank("conv3d", **_POLICY_GEOM, cands=cands)


def test_top_candidates_keeps_default_and_order():
    cands = at.candidates(h_out=64, cout=512)
    keep = tp.top_candidates("dense", (1, 64, 64, 16), (3, 3, 16, 512),
                             cands, top=at.POLICY_TOP,
                             default_tiles=at.DEFAULT_TILES)
    assert len(keep) <= at.POLICY_TOP + 1
    assert at.DEFAULT_TILES in keep
    assert keep == [c for c in cands if c in keep]    # sweep order preserved
    # forcing the sweep returns the grid unchanged
    os.environ["REPRO_AUTOTUNE_SWEEP"] = "1"
    try:
        assert tp.top_candidates("dense", (1, 64, 64, 16), (3, 3, 16, 512),
                                 cands) == cands
    finally:
        del os.environ["REPRO_AUTOTUNE_SWEEP"]


def test_policy_tune_agrees_with_sweep_on_default_winner(tmp_path,
                                                         monkeypatch):
    """When the true winner is DEFAULT_TILES, the policy tune and the
    exhaustive sweep pick the SAME tiles — the default always rides, so the
    policy can never lose to the baseline tiling."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path))
    at.clear_memory_cache()
    cands = at.candidates(h_out=64, cout=512)
    cost = {c: 5.0 - 4.0 * (c == at.DEFAULT_TILES) for c in cands}
    monkeypatch.setattr(at, "_build_call",
                        lambda kind, x, w, th, tc, *a, **k: (th, tc))
    monkeypatch.setattr(at, "_time_candidate",
                        lambda call, iters: cost[call])
    geom = dict(x_shape=(1, 64, 64, 16), w_shape=(3, 3, 16, 512))
    policy_pick = at.tune("dense", **geom, cands=cands, iters=1)
    monkeypatch.setenv("REPRO_AUTOTUNE_SWEEP", "1")
    sweep_pick = at.tune("dense", **geom, cands=cands, iters=1)
    assert policy_pick == sweep_pick == at.DEFAULT_TILES
    at.clear_memory_cache()


@pytest.mark.slow
def test_policy_vs_sweep_measured():
    """The benchmark-grade comparison on real wall times: the policy's pick
    stays within 50% of the exhaustive winner on the smoke geometries (the
    committed trajectory tracks the tighter 1.05 acceptance bar)."""
    from benchmarks.mixed_precision import policy_vs_sweep

    for kind, r in policy_vs_sweep(iters=2).items():
        assert r["n_timed_policy"] <= at.POLICY_TOP + 1
        # the policy only thins grids bigger than its timed set
        if r["n_candidates"] > at.POLICY_TOP + 1:
            assert r["n_timed_policy"] < r["n_candidates"]
        assert r["agree"] or r["time_ratio"] <= 1.5, (kind, r)
