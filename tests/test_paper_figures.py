"""Golden tests pinning the cycle model to the paper's published figures.

The abstract claims the decomposition "can cut down 87.8% of the cycle
counts to achieve 8.2X speedup over a naive execution for the ENet case".
These tests freeze that reproduction so cycle-model refactors cannot
silently drift off the paper:

* **headline** — per-group cycle ratios normalized by the paper's own
  Fig. 10 workload mix must recover 8.2x (±5%) and ≥87% reduction
  (see ``cycle_model.headline`` for why the mix normalization is the
  honest pinning);
* **Fig. 11** — per-dilation-rate efficiency vs ideal sparse must sit in
  the published 83–98% band and fall monotonically with D;
* **Fig. 12** — per-output-size transposed efficiency must reach 99% at
  512 and degrade only marginally with tiling;
* the ESPNet workload and the training-cost extension ride on the same
  harness so they are pinned from birth.
"""

import pytest

from repro.core import cycle_model as cm
from repro.core.enet_spec import (
    dilated_layer_sets, enet_512_layers, transposed_layer_sets,
)
from repro.core.espnet_spec import espnet_layers
from repro.core.gen_spec import dcgan_layers, unet_decoder_layers

# the benchmarks package lives at the repo root (pytest's pythonpath only
# covers src/); one module-level insert serves every benchmark-harness test
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

PAPER_SPEEDUP = 8.2
PAPER_REDUCTION_PCT = 87.8


@pytest.fixture(scope="module")
def enet():
    return enet_512_layers()


@pytest.fixture(scope="module")
def espnet():
    return espnet_layers()


# ------------------------------------------------------------- headline ---

def test_headline_speedup_within_5pct(enet):
    hl = cm.headline(enet)
    assert PAPER_SPEEDUP * 0.95 <= hl["speedup"] <= PAPER_SPEEDUP * 1.05, hl


def test_headline_cycle_reduction(enet):
    hl = cm.headline(enet)
    assert hl["cycle_reduction_pct"] >= 87.0
    assert abs(hl["cycle_reduction_pct"] - PAPER_REDUCTION_PCT) <= 2.0


def test_headline_group_ratios(enet):
    """The per-group ratios behind the headline (Fig. 10's 2/2/9 vs 85/7/8)."""
    r = cm.headline(enet)["group_ratios"]
    assert r["dilated"] == pytest.approx(2 / 85, rel=0.20)     # 85% -> ~2%
    assert r["transposed"] == pytest.approx(2 / 7, rel=0.15)   # 7%  -> ~2%
    assert 1.05 <= r["general"] <= 1.20                        # 8%  -> ~9%


def test_naive_array_baseline(enet):
    """The zero-laden schedule on the same array costs MORE than ideal dense
    (utilization losses), and the decomposition still wins >7x against it."""
    rep = cm.report(enet)
    assert rep["naive_cycles"] >= rep["ideal_dense_cycles"]
    assert 7.0 <= rep["speedup_vs_naive"] <= 9.0
    assert 85.0 <= rep["cycle_reduction_vs_naive_pct"] <= 90.0


def test_honest_inventory_bands(enet):
    """The full honest ENet inventory (no mix normalization) stays in the
    band the seed established — a drift alarm, not a paper claim."""
    rep = cm.report(enet)
    assert 6.0 <= rep["overall_speedup"] <= 9.0
    assert 82.0 <= rep["cycle_reduction_pct"] <= 90.0


# ------------------------------------------------------ Fig. 11 (dilated) ---

FIG11_BANDS = {1: (0.95, 0.99), 3: (0.93, 0.98), 7: (0.88, 0.95),
               15: (0.83, 0.88)}


def test_fig11_efficiency_bands(enet):
    effs = {}
    for D, ls in dilated_layer_sets(enet).items():
        effs[D] = (sum(cm.cycles_ideal_sparse(l) for l in ls)
                   / sum(cm.cycles_our_decomposed(l) for l in ls))
    assert set(effs) == set(FIG11_BANDS)
    for D, (lo, hi) in FIG11_BANDS.items():
        assert lo <= effs[D] <= hi, (D, effs[D])
    assert effs[1] > effs[3] > effs[7] > effs[15]   # paper: falls with D


def test_fig11_speedup_rises_with_D(enet):
    sps = {D: (sum(cm.cycles_ideal_dense(l) for l in ls)
               / sum(cm.cycles_our_decomposed(l) for l in ls))
           for D, ls in dilated_layer_sets(enet).items()}
    assert sps[1] < sps[3] < sps[7] < sps[15]
    # ~ (2D+3)^2/9 x efficiency: pin the endpoints
    assert sps[1] == pytest.approx(2.8, rel=0.10)
    assert sps[15] == pytest.approx(121 * 0.833 / 0.69, rel=0.15)


# --------------------------------------------------- Fig. 12 (transposed) ---

def test_fig12_transposed_bands(enet):
    effs = {sz: (sum(cm.cycles_ideal_sparse(l) for l in ls)
                 / sum(cm.cycles_our_decomposed(l) for l in ls))
            for sz, ls in transposed_layer_sets(enet).items()}
    assert set(effs) == {128, 256, 512}
    assert effs[512] >= 0.97                        # paper: "up to 99%"
    assert all(e >= 0.88 for e in effs.values())
    assert effs[128] < effs[256] < effs[512]        # tiling loss shrinks


# -------------------------------------------------------- ESPNet workload ---

def test_espnet_is_dilated_dominated(espnet):
    """The spatial pyramid makes ESPNet even more dilated-heavy than ENet
    (96% of the ideal-dense cycles); its 2x2 upsamplers on the class
    channels are a small share (1.2%)."""
    rep = cm.report(espnet)
    assert rep["share_dilated_pct"] >= 90.0
    assert 0.5 <= rep["share_transposed_pct"] <= 3.0


def test_espnet_overall_speedup(espnet):
    """Dilated work at D = 15 in every module lifts the whole-net speed-up
    above ENet's (15.8x ideal dense, 22.0x naive)."""
    rep = cm.report(espnet)
    assert 14.0 <= rep["overall_speedup"] <= 18.0
    assert 19.0 <= rep["speedup_vs_naive"] <= 25.0


def test_espnet_dilated_bands(espnet):
    """Every module runs the whole band D = 1, 3, 7, 15 on narrow branches
    (12 and 25 channels), which read 0.66-0.76 of ideal sparse, below
    ENet's Fig. 11 band; the branches are stride 1 after the DownSamplerB's
    strided reduce."""
    effs = {}
    for D, ls in dilated_layer_sets(espnet).items():
        assert all(l.stride == 1 for l in ls)
        assert len(ls) == 13                        # one per ESP module
        effs[D] = (sum(cm.cycles_ideal_sparse(l) for l in ls)
                   / sum(cm.cycles_our_decomposed(l) for l in ls))
    assert set(effs) == {1, 3, 7, 15}
    assert all(0.60 <= e <= 0.80 for e in effs.values())
    assert effs[1] > effs[3] > effs[7] > effs[15]
    assert [l.name for l in espnet if l.stride == 2 and l.kind == "conv"] \
        == ["level1", "l2.0.reduce3x3s2", "l3.0.reduce3x3s2"]


# ------------------------------------------- generative decoder workloads ---
#
# EcoFlow's argument, pinned: the weight decomposition matters most where
# transposed convolutions dominate — GAN generators and diffusion decoders,
# not segmentation decoder tails.  Bands computed from the gen_spec tables
# (mirroring the fig11 pattern: cycle bands + an executable MAC-skip
# cross-check from each layer set's own geometry).

@pytest.fixture(scope="module")
def dcgan64():
    return dcgan_layers(64)


@pytest.fixture(scope="module")
def dcgan128():
    return dcgan_layers(128)


@pytest.fixture(scope="module")
def unet_dec():
    return unet_decoder_layers()


def _tconv_mac_skip(layers):
    """naive/decomposed MAC ratio from each layer's own geometry — the SAME
    helper the fig12 benchmark emits, so the golden pin and the benchmark
    row cannot drift apart."""
    from benchmarks.fig12_transposed_layers import _tconv_mac_skip as skip

    return skip(layers)


def test_dcgan_is_transposed_dominated(dcgan64, dcgan128, enet):
    """>99% of generator cycles are transposed conv — the whole net runs on
    the weight decomposition, vs ENet's ~5% decoder tail."""
    for layers in (dcgan64, dcgan128):
        rep = cm.report(layers)
        assert rep["share_transposed_pct"] >= 99.0
        assert rep["share_dilated_pct"] == 0.0
    assert cm.report(enet)["share_transposed_pct"] <= 10.0


def test_dcgan_reduction_bands(dcgan64, dcgan128):
    """Pinned bands: the k=4/s=2 chains cut ~72% of the naive-array cycles
    (s**2 = 4x MAC skip, minus the input-tiling and boundary losses that
    dominate at the 4x4/8x8 ends of the chain)."""
    for layers, lo_sp in ((dcgan64, 3.4), (dcgan128, 3.4)):
        rep = cm.report(layers)
        assert lo_sp <= rep["speedup_vs_naive"] <= 3.9, rep
        assert 70.0 <= rep["cycle_reduction_vs_naive_pct"] <= 75.0, rep
        assert 2.3 <= rep["transposed_speedup"] <= 2.9, rep


def test_dcgan_mac_skip_is_exactly_s_squared(dcgan64, dcgan128, unet_dec):
    """Exact-2x even-kernel geometry gives every parity (k/s)**2 live taps,
    so the executable MAC skip is exactly s**2 = 4 for all three workloads —
    the cross-check that the spec tables record the true geometry."""
    for layers in (dcgan64, dcgan128, unet_dec):
        assert _tconv_mac_skip(layers) == pytest.approx(4.0, rel=1e-9)


def test_dcgan_boundary_loss_shrinks_with_size(dcgan64, dcgan128):
    """Transposed efficiency vs ideal sparse improves with extent (the
    Fig. 12 trend, sampled at generative 4..128 extents where the boundary
    taps of p_lo=2 actually bite)."""

    def eff(layers):
        g = cm.summarize(layers)
        return g["transposed"].cycles_sparse / g["transposed"].cycles_ours

    assert 0.50 <= eff(dcgan64) <= 0.60
    assert 0.55 <= eff(dcgan128) <= 0.66
    assert eff(dcgan64) < eff(dcgan128)


def test_unet_decoder_bands(unet_dec):
    """The mixed conv/tconv decoder: transposed is ~half the cycle share and
    the decomposition still removes ~30% of the naive-array cycles."""
    rep = cm.report(unet_dec)
    assert 40.0 <= rep["share_transposed_pct"] <= 55.0
    assert 1.3 <= rep["speedup_vs_naive"] <= 1.6
    assert 26.0 <= rep["cycle_reduction_vs_naive_pct"] <= 34.0
    assert 2.6 <= rep["transposed_speedup"] <= 3.0


def test_generative_training_report(dcgan64, unet_dec):
    """The fwd+bwd extension holds for the generative workloads too: the
    adjoint of a k=4/s=2 upsample is a strided dense conv at the input
    extent, so training keeps a transposed-class win."""
    for layers in (dcgan64, unet_dec):
        t = cm.training_report(layers)
        assert t["train_speedup_vs_naive"] >= 1.2
        assert t["train_cycles"] > t["fwd_cycles"] > 0


def test_ecoflow_share_ordering(dcgan64, unet_dec, enet, espnet):
    """The weight decomposition's leverage orders exactly as EcoFlow argues:
    generator >> diffusion decoder >> segmentation nets."""
    share = {id(l): cm.report(l)["share_transposed_pct"]
             for l in (dcgan64, unet_dec, enet, espnet)}
    assert share[id(dcgan64)] > share[id(unet_dec)] > share[id(enet)]
    assert share[id(dcgan64)] > share[id(unet_dec)] > share[id(espnet)]


# --------------------------------------------- training-cost extension ---

def test_training_speedup_carries_to_backward(enet, espnet):
    """EcoFlow's observation: the backward pass is itself dilated/transposed
    convolutions, so the decomposition accelerates training, not just
    inference — the fwd+bwd speedup stays within ~15% of forward-only."""
    for layers in (enet, espnet):
        tr = cm.training_report(layers)
        assert tr["bwd_speedup_vs_naive"] >= 5.0
        assert tr["train_speedup_vs_naive"] >= 0.85 * tr["fwd_speedup_vs_naive"]
        assert tr["train_cycles"] > tr["fwd_cycles"] > 0


def test_adjoint_layer_classes(enet):
    """The adjoint symmetry at the spec level: transposed -> strided dense at
    the input extent; dilated -> dilated; channels always swap."""
    for l in enet:
        a = cm.adjoint_layer(l)
        assert (a.cin, a.cout) == (l.cout, l.cin)
        if l.kind == "transposed":
            assert a.kind == "conv"
            assert (a.h_out, a.w_out) == cm.tconv_input_size(l)
        elif l.kind == "dilated":
            assert a.kind == "dilated" and a.D == l.D


# ----------------------------------------------------- benchmark harness ---

def test_fig10_and_fig11_benchmarks_run():
    """The figure benchmarks stay executable and emit the golden rows."""
    from benchmarks import fig10_enet_speedup, fig11_dilated_layers

    rows10 = {name: val for name, _, val in fig10_enet_speedup.run(csv=True)}
    assert "fig10.headline_speedup_x" in rows10
    assert float(rows10["fig10.headline_speedup_x"].split()[0]) >= 7.7
    rows11 = [name for name, _, _ in fig11_dilated_layers.run(csv=True)]
    assert any(n.startswith("fig11.enet.D15") for n in rows11)
    assert any(n.startswith("fig11.espnet.D7") for n in rows11)


def test_fig12_benchmark_emits_generative_rows():
    """fig12 carries the generative workload rows (they ride into the
    BENCH_<rev>.json artifact through benchmarks/run.py)."""
    from benchmarks import fig12_transposed_layers

    rows = {name: val for name, _, val in fig12_transposed_layers.run(csv=True)}
    for wl in ("dcgan64", "dcgan128", "unet_dec"):
        assert f"fig12.{wl}.speedup_vs_naive_x" in rows
        assert float(rows[f"fig12.{wl}.mac_skip_ratio"]) == pytest.approx(4.0)
    assert float(rows["fig12.dcgan64.share_transposed_pct"]) >= 99.0
    assert float(rows["fig12.L512.eff_vs_sparse_pct"]) >= 97.0  # paper band
