"""Sharding-rule resolution (pure logic — no multi-device requirement) and a
subprocess 8-device lower/compile check."""

import json
import subprocess
import sys
import textwrap

import pytest


# resolve_spec needs a Mesh only for .shape: use a lightweight stand-in.
class _FakeMesh:
    def __init__(self, **axes):
        self.shape = axes


from repro.distributed.sharding import param_pspec, resolve_spec  # noqa: E402


def P(*args):
    from jax.sharding import PartitionSpec
    return PartitionSpec(*args)


MESH = _FakeMesh(data=16, model=16)
MESH_POD = _FakeMesh(pod=2, data=16, model=16)


def test_activation_batch_sharding():
    spec = resolve_spec(MESH_POD, ("data", None, None), (256, 4096, 5120))
    assert spec == P(("pod", "data"), None, None)


def test_divisibility_guard_drops():
    # batch 1 cannot shard over data -> dropped
    spec = resolve_spec(MESH, ("data", None), (1, 64))
    assert spec == P(None, None)


def test_image_sharding_spec_resolution():
    """Generative-serving NHWC state (launch.serve_gen): batch over data,
    spatial height over model only when requested AND divisible."""
    spec = resolve_spec(MESH, ("data", "spatial", None, None),
                        (32, 64, 64, 3))
    assert spec == P("data", "model", None, None)
    # smoke batch of 4 with 16-way data axis -> batch axis dropped; 15 rows
    # don't divide the model axis -> spatial dropped too
    spec = resolve_spec(MESH, ("data", "spatial", None, None),
                        (4, 15, 15, 3))
    assert spec == P(None, None, None, None)


def test_image_sharding_on_real_mesh():
    import jax

    from repro.distributed.sharding import image_sharding
    from repro.launch.mesh import make_smoke_mesh

    mesh = make_smoke_mesh()
    sh = image_sharding(mesh, (4, 16, 16, 3), spatial=True)
    x = jax.device_put(jax.numpy.zeros((4, 16, 16, 3)), sh)
    assert x.shape == (4, 16, 16, 3)


def test_axis_reuse_guard():
    # both dims want the model axis; only the first gets it
    spec = resolve_spec(MESH, ("model", "expert"), (64, 128))
    assert spec == P("model", None)


def test_kvseq_widens_for_batch1():
    # long-context decode: batch 1 -> sequence takes every axis
    spec = resolve_spec(MESH_POD, ("data_kvseq", "kvseq", "model_kv", None),
                        (1, 524288, 8, 256))
    assert spec == P(None, ("pod", "data", "model"), None, None)


def test_kvseq_model_only_when_batch_sharded():
    spec = resolve_spec(MESH_POD, ("data_kvseq", "kvseq", "model_kv", None),
                        (128, 32768, 8, 128))
    assert spec == P(("pod", "data"), ("model",)[0], None, None)


def test_param_rules():
    assert param_pspec(MESH, "blocks/0/mixer/wq", (64, 2048, 8192)) == \
        P(None, "data", "model")
    assert param_pspec(MESH, "blocks/0/mixer/wo", (64, 8192, 2048)) == \
        P(None, "model", "data")
    assert param_pspec(MESH, "embed", (151936, 5120)) == P("model", "data")
    assert param_pspec(MESH, "blocks/0/ffn/we_gate", (64, 16, 8192, 768)) == \
        P(None, "model", "data", None)  # expert dim -> model (EP)
    assert param_pspec(MESH, "blocks/0/norm1", (64, 5120)) == P()
    assert param_pspec(MESH, "blocks/0/ffn/router", (5120, 128)) == P()


def test_moe_dense_ffn_rules_distinct():
    # dense-FFN w_gate vs expert-stacked we_gate must get different rules
    assert param_pspec(MESH, "blocks/1/ffn/w_gate", (24, 2048, 5632)) == \
        P(None, "data", "model")
    assert param_pspec(MESH, "blocks/1/ffn/we_down", (24, 16, 768, 2048)) == \
        P(None, "model", None, "data")


# --------------------------------------------------------------------------
# Multi-device conv parity grid (DESIGN.md §13) — runs in-process on the
# simulated 8-device CPU mesh (the opt-in XLA_FLAGS fake-device session,
# see conftest.py).  Forward sharding is GSPMD over the
# batch (plus the decomposed phase/parity fold) and must be BITWISE equal to
# the single-device result; gradients recompose through different fusion
# boundaries, so they are held to allclose.
# --------------------------------------------------------------------------

import numpy as np  # noqa: E402

#: the three engine kinds of the paper's decomposition, with uneven extents
#: (B=5, H=13 divide none of the mesh sizes — the pad_batch remainder path)
_ENGINES = {
    "dense": dict(dilation=1),
    "dilated": dict(dilation=2),
    "tconv": dict(transposed=True, stride=2),
}


def _conv_case(kind):
    import jax
    import jax.numpy as jnp

    kx, kw = jax.random.split(jax.random.PRNGKey(11))
    x = jax.random.normal(kx, (5, 13, 13, 3), jnp.float32)
    w = jax.random.normal(kw, (3, 3, 3, 4), jnp.float32)
    return x, w, dict(_ENGINES[kind])


@pytest.mark.mesh
@pytest.mark.parametrize("nd", [1, 2, 4, 8])
@pytest.mark.parametrize("kind", sorted(_ENGINES))
def test_shard_conv2d_parity_grid(kind, nd, mesh_devices):
    import jax
    import jax.numpy as jnp

    from repro.core.decompose import conv2d
    from repro.distributed.sharding import shard_conv2d
    from repro.launch.mesh import make_train_mesh

    if nd > mesh_devices:
        pytest.skip(f"need {nd} devices, have {mesh_devices}")
    x, w, kw = _conv_case(kind)
    mesh = make_train_mesh(nd)

    ref = conv2d(x, w, **kw)
    y, dx, dw = shard_conv2d(mesh, x, w, with_grads=True, **kw)
    assert np.array_equal(np.asarray(y), np.asarray(ref)), kind

    ry, vjp = jax.vjp(lambda xx, ww: conv2d(xx, ww, **kw), x, w)
    rdx, rdw = vjp(jnp.ones_like(ry))
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rdx),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(rdw),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.mesh
def test_shard_conv2d_spatial_dilated(mesh_devices):
    """Spatial (H) sharding on top of the batch axis: the dilated phase
    fold subdivides H by the dilation, so the halo-free phase view must
    still match the single-device result bitwise."""
    from repro.core.decompose import conv2d
    from repro.distributed.sharding import shard_conv2d
    from repro.launch.mesh import make_smoke_mesh

    x, w, kw = _conv_case("dilated")
    mesh = make_smoke_mesh(min(4, mesh_devices))
    y = shard_conv2d(mesh, x, w, spatial=True, **kw)
    assert np.array_equal(np.asarray(y), np.asarray(conv2d(x, w, **kw)))


@pytest.mark.slow
def test_small_mesh_lower_and_compile():
    """Subprocess with 8 fake devices: reduced arch lowers + compiles with
    collectives on both step kinds."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        from repro.configs import get_reduced
        from repro.launch.mesh import auto_mesh
        from repro.launch.steps import lower_cell
        import repro.launch.shapes as shapes
        from repro.distributed import hlo_analysis as ha

        shapes.SHAPES["t"] = shapes.ShapeCell("t", 64, 8, "train")
        shapes.SHAPES["d"] = shapes.ShapeCell("d", 64, 8, "decode")
        mesh = auto_mesh((4, 2), ("data", "model"))
        out = {}
        for cell in ("t", "d"):
            lowered, _ = lower_cell(get_reduced("qwen3-moe-30b-a3b"), cell,
                                    mesh)
            a = ha.analyze(lowered.compile().as_text())
            out[cell] = {"flops": a.flops,
                         "colls": sorted(a.collectives)}
        print(json.dumps(out))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["t"]["flops"] > 0
    assert "all-reduce" in out["t"]["colls"]  # grad reduction exists
