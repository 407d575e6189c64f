"""Gradient-compression unit tests (DESIGN.md §13).

The sharded train step routes its cross-device reduction through
``repro.distributed.compression``; these tests pin the pieces standalone:
quantization error bounds, the error-feedback accumulator's unbiasedness,
wire packing of awkward leaves (odd-length, scalar, zero-size — a bias-free
layer contributes an EMPTY grad leaf — and non-contiguous numpy views), and
the fixed-order ``mesh_allreduce`` that makes the train step bitwise
mesh-invariant.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import compression as C
from repro.launch.mesh import make_train_mesh

#: leaf shapes chosen to stress the wire format: odd length, scalar,
#: zero-size, word-aligned, and > one word
_SHAPES = ((3,), (), (0, 2), (4,), (5, 7))


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {f"leaf{i}": jnp.asarray(rng.normal(size=s).astype(np.float32))
            for i, s in enumerate(_SHAPES)}


# ------------------------------------------------------------ quantization ---

def test_bf16_round_trip_dtype_and_error():
    g = _tree()
    out = C.decompress_bf16(C.compress_bf16(g))
    for k, leaf in g.items():
        assert out[k].dtype == jnp.float32
        # bf16 keeps 8 mantissa bits: relative error < 2^-8
        np.testing.assert_allclose(out[k], leaf, rtol=1 / 256, atol=1e-6)


def test_int8_error_bounded_by_half_step():
    g = jnp.asarray(np.random.default_rng(1).normal(size=(64,)).astype(np.float32))
    q, scale = C.quantize_int8(g)
    err = np.abs(np.asarray(C.dequantize_int8(q, scale)) - np.asarray(g))
    assert err.max() <= float(scale) / 2 + 1e-7


def test_int8_empty_leaf_regression():
    """A zero-size grad leaf must quantize (scale from ``initial=0.0``),
    not crash the reduction with an empty-max error."""
    q, scale = C.quantize_int8(jnp.zeros((0, 3), jnp.float32))
    assert q.shape == (0, 3) and np.isfinite(float(scale))
    qt, st, et = C.compress_int8_ef(_tree(), C.init_error_feedback(_tree()))
    assert qt["leaf2"].shape == (0, 2)
    out = C.decompress_int8(qt, st)
    assert out["leaf2"].shape == (0, 2)


def test_error_feedback_unbiased_over_steps():
    """Residual carry makes repeated int8 compression unbiased: the sum of
    dequantized gradients tracks the sum of true gradients to within one
    quantization step, independent of the step count."""
    g = _tree(seed=2)
    errors = C.init_error_feedback(g)
    total = jax.tree.map(jnp.zeros_like, g)
    n = 25
    for _ in range(n):
        q, s, errors = C.compress_int8_ef(g, errors)
        total = jax.tree.map(lambda t, d: t + d, total, C.decompress_int8(q, s))
    for k in g:
        if g[k].size == 0:
            continue
        step = float(jnp.max(jnp.abs(g[k]))) / 127.0
        np.testing.assert_allclose(np.asarray(total[k]) / n, np.asarray(g[k]),
                                   atol=2 * step / n + 1e-7)


# ------------------------------------------------------------- wire packing ---

@pytest.mark.parametrize("word", [1, 4, 8])
def test_pack_unpack_round_trip(word):
    q_tree, _, _ = C.compress_int8_ef(_tree(3), C.init_error_feedback(_tree(3)))
    buf, manifest = C.pack_int8(q_tree, word=word)
    assert buf.dtype == jnp.int8 and buf.size % word == 0
    out = C.unpack_int8(buf, manifest)
    for k in q_tree:
        assert out[k].shape == q_tree[k].shape
        assert np.array_equal(np.asarray(out[k]), np.asarray(q_tree[k])), k


def test_pack_non_contiguous_and_odd_leaves():
    """numpy views (negative stride, strided slice) and odd-length leaves
    must pack to the same bytes as their contiguous copies."""
    base = np.arange(60, dtype=np.int8).reshape(6, 10)
    tree = {"rev": base[::-1], "strided": base[:, ::3], "odd": base.ravel()[:7]}
    buf, manifest = C.pack_int8(tree)
    out = C.unpack_int8(buf, manifest)
    for k in tree:
        assert np.array_equal(np.asarray(out[k]), np.asarray(tree[k])), k
    contig = {k: np.ascontiguousarray(v) for k, v in tree.items()}
    buf2, _ = C.pack_int8(contig)
    assert np.array_equal(np.asarray(buf), np.asarray(buf2))


def test_pack_word_validation_and_empty_tree():
    with pytest.raises(ValueError, match="word"):
        C.pack_int8({"a": jnp.zeros((3,), jnp.int8)}, word=0)
    buf, manifest = C.pack_int8({})
    assert buf.size == 0 and C.unpack_int8(buf, manifest) == {}


# ----------------------------------------------------------- mesh allreduce ---

def _stacks(chunks=8, seed=4):
    rng = np.random.default_rng(seed)
    return {f"leaf{i}": jnp.asarray(
        rng.normal(size=(chunks,) + s).astype(np.float32))
        for i, s in enumerate(((3, 5), (7,), ()))}


def _reduce_on(nd, stacks, transport):
    mesh = make_train_mesh(nd)
    fn = jax.shard_map(
        lambda s: C.mesh_allreduce(s, "data", transport=transport),
        mesh=mesh, in_specs=(P("data"),), out_specs=P(), check_vma=False)
    return jax.jit(fn)(stacks)


@pytest.mark.mesh
@pytest.mark.parametrize("nd", [2, 4, 8])
def test_mesh_allreduce_dense_bitwise_mesh_invariant(nd, mesh_devices):
    """The §13 pillar: all_gather + ONE fixed-order sum gives the same bits
    on every mesh size (a psum tree would reassociate with the mesh)."""
    if nd > mesh_devices:
        pytest.skip(f"need {nd} devices, have {mesh_devices}")
    stacks = _stacks()
    ref = _reduce_on(1, stacks, "dense")
    out = _reduce_on(nd, stacks, "dense")
    for k in ref:
        assert np.array_equal(np.asarray(out[k]), np.asarray(ref[k])), k
        # and the fixed order IS plain sum-over-chunks
        assert np.array_equal(np.asarray(ref[k]),
                              np.asarray(jnp.sum(stacks[k], axis=0))), k


@pytest.mark.mesh
def test_mesh_allreduce_bf16_transport_close(mesh_devices):
    nd = min(4, mesh_devices)
    stacks = _stacks(seed=5)
    dense = _reduce_on(nd, stacks, "dense")
    bf16 = _reduce_on(nd, stacks, "bf16")
    for k in dense:
        np.testing.assert_allclose(np.asarray(bf16[k]), np.asarray(dense[k]),
                                   rtol=0.05, atol=0.05)


def test_mesh_allreduce_unknown_transport_raises():
    with pytest.raises(ValueError, match="transport"):
        C.mesh_allreduce({"g": jnp.zeros((2, 3))}, "data", transport="int4")
