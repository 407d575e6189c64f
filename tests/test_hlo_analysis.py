"""Loop-aware HLO analyzer: exact FLOPs on known programs, collective sizing."""

import jax
import jax.numpy as jnp
import pytest

from repro.distributed.hlo_analysis import (CollectiveStat, analyze,
                                            roofline_terms)


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


def test_scan_flops_multiplied_by_trip_count():
    def body(c, _):
        return c @ c, None

    def f(x):
        return jax.lax.scan(body, x, None, length=8)[0]

    x = jnp.ones((128, 128), jnp.float32)
    a = analyze(_compile(f, x))
    assert a.flops == pytest.approx(8 * 2 * 128 ** 3)


def test_nested_scan_flops():
    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ c2, None
            return jax.lax.scan(inner, c, None, length=8)[0], None
        return jax.lax.scan(outer, x, None, length=4)[0]

    x = jnp.ones((64, 64), jnp.float32)
    a = analyze(_compile(f, x))
    assert a.flops == pytest.approx(32 * 2 * 64 ** 3)


def test_plain_matmul_flops_and_bytes():
    def f(a, b):
        return a @ b

    a_ = jnp.ones((256, 512), jnp.bfloat16)
    b_ = jnp.ones((512, 128), jnp.bfloat16)
    a = analyze(_compile(f, a_, b_))
    assert a.flops == pytest.approx(2 * 256 * 512 * 128)
    # dot reads both operands + writes output at least once
    min_bytes = (256 * 512 + 512 * 128 + 256 * 128) * 2
    assert a.hbm_bytes >= min_bytes


def test_xla_cost_analysis_is_loop_unaware():
    """Documents WHY this module exists: XLA counts the body once."""
    def body(c, _):
        return c @ c, None

    def f(x):
        return jax.lax.scan(body, x, None, length=8)[0]

    x = jnp.ones((128, 128), jnp.float32)
    compiled = jax.jit(f).lower(x).compile()
    xla_flops = compiled.cost_analysis()["flops"]
    ours = analyze(compiled.as_text()).flops
    assert xla_flops == pytest.approx(2 * 128 ** 3)          # 1 iteration
    assert ours == pytest.approx(8 * xla_flops)


def test_collective_wire_model():
    s = CollectiveStat("all-reduce")
    # formulas validated by construction in analyze(); check the ring model
    # numbers on a synthetic record
    from repro.distributed.hlo_analysis import PEAKS, V5E_KIND

    a = analyze("""
HloModule m, entry_computation_layout={()->f32[]}

ENTRY %main.1 () -> f32[] {
  %c = f32[1024,1024]{1,0} constant(0)
  %ar = f32[1024,1024]{1,0} all-reduce(%c), replica_groups=[16,16]<=[256], to_apply=%x
  ROOT %r = f32[] constant(0)
}
""")
    ar = a.collectives["all-reduce"]
    size = 1024 * 1024 * 4
    assert ar.operand_bytes == pytest.approx(size)
    assert ar.wire_bytes == pytest.approx(2 * size * 15 / 16)
    t = roofline_terms(a, V5E_KIND)
    assert t["collective_s"] == pytest.approx(
        ar.wire_bytes / PEAKS[V5E_KIND]["ici_gbps"])


def test_roofline_terms_dimensions():
    def f(a, b):
        return jnp.sum(a @ b)

    a_ = jnp.ones((512, 512), jnp.float32)
    from repro.distributed.hlo_analysis import V5E_KIND

    t = roofline_terms(analyze(_compile(f, a_, a_)), V5E_KIND)
    assert set(t) == {"compute_s", "memory_s", "collective_s"}
    assert t["compute_s"] > 0 and t["memory_s"] > 0
    assert t["collective_s"] == 0.0  # single device: no collectives


def test_roofline_terms_refuse_unknown_device():
    """Peaks are looked up by device kind; a chip without published peaks
    (or the CPU) is an error, never silently priced as a v5e."""
    a = analyze(_compile(lambda x: x * 2, jnp.ones((8,), jnp.float32)))
    with pytest.raises(ValueError, match="no published peaks"):
        roofline_terms(a, "cpu")
