"""The reduction from a profiler trace to the per-layer numbers, on a
synthetic trace with known answers and on a small trace recorded on a TPU
v5e (``bench/record_test_trace.py``)."""

import pathlib

import pytest
from jax.profiler import ProfileData

from bench.metrics import readers
from bench.trace_reduce import merge, op_name, reduce_profile, reduce_trace

RECORDED = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


def _events(rows):
    """rows of (metadata id, start us, duration us[, custom call])"""
    out = []
    for mid, start, dur, *call in rows:
        stat = (' stats { metadata_id: 9 str_value: "tpu_custom_call" }'
                if call else "")
        out.append(f"events {{ metadata_id: {mid} offset_ps: {start * 10**6}"
                   f" duration_ps: {dur * 10**6}{stat} }}")
    return " ".join(out)


def synthetic() -> ProfileData:
    # device: a Pallas kernel 10-30 us, XLA ops 25-40 (overlapping) and
    # 66-70; host spans 0-50 and 55-100 us
    dev = _events([(1, 10, 20, True), (2, 25, 15), (2, 66, 4)])
    host = _events([(1, 0, 50), (1, 55, 45), (2, 50, 5)])
    return ProfileData.from_text_proto(f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {dev} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "tconv_kernel.4" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "fusion.12" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "custom_call_target" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.step" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "other" }} }}
}}
""")


def test_synthetic_trace_reduces_to_known_numbers():
    r = reduce_profile(synthetic(), "bench.step")
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(34e-6)        # 10-40 and 66-70
    assert r["pallas_s"] == pytest.approx(20e-6)
    assert r["xla_s"] == pytest.approx(19e-6)
    assert r["spans"] == [pytest.approx((50e-6, 30e-6)),
                          pytest.approx((45e-6, 4e-6))]
    gaps = [(name, pytest.approx(s)) for name, s in r["idle_gaps"]]
    assert gaps == [("bench.step", 30e-6), ("outside spans > other", 26e-6),
                    ("bench.step", 10e-6)]
    assert [n for n, _ in r["top_ops"]] == ["tconv_kernel", "fusion"]
    ctx = {"trace": r, "units": 2, "work": {"conv_min_s": 5e-6},
           "peak": {"bf16_flops": 1e12}, "counters": {}}
    assert readers.idle_share(ctx) == pytest.approx(66.0)
    assert readers.pallas_ms(ctx) == pytest.approx(0.01)
    assert readers.conv_roofline(ctx) == pytest.approx(50.0)
    assert readers.host_ms_per_tick(ctx) == pytest.approx(0.0305)
    assert readers.slot_fill(ctx) is None


def test_helpers():
    assert merge([(5, 6), (1, 3), (2, 4)]) == [[1, 4], [5, 6]]
    assert op_name("fusion.12") == "fusion"
    assert op_name("conv_kernel") == "conv_kernel"


def test_recorded_chip_trace():
    r = reduce_trace(str(RECORDED), "bench.frame")
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["pallas_s"] > 0 and r["xla_s"] > 0
    assert len(r["spans"]) == 2
    # the device clock sits up to ~1 ms off the host's in a v5e trace, so a
    # span can miss its own frame's device time; the spans together do not
    assert all(0 <= busy <= dur for dur, busy in r["spans"])
    assert sum(busy for _, busy in r["spans"]) > 0
    s = reduce_trace(str(RECORDED), "bench.step")
    assert len(s["spans"]) == 1 and s["pallas_s"] > 0 and s["xla_s"] > 0
