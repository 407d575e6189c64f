"""The program's own names in a trace (``bench/program_trace.py``): on a
synthetic trace with known answers and on a trace recorded on a TPU v5e
after the program gained its scopes and spans
(``bench/record_test_trace.py``)."""

import pathlib
import re

import pytest
from jax.profiler import ProfileData

from bench import program_trace as pt
from bench.trace_reduce import reduce_trace

RECORDED = pathlib.Path(__file__).parent / "data" / "scoped.xplane.pb"
TF_OP, PROGRAM_ID, CALL = 20, 21, 22


def _events(rows):
    """rows of (metadata id, start us, duration us[, custom call])"""
    out = []
    for mid, start, dur, *call in rows:
        stat = (f' stats {{ metadata_id: {CALL} str_value: "tpu_custom_call" }}'
                if call else "")
        out.append(f"events {{ metadata_id: {mid} offset_ps: {start * 10**6}"
                   f" duration_ps: {dur * 10**6}{stat} }}")
    return " ".join(out)


def _op(mid, name, tf_op=None, program=None):
    stats = ""
    if tf_op is not None:
        stats += f' stats {{ metadata_id: {TF_OP} str_value: "{tf_op}" }}'
    if program is not None:
        stats += f" stats {{ metadata_id: {PROGRAM_ID} uint64_value: {program} }}"
    return (f'event_metadata {{ key: {mid} value {{ id: {mid} name: "{name}"'
            f"{stats} }} }}")


def synthetic(tmp_path, name="t.xplane.pb") -> str:
    """Two executables (programs 111 and 222) on one device: a Pallas
    kernel under engine.dense 10-30 us, its phase split 5-8, a fusion named
    alike in both programs (train.loss in 111 at 40-50, train.optimizer in
    222 at 70-75), an async copy with no name stack 80-81; host: the
    harness span 0-60 and 65-100 us, gen.admit 2-12 with gen.fetch 20-30
    and a zero-length repro.compile at 66 inside the second."""
    dev = _events([(1, 10, 20, True), (2, 5, 3), (3, 40, 10), (4, 70, 5),
                   (5, 80, 1)])
    modules = _events([(10, 0, 55), (11, 60, 30)])
    host = _events([(1, 0, 60), (2, 2, 10), (3, 20, 10), (1, 65, 35),
                    (4, 66, 0)])
    dense = "jit(f)/engine.dense/jit(conv2d)"
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {modules} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {dev} }}
  {_op(1, "conv2d.4", dense + "/pallas_call:", 111)}
  {_op(2, "copy.7", dense + "/layout.phase_split/transpose:", 111)}
  {_op(3, "fusion.1", "jit(f)/transpose(jvp(train.loss))/mul:", 111)}
  {_op(4, "fusion.1", "jit(g)/train.optimizer/add:", 222)}
  {_op(5, "copy-done.3")}
  {_op(10, "jit_f(111)")}
  {_op(11, "jit_g(222)")}
  stat_metadata {{ key: {TF_OP} value {{ id: {TF_OP} name: "tf_op" }} }}
  stat_metadata {{ key: {PROGRAM_ID} value {{ id: {PROGRAM_ID} name: "program_id" }} }}
  stat_metadata {{ key: {CALL} value {{ id: {CALL} name: "custom_call_target" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.step" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "gen.admit" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "gen.fetch" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "repro.compile" }} }}
}}
"""
    path = tmp_path / name
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_metadata_decoder_reads_name_stacks_and_programs(tmp_path):
    meta = pt.op_metadata(synthetic(tmp_path))
    assert list(meta) == ["/device:TPU:0"]
    ops = meta["/device:TPU:0"]
    assert ops["conv2d.4"] == [(111, "jit(f)/engine.dense/jit(conv2d)/"
                                     "pallas_call:")]
    assert sorted(ops["fusion.1"]) == [
        (111, "jit(f)/transpose(jvp(train.loss))/mul:"),
        (222, "jit(g)/train.optimizer/add:")]
    assert ops["copy-done.3"] == [(None, None)]


def test_synthetic_trace_sums_scopes_and_spans(tmp_path):
    s = pt.summarize(synthetic(tmp_path))
    us = pytest.approx
    assert s["window_s"] == us(100e-6)
    # the shared name resolves by the module around each op
    assert s["scope_s"] == {"engine.dense": us(23e-6),
                            "layout.phase_split": us(3e-6),
                            "train.loss": us(10e-6),
                            "train.optimizer": us(5e-6)}
    assert s["family_s"] == {"engine": us(23e-6), "layout": us(3e-6),
                             "train": us(15e-6)}
    assert (s["ops"], s["ops_named"]) == (5, 4)
    assert (s["pallas_ops"], s["pallas_one_engine"]) == (1, 1)
    spans = s["spans"]
    assert spans["bench.step"][:2] == [2, us(95e-6)]
    assert spans["bench.step"][2] == us(75e-6)      # less admit and fetch
    assert spans["gen.admit"] == [1, us(10e-6), us(10e-6)]
    assert spans["repro.compile"][0] == 1
    # idle gaps: the longest is 50-70 us, across the harness spans' pause
    g0, g1, where = s["gaps"][0]
    assert (g1 - g0) == us(20e3) and where == "bench.step"


def test_readers_divide_by_units_and_check_the_window(tmp_path):
    (tmp_path / "run").mkdir()
    synthetic(tmp_path / "run")
    ctx = {"trace": {"window_s": 100e-6}, "units": 2}
    root = tmp_path
    assert pt.device_ms(ctx, "engine.dense", root) == pytest.approx(0.0115)
    assert pt.device_ms(ctx, "layout", root) == pytest.approx(0.0015)
    assert pt.device_ms(ctx, "grad.dw", root) is None
    assert pt.span_ms(ctx, "gen.fetch", root) == pytest.approx(0.005)
    assert pt.span_ms(ctx, "gen.dispatch", root) is None
    assert pt.span_count(ctx, "repro.compile", root) == 1
    # a trace whose window is not the run's is not read
    other = {"trace": {"window_s": 90e-6}, "units": 2}
    assert pt.device_ms(other, "engine.dense", root) is None
    assert pt.span_count(other, "repro.compile", root) is None
    assert pt.read(ctx, tmp_path / "empty") is None


def test_report_mode(tmp_path):
    text = pt.report(synthetic(tmp_path))
    assert "engine.dense" in text and "gen.admit" in text
    assert re.search(r"20\.000 ms|0\.020 ms", text)


# ------------------------------------------------------ the recorded trace

@pytest.fixture(scope="module")
def recorded():
    return pt.summarize(str(RECORDED)), ProfileData.from_file(str(RECORDED))


def test_recorded_every_op_with_a_name_stack_is_decoded(recorded):
    _, pd = recorded
    meta = pt.op_metadata(str(RECORDED))
    assert list(meta) == ["/device:TPU:0"]
    ops = list(pt.device_ops(pd, meta))
    names = {e.name for p in pd.planes if p.name in meta
             for line in p.lines if line.name == "XLA Ops"
             for e in line.events}
    assert names <= set(meta["/device:TPU:0"])      # every event's metadata
    total = sum(e - s for _, s, e, _, _ in ops)
    bare = [(e - s) for _, s, e, op, _ in ops if op is None]
    # what has no name stack is what the compiler adds: async copies
    assert sum(bare) < 0.05 * total
    assert all(op is not None for _, _, _, op, pallas in ops if pallas)


def test_recorded_every_pallas_op_is_under_one_engine(recorded):
    s, _ = recorded
    assert s["pallas_ops"] > 0
    assert s["pallas_one_engine"] == s["pallas_ops"]
    assert set(s["family_s"]) >= {"engine", "layout"}
    assert {"engine.dense", "engine.dilated", "engine.transposed",
            "layout.phase_split", "layout.parity_interleave"} <= set(
                s["scope_s"])


def test_recorded_gen_spans_nest_in_the_harness_step(recorded):
    _, pd = recorded
    spans = pt.host_spans(pd)
    steps = [(s, e) for s, e, n, _ in spans if n == "bench.step"]
    gen = [(s, e, n) for s, e, n, _ in spans if n.startswith("gen.")]
    assert len(steps) == 1
    assert [n for _, _, n in gen] == ["gen.expire", "gen.admit",
                                      "gen.dispatch", "gen.fetch"]
    assert all(steps[0][0] <= s <= e <= steps[0][1] for s, e, _ in gen)


def test_recorded_window_matches_the_harness_reduction(recorded, tmp_path):
    s, _ = recorded
    frames = reduce_trace(str(RECORDED), "bench.frame")
    step = reduce_trace(str(RECORDED), "bench.step")
    # the recording holds two kinds of harness span; a run holds one
    assert s["window_s"] >= frames["window_s"] + step["window_s"]
    run = tmp_path / "run"
    run.mkdir()
    (run / "t.xplane.pb").write_bytes(RECORDED.read_bytes())
    ctx = {"trace": {"window_s": s["window_s"]}, "units": 1}
    assert pt.device_ms(ctx, "engine", tmp_path) > 0
    assert pt.device_ms({"trace": frames, "units": 1}, "engine",
                        tmp_path) is None
