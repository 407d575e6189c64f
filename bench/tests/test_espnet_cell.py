"""The ESPNet cell (``espnet1024x512.frame``) on the CPU at 32x64 with 5
classes: the reference in the program's place is correct, its control and a
planted fault are not, the repository's Pallas path (interpret mode) is,
and the ``esp_merge_ms.seg`` reader sums its scope on a synthetic trace.
At 32x64 the level-3 maps are 4x8, where a d=16 branch reads only its
centre tap; a second fault, at 160x320 (level-3 maps 20x40), zeroes only
the off-centre taps."""

import contextlib

import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from bench import common
from bench.control import control_program
from bench.tests.test_harness import ref_program, run_cell, throwaway
from bench.tests.test_program_trace import (CALL, PROGRAM_ID, TF_OP,
                                            _events, _op)

CELL = "espnet1024x512.frame"
TINY = {"height": 32, "width": 64, "num_classes": 5}
#: the smallest 1:2 frame, in steps of 32, whose level-3 maps (H/8) hold
#: the d=16 taps at +-16 in both directions
WIDE = TINY | {"height": 160, "width": 320}
#: set between the readings at this size (CPU, two seeds each): the
#: repository's Pallas path 8.0e-7 and 5.6e-7, the control 1.4e-5 and
#: 1.8e-5 (dropping a d=16 branch reads 6.5e-2)
TINY_LIMIT = 4e-6


@contextlib.contextmanager
def tiny_cell(name: str, pool: int = 3, size: dict = TINY):
    spec = common.cell_spec(CELL)
    cfg = common.config_spec(spec["config"])
    cfg_name = f"_test-{name}"
    cfg = cfg | size | {"name": cfg_name, "work": cfg["name"]}
    spec = spec | {"config": cfg_name, "limits": {"logit_gap": TINY_LIMIT},
                   "params": {"pool": pool}}
    with throwaway("configs", f"{cfg_name}.json", cfg), \
            throwaway("workloads", f"_test.{name}.json", spec):
        yield f"_test.{name}"


def _planted_d16(keep_centre: bool):
    """The reference with the d=16 branch of one level-3 module zeroed,
    all of it or all but its centre tap."""
    def program(run):
        prog = control_program(run, "highest")
        f = prog.forward(run.cfg)

        def forward(params, x):
            module = params["l3_4"]
            w = module["d16"]
            kept = jnp.zeros_like(w)
            if keep_centre:
                kept = kept.at[1, 1].set(w[1, 1])
            return f(params | {"l3_4": module | {"d16": kept}}, x)

        prog.forward = lambda cfg: forward
        return prog
    return program


_drop_d16 = _planted_d16(keep_centre=False)


@pytest.mark.parametrize("program,seed,correct", [
    (ref_program(), 2**31 + 5, True),
    (ref_program("high"), 7, False),
    (_drop_d16, 11, False),
], ids=["reference", "control", "drop-d16"])
def test_espnet_cell_correct_sees_precision_and_the_widest_branch(
        program, seed, correct):
    with tiny_cell("espnet") as name:
        res = run_cell(name, seed, program)
    assert res["correct"] == correct, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_espnet_cell_sees_the_d16_off_centre_taps():
    """At 160x320 the fault that keeps only the centre tap of one level-3
    d=16 branch reads past both this size's limit and the cell's own."""
    with tiny_cell("espnet-wide", pool=2, size=WIDE) as name:
        res = run_cell(name, 2**31 + 9, _planted_d16(keep_centre=True),
                       seconds=0.1)
    assert not res["correct"], res["compared"]
    gap = res["compared"]["logit_gap"]["value"]
    cell_limit = common.cell_spec(CELL)["limits"]["logit_gap"]
    assert gap > 100 * max(TINY_LIMIT, cell_limit), gap


def test_espnet_repository_path_is_correct():
    """ESPNet through the repository's Pallas engines (interpret mode)."""
    with tiny_cell("espnet-pallas", pool=2) as name:
        res = run_cell(name, 3, seconds=0.1)
    assert res["correct"], res["compared"]


def test_esp_merge_reader_sums_its_scope(tmp_path):
    """A Pallas kernel 10-30 us, an ESP merge fusion 40-50 and a layout
    pass 60-62 under ``esp.merge`` on one device; the harness's frame spans
    0-55 and 58-100 us: 12 us of merge over 2 frames."""
    dev = _events([(1, 10, 20, True), (2, 40, 10), (3, 60, 2)])
    modules = _events([(10, 0, 100)])
    host = _events([(1, 0, 55), (1, 58, 42)])
    merge = "jit(forward)/esp.merge"
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {modules} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {dev} }}
  {_op(1, "conv2d.4", "jit(forward)/engine.dense/pallas_call:", 111)}
  {_op(2, "fusion.9", merge + "/concatenate:", 111)}
  {_op(3, "copy.2", merge + "/layout.pad/pad:", 111)}
  {_op(10, "jit_forward(111)")}
  stat_metadata {{ key: {TF_OP} value {{ id: {TF_OP} name: "tf_op" }} }}
  stat_metadata {{ key: {PROGRAM_ID} value {{ id: {PROGRAM_ID} name: "program_id" }} }}
  stat_metadata {{ key: {CALL} value {{ id: {CALL} name: "custom_call_target" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.frame" }} }}
}}
"""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    reader = common.metric_reader("esp_merge_ms.seg")
    ctx = {"trace": {"window_s": 100e-6}, "units": 2}
    assert reader.read(ctx, root=tmp_path) == pytest.approx(0.006)
    assert reader.read(ctx | {"trace": {"window_s": 90e-6}},
                       root=tmp_path) is None
