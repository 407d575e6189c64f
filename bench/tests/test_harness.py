"""The harness on the CPU, past its look for a chip, at sizes a test run
holds: cells found by name, the control and planted faults caught by
``correct``, and no result without a TPU.

The program's place is taken by the plain reference at the highest
precision (fast on the CPU) except where a test says it drives the
repository's own path (Pallas in interpret mode).
"""

import contextlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import common
from bench.control import control_program
from bench.run import Run, execute

BENCH = common.BENCH
ROOT = common.ROOT

TINY_ENET = {"height": 64, "width": 64, "train_batch": 2}
TINY_DCGAN = {"ngf": 8, "lane_batch": 4}
#: limits at these sizes, set between the readings of the repository's
#: path and of the control here (CPU, two seeds each): frame 1.07e-6 and
#: 5.6e-7 against 2.8e-5 and 1.8e-5; DCGAN 1.13e-6 and 9.5e-7 against
#: 6.1e-5 and 6.4e-5; training (control only; the reference in the
#: program's place reads 0) loss 6.0e-6 and 1.2e-5, gradient 9.2e-4 and
#: 3.6e-4, update 1.6e-3 and 1.1e-2
TINY_LIMITS = {
    "enet512.frame": {"logit_gap": 5e-6},
    "dcgan64.backlog": {"image_gap": 1e-5},
    "dcgan64.burst": {"image_gap": 1e-5},
    "enet512.train": {"loss_gap": 2e-6, "grad_gap": 1e-4,
                      "update_gap": 1e-3},
}


@contextlib.contextmanager
def throwaway(kind: str, name: str, payload):
    """A file the harness finds by name, removed afterwards."""
    path = BENCH / kind / name
    if path.exists():
        raise FileExistsError(path)
    if isinstance(payload, str):
        path.write_text(payload)
    else:
        path.write_text(json.dumps(payload))
    try:
        yield path
    finally:
        path.unlink()


@contextlib.contextmanager
def tiny_cell(cell: str, name: str, cfg_over: dict, params_over=None):
    """A copy of ``cell`` on a shrunken copy of its configuration, with
    the limits of that size (``TINY_LIMITS``)."""
    spec = common.cell_spec(cell)
    cfg = common.config_spec(spec["config"])
    cfg_name = f"_test-{name}"
    cfg = cfg | cfg_over | {"name": cfg_name, "work": cfg["name"]}
    spec = spec | {"config": cfg_name, "limits": TINY_LIMITS[cell]}
    spec["params"] = spec["params"] | (params_over or {})
    with throwaway("configs", f"{cfg_name}.json", cfg), \
            throwaway("workloads", f"_test.{name}.json", spec):
        yield f"_test.{name}"


def run_cell(name: str, seed: int, program=None, seconds=0.5, spec=None):
    run = Run(name, seed, seconds, False, spec=spec)
    if program is not None:
        run.prog = program(run)
    return execute(run, jax.devices(), None)


def ref_program(precision="highest"):
    return lambda run: control_program(run, precision)


FRAME = ("enet512.frame", "frame", TINY_ENET, {"pool": 3})
TRAIN = ("enet512.train", "train", TINY_ENET,
         {"pool": 4, "ref_rows": 1})
BACKLOG = ("dcgan64.backlog", "backlog", TINY_DCGAN, {"drain_s": 10})
BURST = ("dcgan64.burst", "burst", TINY_DCGAN,
         {"rate_per_s": 40.0, "drain_s": 10})


@pytest.mark.parametrize("cell", [FRAME, TRAIN, BACKLOG, BURST],
                         ids=lambda c: c[1])
def test_reference_in_program_place_is_correct(cell):
    with tiny_cell(*cell) as name:
        res = run_cell(name, 2**31 + 5, ref_program())
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"
    assert res["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("cell", [FRAME, TRAIN, BACKLOG, BURST],
                         ids=lambda c: c[1])
def test_control_is_not_correct(cell):
    """The reference at ``high`` (three bfloat16 passes) in the program's
    place fails the comparison."""
    with tiny_cell(*cell) as name:
        res = run_cell(name, 7, ref_program("high"))
    assert not res["correct"], res["compared"]


def _altered_forward(run):
    prog = control_program(run, "highest")
    f = prog.forward(run.cfg)
    prog.forward = lambda cfg: lambda p, x: f(p, x).at[0, 3, 5, 1].add(
        0.01 * jax.numpy.max(jax.numpy.abs(f(p, x))))
    return prog


def _unchanged_state(run):
    prog = control_program(run, "highest")
    init, step = prog.train(run.cfg)
    prog.train = lambda cfg: (init, lambda s, b: (s, step(s, b)[1]))
    return prog


def _half_batch(run):
    prog = control_program(run, "highest")
    init, step = prog.train(run.cfg)
    half = lambda b: {k: v[: v.shape[0] // 2] for k, v in b.items()}
    prog.train = lambda cfg: (init, lambda s, b: step(s, half(b)))
    return prog


def _altered_image(run):
    prog = control_program(run, "highest")
    make = prog.server

    def server(cfg, params):
        srv = make(cfg, params)
        step = srv.step

        def altered():
            done = step()
            for r in done[:1]:
                r.result = r.result.copy()
                r.result[10, 10, 0] += 0.01
            return done

        srv.step = altered
        return srv

    prog.server = server
    return prog


@pytest.mark.parametrize("cell,fault", [
    (FRAME, _altered_forward), (TRAIN, _unchanged_state),
    (TRAIN, _half_batch), (BACKLOG, _altered_image), (BURST, _altered_image),
], ids=["frame-altered", "train-unchanged", "train-half-batch",
        "backlog-altered", "burst-altered"])
def test_planted_fault_is_not_correct(cell, fault):
    with tiny_cell(*cell) as name:
        res = run_cell(name, 11, fault)
    assert not res["correct"], res["compared"]


def test_repository_path_frame_is_correct():
    """ENet through the repository's Pallas engines (interpret mode)."""
    with tiny_cell(*FRAME[:3], {"pool": 2}) as name:
        res = run_cell(name, 3, seconds=0.1)
    assert res["correct"], res["compared"]


def test_repository_path_serving_is_correct():
    """DCGAN through ``GenServer`` and its Pallas engines (interpret)."""
    with tiny_cell(*BACKLOG) as name:
        res = run_cell(name, 4, seconds=0.1)
    assert res["correct"], res["compared"]


def test_new_cell_config_and_metric_are_found_by_name():
    """A cell, its configuration and a per-layer metric added as new files
    (and entries in the spec) run with no edit to an existing file."""
    reader = ('from bench.metrics.readers import idle_share as read\n'
              'LAYER, UNIT, MOVES = "device", "%", "seg_frames_per_s"\n')
    spec = common.benchmark_spec()
    spec["workloads"].append({"name": "_test.newcell", "config": "x",
                              "traffic": "x", "chips": 1, "why": "x"})
    spec["end_to_end"][0]["workloads"].append("_test.newcell")
    spec["per_layer"].append({
        "name": "_test_idle.seg", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "seg_frames_per_s", "workloads": ["_test.newcell"]})
    with tiny_cell(*FRAME[:2], TINY_ENET | {"num_classes": 5},
                   FRAME[3]) as tiny, \
            throwaway("metrics", "_test_idle.seg.py", reader):
        cell = common.cell_spec(tiny)
        with throwaway("workloads", "_test.newcell.json", cell):
            run = Run("_test.newcell", 1, 0.3, False, spec=spec)
            assert [m["name"] for m in run.per_layer] == ["_test_idle.seg"]
            assert common.metric_reader("_test_idle.seg").read(
                {"trace": {"window_s": 2.0, "busy_s": 1.5}}) == 25.0
            run.prog = control_program(run, "highest")
            res = execute(run, jax.devices(), None)
    assert res["correct"]
    assert set(res["metrics"]) == {"seg_frames_per_s", "setup_s"}


def _run_py(cwd, env_over=None, seconds="1"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_over or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "enet512.frame",
         "--seed", "1", "--seconds", seconds, "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
