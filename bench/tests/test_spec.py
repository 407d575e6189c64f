"""``BENCHMARK.json`` against its files: every name resolves, every
reader declares what the spec says, and the contract's limits hold."""

import json
import re

import pytest

from bench import common

SPEC = common.benchmark_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_keys_and_names():
    assert set(SPEC) == KEYS["top"]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[kind]]
        assert len(names) == len(set(names))
        for e in SPEC[kind]:
            extra = set(e) - KEYS[kind]
            assert set(e) >= KEYS[kind] and extra <= {"workloads"}, e
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_every_config_file_is_under_paths_and_complete():
    for c in SPEC["configs"]:
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        cfg = json.loads((common.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["precision"] in ("highest",)


def test_every_cell_matches_its_file_and_reports_enough():
    used = set()
    for w in SPEC["workloads"]:
        cell = common.cell_spec(w["name"])
        for k in ("config", "traffic", "chips", "why"):
            assert cell[k] == w[k], (w["name"], k)
        assert (common.BENCH / "traffic" / f"{cell['driver']}.py").is_file()
        used.add(w["config"])
        e2e, layer = common.cell_metrics(SPEC, w["name"])
        names = [m["name"] for m in e2e]
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert layer, w["name"]
    assert used == {c["name"] for c in SPEC["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_per_layer_metric_has_its_reader():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        reader = common.metric_reader(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            m["layer"], m["unit"], m["moves"]), m["name"]
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w]), (m["name"], w)
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")


def test_bounds_and_run_length_fit_the_contract():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    setup = next(m for m in SPEC["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.25 and "workloads" not in setup
    s = SPEC["run_seconds"]
    assert 1 <= s <= 51
    full = (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200
    assert full <= 43200
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_limits_are_set(cell):
    spec = common.cell_spec(cell)
    assert spec["limits"] and all(v > 0 for v in spec["limits"].values())
