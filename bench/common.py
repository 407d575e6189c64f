"""Shared helpers of the benchmark: where things live, how pieces are
found by name, seeds, and the small statistics the drivers report.

Everything the harness runs is found by name under ``bench/``:

* ``configs/<config>.json`` -- a model configuration (sizes, precision,
  the program adapter, the reference and the work table it uses);
* ``workloads/<cell>.json`` -- a cell: configuration, traffic mix
  parameters, chips, driver, the limits of its ``correct`` comparison;
* ``traffic/<driver>.py`` -- the code that drives a cell's traffic;
* ``programs/<program>.py`` -- how the system under test is called;
* ``refs/<reference>.py`` -- the plain reference (and the weight maker);
* ``work/<config>.py`` -- the useful FLOPs and least bytes of a model;
* ``metrics/<metric>.py`` -- one reader per per-layer metric.

A later change adds a file and never edits one to add a cell.
"""

from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import sys
from types import ModuleType

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path (file names may hold ``-`` and ``.``)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in str(path.relative_to(BENCH)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_spec(name: str) -> dict:
    return load_json(BENCH / "workloads" / f"{name}.json")


def config_spec(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def program(cfg: dict) -> ModuleType:
    return load_module(BENCH / "programs" / f"{cfg['program']}.py")


def reference(cfg: dict) -> ModuleType:
    return load_module(BENCH / "refs" / f"{cfg['reference']}.py")


def work(cfg: dict) -> ModuleType:
    """The work table: ``work/<config>.py``, or the one ``cfg["work"]``
    names (a configuration that shares another's layers)."""
    return load_module(BENCH / "work" / f"{cfg.get('work', cfg['name'])}.py")


def driver(cell: dict) -> ModuleType:
    return load_module(BENCH / "traffic" / f"{cell['driver']}.py")


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py")


def cell_metrics(spec: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics ``BENCHMARK.json`` gives
    ``cell``: an entry with a ``workloads`` key names its cells; one
    without it is reported in every cell (a per-layer one in every cell
    that reports the end-to-end metric it moves)."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if cell in m.get("workloads", [cell]) and m["moves"] in names]
    return e2e, layer


def seed_words(seed: int, stream: int = 0, n: int = 2) -> list[int]:
    """``n`` uint32 words for one use (``stream``) of any whole number
    (negative, or wider than 64 bits, included)."""
    import numpy as np

    return [int(w) for w in np.random.SeedSequence(
        [seed % (1 << 128), stream]).generate_state(n, np.uint32)]


def rng(seed: int, stream: int):
    """A NumPy generator for one named use (``stream``) of the seed."""
    import numpy as np

    return np.random.default_rng(np.random.SeedSequence(
        [seed % (1 << 128), stream]))


def prng_key(seed: int, stream: int = 0):
    """A JAX threefry key for one use of the seed (any whole number)."""
    import jax
    import jax.numpy as jnp

    words = seed_words(seed, stream)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def percentile(values, p: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (failed requests) count
    as missing every limit."""
    if not values:
        return math.nan
    s = sorted(values)
    k = max(1, math.ceil(p / 100.0 * len(s)))
    return float(s[k - 1])


def rel_gap(got, ref) -> float:
    """Widest |got - ref| over the widest |ref|, in float64."""
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return math.inf
    if not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))
