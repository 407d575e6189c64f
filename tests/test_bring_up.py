"""Guards of the chip path that hold on CPU: ``chip_smoke.py`` refuses
anything but a TPU and anything but a checkout, the persistent compilation
cache goes where it is told, and ``REPRO_AUTOTUNE=off`` pins the tiles."""

from __future__ import annotations

import os
import pathlib
import shutil
import subprocess
import sys

import jax

from repro.kernels import autotune
from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run_smoke(cwd: pathlib.Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_refuses_cpu():
    res = _run_smoke(ROOT)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_chip_smoke_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = _run_smoke(tmp_path)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "checkout" in res.stderr


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX took the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert compile_cache.enable_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert pathlib.Path(first) == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_autotune_off_pins_default_tiles(monkeypatch, tmp_path):
    """A table on disk is not read when the run asks for default tiles."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path))
    autotune.clear_memory_cache()
    geom = dict(kind="dense", x_shape=(1, 16, 16, 8), w_shape=(3, 3, 8, 16))
    key = autotune.make_key(**geom)
    autotune._persist(key, (4, 64))
    autotune.clear_memory_cache()
    try:
        assert autotune.get_tiles(**geom) == (4, 64)
        monkeypatch.setenv("REPRO_AUTOTUNE", "off")
        autotune.clear_memory_cache()
        assert autotune.get_tiles(**geom) == autotune.DEFAULT_TILES
    finally:
        autotune.clear_memory_cache()
