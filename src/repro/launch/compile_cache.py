"""JAX persistent compilation cache for the repo's entry points.

A cold process recompiles every kernel shape of ENet's forward and
backward; the persistent cache lets the next process on the same machine
load them instead.  The cache key includes the directory, so the directory
must not move between runs: it is either what ``$JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself; nothing else is set) or the fixed
``<checkout>/.jax_cache``, which ``.gitignore`` lists.

Entry points (``chip_smoke.py``, ``examples/train_enet.py``,
``python -m repro.launch.serve_gen``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up; importing this module changes
nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: this file is <checkout>/src/repro/launch/compile_cache.py
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

#: the in-checkout cache used when the environment names none
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX already took it from
    the environment); otherwise the cache goes to :data:`DEFAULT_DIR`.

    The key includes the HLO metadata: an executable loaded from the cache
    keeps the metadata it was compiled with, so without it a profile could
    show the scope names (``repro.obs``) of another revision of the code.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


__all__ = ["ENV_VAR", "CHECKOUT", "DEFAULT_DIR", "enable_compile_cache"]
