"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
root of a checkout, on the CPU (``JAX_PLATFORMS=cpu``)."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
