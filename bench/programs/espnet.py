"""How the benchmark calls the system under test for ESPNet: the model's
forward on the Pallas engines (compiled on a TPU, interpreted on a CPU
where only the tests run).  The weights come from the benchmark
(``bench/refs/espnet.py``)."""

from __future__ import annotations

import functools


def forward(cfg: dict):
    """``f(params, x) -> logits``: the entry a segmentation user calls."""
    from repro.models import espnet

    return functools.partial(espnet.forward, backend="pallas", interpret=None,
                             alpha2=cfg["alpha2"], alpha3=cfg["alpha3"])
