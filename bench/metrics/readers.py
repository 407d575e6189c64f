"""The arithmetic of the per-layer metrics, shared by the one-file
readers beside it.  Each reader gets ``ctx``:

* ``trace``: :func:`bench.trace_reduce.reduce_profile` of the traced window;
* ``units``: frames, train steps or scheduler ticks inside that window;
* ``work``: per unit, the useful FLOPs (``flops``) and the least time of
  the convolutions that run in Pallas kernels (``conv_min_s``), from
  ``bench/work``;
* ``peak``: the chip's row of ``bench/peaks.py``;
* ``counters``: program counters over the window (``GenServer.stats()``).

A reader that finds nothing to read returns ``None`` and the metric is
left out of the result line.
"""

from __future__ import annotations


def idle_share(ctx) -> float | None:
    t = ctx["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def pallas_ms(ctx) -> float | None:
    if not ctx["units"] or ctx["trace"]["pallas_s"] <= 0:
        return None
    return 1000.0 * ctx["trace"]["pallas_s"] / ctx["units"]


def xla_op_ms(ctx) -> float | None:
    if not ctx["units"]:
        return None
    return 1000.0 * ctx["trace"]["xla_s"] / ctx["units"]


def conv_roofline(ctx) -> float | None:
    """Least time of the Pallas-run convolutions over their device time."""
    t = ctx["trace"]
    if not ctx["units"] or t["pallas_s"] <= 0:
        return None
    return 100.0 * ctx["work"]["conv_min_s"] * ctx["units"] / t["pallas_s"]


def mfu(ctx) -> float | None:
    """Useful FLOPs per second of the traced window over the bf16 peak."""
    t = ctx["trace"]
    if not ctx["units"] or t["window_s"] <= 0:
        return None
    rate = ctx["work"]["flops"] * ctx["units"] / t["window_s"]
    return 100.0 * rate / ctx["peak"]["bf16_flops"]


def host_ms_per_tick(ctx) -> float | None:
    """Mean over scheduler ticks of the tick's host span minus the device
    busy time inside it."""
    spans = ctx["trace"]["spans"]
    if not spans:
        return None
    return 1000.0 * sum(d - b for d, b in spans) / len(spans)


def slot_fill(ctx) -> float | None:
    c = ctx["counters"]
    if not c.get("device_steps"):
        return None
    return 100.0 * c["substeps"] / (c["device_steps"] * c["batch"])
