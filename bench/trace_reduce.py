"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* The device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds
  one event per operation run on the chip.
* busy: the union of those events' intervals inside the window, averaged
  over the devices.
* window: from the start of the first harness span (``span``, a
  ``TraceAnnotation`` on a host thread) to the end of the last one.
* Pallas time: events of Mosaic kernels, which a TPU trace names by the
  kernel function and marks as custom calls (see :func:`is_pallas`); every
  other device operation is XLA time.
* per span: each harness span's host duration and the device busy time
  inside it.
* idle gaps: the longest stretches of the window with no device
  operation, each labelled with what the host thread of the harness spans
  was doing at the gap's middle: the harness span (``outside spans`` where
  it was in none) and the innermost event of that thread around it (the
  profiler records Python calls, such as ``$serve_gen.py:497 tick``).
* top ops: device time by operation name, the numeric suffix dropped.
"""

from __future__ import annotations

import bisect
import collections
import re

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
_SUFFIX = re.compile(r"[._]\d+$")


def _stat_text(event) -> str:
    return " ".join(str(v) for _, v in event.stats)


def is_pallas(event) -> bool:
    """A Mosaic (Pallas) kernel: a TPU custom call to ``tpu_custom_call``."""
    text = event.name + " " + _stat_text(event)
    return "tpu_custom_call" in text or "mosaic" in text.lower()


def op_name(name: str) -> str:
    """A device op's name without its numeric suffix; a TPU trace names an
    op by its HLO text (``%conv2d.94 = f32[1,256,256,13]{...} custom-call(
    ...)``), which becomes ``conv2d f32[1,256,256,13]``: the op and the
    shape it writes."""
    shape = ""
    if name.startswith("%") and " = " in name:
        head, rest = name[1:].split(" = ", 1)
        shape = " " + rest.split("{", 1)[0].split(" ", 1)[0]
        name = head
    while _SUFFIX.search(name):
        name = _SUFFIX.sub("", name)
    return name + shape


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def overlap(merged, starts, s: float, e: float) -> float:
    """Length of ``[s, e]`` covered by the disjoint sorted ``merged``
    (``starts``: their start points)."""
    i = max(0, bisect.bisect_right(starts, s) - 1)
    total = 0.0
    while i < len(merged) and merged[i][0] < e:
        total += max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return total


def _device_events(pd):
    devices = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                devices[plane.name] = list(line.events)
    return devices


def _spans(pd, span: str):
    """The harness spans, and every event of the host thread they are on
    as ``(start, end, name)``."""
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            events = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            spans = sorted((s, e) for s, e, n in events if n == span)
            if spans:
                return spans, events
    return [], []


def _innermost(events, t: float) -> str | None:
    inside = [(e - s, n) for s, e, n in events if s <= t <= e]
    return min(inside)[1] if inside else None


def reduce_profile(pd, span: str, top: int = 10) -> dict:
    """The reduction of a loaded ``ProfileData`` (see the module doc)."""
    spans, host = _spans(pd, span)
    devices = _device_events(pd)
    if not spans or not devices:
        raise ValueError(f"trace holds {len(spans)} {span!r} spans and "
                         f"{len(devices)} device op lines")
    w0, w1 = spans[0][0], spans[-1][1]
    busy, pallas, xla = [], 0.0, 0.0
    per_op = collections.Counter()
    merged_all = []
    for events in devices.values():
        inside = [e for e in events if e.end_ns > w0 and e.start_ns < w1]
        merged = merge((max(e.start_ns, w0), min(e.end_ns, w1))
                       for e in inside)
        merged_all.append(merged)
        busy.append(sum(e - s for s, e in merged))
        for e in inside:
            d = min(e.end_ns, w1) - max(e.start_ns, w0)
            per_op[op_name(e.name)] += d
            if is_pallas(e):
                pallas += d
            else:
                xla += d
    n_dev = len(devices)
    first = merged_all[0]
    first_starts = [s for s, _ in first]
    span_rows = [(s, e, overlap(first, first_starts, s, e)) for s, e in spans]
    gaps = []
    prev = w0
    for s, e in first + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    starts = [s for s, _ in spans]

    def label(t):
        i = bisect.bisect_right(starts, t) - 1
        where = span if i >= 0 and spans[i][1] >= t else "outside spans"
        inner = _innermost(host, t)
        return where if inner in (None, span) else f"{where} > {inner}"

    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy) / n_dev * ns,
        "pallas_s": pallas / n_dev * ns,
        "xla_s": xla / n_dev * ns,
        "devices": n_dev,
        "spans": [((e - s) * ns, b * ns) for s, e, b in span_rows],
        "top_ops": [[name, t / n_dev * ns]
                    for name, t in per_op.most_common(top)],
        "idle_gaps": [[label((s + e) / 2), (e - s) * ns]
                      for s, e in gaps[:top]],
    }


def reduce_trace(path: str, span: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), span, top)
