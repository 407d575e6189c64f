#!/usr/bin/env python3
"""What the program names in a traced run: device time per scope and host
time per span, read from the run's ``.xplane.pb``.

  python3 bench/program_trace.py <xplane.pb> [--top 10]

prints a report: device time per scope, each span's total and self time,
and the longest idle gaps of the device, each with the innermost ``gen.*``
or ``bench.*`` span around its middle.

The program names its work with ``repro.obs``: ``jax.named_scope``s
(``engine.*``, ``layout.*``, ``grad.*``, ``train.*``) that reach a TPU
trace as each device op's name stack, and host spans (``gen.*``,
``repro.compile``).  The metric readers get only ``ctx``, so
:func:`read` finds the run's trace itself (the newest ``*.xplane.pb``
under ``bench/.trace/``) and trusts it only where the window it rebuilds
from the harness's ``bench.*`` spans equals ``ctx["trace"]["window_s"]``.

* ``jax.profiler.ProfileData`` gives the events and their times, but not
  the stats of their metadata, where a TPU trace keeps an op's name stack
  (``tf_op``) and its executable (``program_id``).  A small reader of the
  protobuf wire format decodes those from the device planes, stepping over
  each ``XLine`` (the events) by its length.  The two join by event name;
  where two executables share a name, the ``XLA Modules`` event around the
  op (``jit_forward(<program id>)``) picks the one.
* Attribution is by a device op's own ``tf_op``: a fusion counts under the
  scopes of the instruction it is named after.  An op counts once under
  each scope it names, and once under each family (``layout``, ...).
* Device time is clipped to the window and averaged over the devices, as
  in ``bench/trace_reduce.py``; host time is that of the spans inside it.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import functools
import glob
import math
import os
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / "bench" / ".trace"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.trace_reduce import (DEVICE_PREFIX, OPS_LINE, is_pallas,  # noqa: E402
                                merge)

MODULES_LINE = "XLA Modules"
HARNESS = "bench."
SCOPE = re.compile(r"\b(?:engine|layout|grad|train)\.[a-z_]+")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# -------------------------------------------------------------- wire format

def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b, i: int, end: int):
    """``(field, value)`` of one message; a length-delimited value is its
    ``(start, end)`` in ``b``, not read."""
    while i < end:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = None, i + 8
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif kind == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(b, span):
    key = value = None
    for f, v in _fields(b, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(b, span, names: dict) -> tuple[str | None, object]:
    """An ``XStat``: its name and its integer or string value."""
    name = value = None
    for f, v in _fields(b, *span):
        if f == 1:
            name = names.get(v)
        elif f in (3, 4):               # uint64, int64
            value = v
        elif f == 5:                    # str
            value = _text(b, v)
        elif f == 7:                    # ref: the name of a stat metadata
            value = names.get(v)
    return name, value


def _device_metadata(b, span) -> tuple[str, dict]:
    """A device ``XPlane``: its name and ``{event name: [(program_id,
    tf_op)]}``; its lines are stepped over."""
    name, stat_names, events = "", {}, []
    for f, v in _fields(b, *span):
        if f == 2:
            name = _text(b, v)
            if not name.startswith(DEVICE_PREFIX):
                return name, {}
        elif f == 4:
            events.append(_map_entry(b, v)[1])
        elif f == 5:
            _, meta = _map_entry(b, v)
            sid = sname = None
            for g, w in _fields(b, *meta):
                if g == 1:
                    sid = w
                elif g == 2:
                    sname = _text(b, w)
            stat_names[sid] = sname
    ops = collections.defaultdict(list)
    for meta in events:
        ev_name, stats = None, {}
        for f, v in _fields(b, *meta):
            if f == 2:
                ev_name = _text(b, v)
            elif f == 5:
                k, val = _stat(b, v, stat_names)
                stats[k] = val
        if ev_name is not None:
            ops[ev_name].append((stats.get("program_id"), stats.get("tf_op")))
    return name, dict(ops)


def op_metadata(path: str) -> dict:
    """``{device plane: {event name: [(program_id, tf_op), ...]}}``;
    ``tf_op`` is ``None`` for ops the compiler adds (async copies)."""
    with open(path, "rb") as f:
        b = memoryview(f.read())
    out = {}
    for f, v in _fields(b, 0, len(b)):
        if f == 1:                      # XSpace.planes
            name, ops = _device_metadata(b, v)
            if name.startswith(DEVICE_PREFIX):
                out[name] = ops
    return out


# ------------------------------------------------------------------- join

def _tf_op(cands, modules, starts, t: float) -> str | None:
    """The ``tf_op`` of an op named by ``cands``, running at ``t``."""
    if len({op for _, op in cands}) == 1:
        return cands[0][1]
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][1] >= t:
        for pid, op in cands:
            if pid == modules[i][2]:
                return op
    return None


def device_ops(pd, meta: dict):
    """Every ``XLA Ops`` event of every device plane, as ``(plane, start_ns,
    end_ns, tf_op or None, pallas)``."""
    for plane in pd.planes:
        ops = meta.get(plane.name)
        if ops is None:
            continue
        lines = {line.name: line for line in plane.lines}
        modules = []
        if MODULES_LINE in lines:
            for e in lines[MODULES_LINE].events:
                m = _PROGRAM_ID.search(e.name)
                modules.append((e.start_ns, e.end_ns,
                                int(m.group(1)) if m else None))
        modules.sort()
        starts = [s for s, _, _ in modules]
        if OPS_LINE not in lines:
            continue
        pallas = {}                     # per op name: one metadata each
        for e in lines[OPS_LINE].events:
            name, start = e.name, e.start_ns
            if name not in pallas:
                pallas[name] = is_pallas(e)
            cands = ops.get(name)
            op = _tf_op(cands, modules, starts, start) if cands else None
            yield plane.name, start, e.end_ns, op, pallas[name]


@functools.lru_cache(maxsize=4096)
def scopes_of(tf_op: str | None) -> frozenset[str]:
    """The program's scopes in a name stack, e.g. ``engine.dense`` from
    ``jit(step)/transpose(jvp(engine.dense))/...``."""
    return frozenset(SCOPE.findall(tf_op)) if tf_op else frozenset()


def host_spans(pd):
    """Host events of the harness (``bench.*``) and the program (``gen.*``,
    ``repro.*``) as ``(start_ns, end_ns, name, line id)``."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith((HARNESS, "gen.", "repro.")):
                    out.append((e.start_ns, e.end_ns, e.name,
                                (plane.name, k)))
    return sorted(out)


def window(spans) -> tuple[float, float] | None:
    """From the first harness span's start to the last one's end."""
    h = [(s, e) for s, e, n, _ in spans if n.startswith(HARNESS)]
    if not h:
        return None
    return h[0][0], max(e for _, e in h)


def self_times(spans, w0: float, w1: float) -> dict:
    """Per span name: ``[count, total s, self s]`` inside the window; self
    time leaves out the spans nested in it on its line."""
    out = collections.defaultdict(lambda: [0, 0.0, 0.0])
    by_line = collections.defaultdict(list)
    for s, e, n, line in spans:
        if s >= w0 and e <= w1:
            by_line[line].append((s, e, n))
    for rows in by_line.values():
        rows.sort(key=lambda r: (r[0], -r[1]))
        stack = []                      # the enclosing spans: (end, name)
        for s, e, n in rows:
            while stack and stack[-1][0] <= s:
                stack.pop()
            d = (e - s) * 1e-9
            out[n][0] += 1
            out[n][1] += d
            out[n][2] += d
            if stack:
                out[stack[-1][1]][2] -= d
            stack.append((e, n))
    return {k: list(v) for k, v in out.items()}


# ---------------------------------------------------------------- summary

def summarize(path: str, top: int = 20) -> dict | None:
    """Device seconds per scope and family, host spans, the ``top``
    longest idle gaps; ``None`` where the trace holds no harness span or no
    device op."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = host_spans(pd)
    win = window(spans)
    if win is None:
        return None
    w0, w1 = win
    meta = op_metadata(path)
    scope_s = collections.Counter()
    family_s = collections.Counter()
    ops = pallas = named = pallas_engines = 0
    intervals = collections.defaultdict(list)
    for plane, s, e, op, is_kernel in device_ops(pd, meta):
        ops += 1
        named += op is not None
        names = scopes_of(op)
        if is_kernel:
            pallas += 1
            pallas_engines += sum(x.startswith("engine.") for x in names) == 1
        if e <= w0 or s >= w1:
            continue
        d = (min(e, w1) - max(s, w0)) * 1e-9
        intervals[plane].append((max(s, w0), min(e, w1)))
        for x in names:
            scope_s[x] += d
        for fam in {x.split(".")[0] for x in names}:
            family_s[fam] += d
    if not intervals:
        return None
    n_dev = len(intervals)
    first = merge(intervals[min(intervals)])
    gaps, prev = [], w0
    for s, e in first + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "devices": n_dev,
        "ops": ops, "ops_named": named,
        "pallas_ops": pallas, "pallas_one_engine": pallas_engines,
        "scope_s": {k: v / n_dev for k, v in scope_s.items()},
        "family_s": {k: v / n_dev for k, v in family_s.items()},
        "spans": self_times(spans, w0, w1),
        "gaps": [(s, e, _innermost(spans, (s + e) / 2))
                 for s, e in gaps[:top]],
    }


def _innermost(spans, t: float) -> str:
    inside = [(e - s, n) for s, e, n, _ in spans
              if s <= t <= e and n.startswith((HARNESS, "gen."))]
    return min(inside)[1] if inside else "outside spans"


@functools.lru_cache(maxsize=4)
def _cached(path: str, mtime_ns: int, size: int) -> dict | None:
    return summarize(path)


def newest_trace(root: pathlib.Path = TRACE_DIR) -> str | None:
    paths = glob.glob(str(root / "**" / "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def read(ctx, root: pathlib.Path = TRACE_DIR) -> dict | None:
    """The summary of this run's trace, or ``None`` where the newest trace
    is not this run's (its window differs from ``ctx``'s) or holds none of
    the program's names."""
    path = newest_trace(root)
    if path is None:
        return None
    st = os.stat(path)
    try:
        s = _cached(path, st.st_mtime_ns, st.st_size)
    except (ValueError, IndexError, KeyError) as e:    # a trace it cannot read
        print(f"program_trace: {path}: {e!r}", file=sys.stderr)
        return None
    if s is None or not math.isclose(s["window_s"], ctx["trace"]["window_s"],
                                     rel_tol=1e-9, abs_tol=1e-9):
        return None
    return s


def device_ms(ctx, scope: str, root: pathlib.Path = TRACE_DIR):
    """Device ms per unit under ``scope`` (``engine.dense``) or a family
    (``layout``); ``None`` where the trace holds none."""
    s = read(ctx, root)
    if s is None or not ctx["units"]:
        return None
    t = (s["scope_s"] if "." in scope else s["family_s"]).get(scope, 0.0)
    return 1000.0 * t / ctx["units"] if t > 0 else None


def span_ms(ctx, name: str, root: pathlib.Path = TRACE_DIR):
    """Host ms per unit in ``name`` spans; ``None`` where there are none."""
    s = read(ctx, root)
    if s is None or not ctx["units"] or name not in s["spans"]:
        return None
    return 1000.0 * s["spans"][name][1] / ctx["units"]


def span_count(ctx, name: str, root: pathlib.Path = TRACE_DIR):
    """How many ``name`` spans the window holds; ``None`` where the
    program left no ``gen.*`` span (it is not instrumented)."""
    s = read(ctx, root)
    if s is None or not any(k.startswith("gen.") for k in s["spans"]):
        return None
    return s["spans"].get(name, [0])[0]


# ----------------------------------------------------------------- report

def report(path: str, top: int = 10) -> str:
    s = summarize(path, top)
    if s is None:
        return f"{path}: no harness span or no device op"
    ms = lambda t: f"{1000.0 * t:12.3f} ms"  # noqa: E731
    out = [f"{path}", f"window {ms(s['window_s'])}, {s['devices']} device(s),"
           f" {s['ops_named']}/{s['ops']} ops named, "
           f"{s['pallas_one_engine']}/{s['pallas_ops']} Pallas ops under one"
           f" engine", "", "device time per scope (window, per device):"]
    for k, v in sorted(s["family_s"].items()):
        out.append(f"  {k + '.*':28s}{ms(v)}")
    for k, v in sorted(s["scope_s"].items()):
        out.append(f"  {k:28s}{ms(v)}")
    out += ["", "host spans: count, total, self time:"]
    for k, (n, tot, own) in sorted(s["spans"].items()):
        out.append(f"  {k:28s}{n:8d}{ms(tot)}{ms(own)}")
    out += ["", f"longest idle gaps of the device ({top}):"]
    for g0, g1, name in s["gaps"][:top]:
        out.append(f"  {ms((g1 - g0) * 1e-9)}  in {name}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--top", type=int, default=10)
    ns = ap.parse_args(argv)
    print(report(ns.xplane, ns.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
