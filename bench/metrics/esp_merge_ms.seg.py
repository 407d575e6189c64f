"""Device time under the program's ``esp.merge`` scope per frame: each ESP
module's HFF sums, concat, residual and BN/PReLU, in ESPNet frames.

``bench/program_trace.py`` sums only the ``engine``, ``layout``, ``grad``
and ``train`` families, so this reader makes its own pass over the run's
trace with that module's decoder, on the same terms: the newest trace,
trusted only where ``program_trace.read`` finds it is this run's; each op
by its own ``tf_op``; device time clipped to the window and averaged over
the devices.  A program without the scope reads nothing."""

from __future__ import annotations

import functools
import os
import re

from bench import program_trace

LAYER = "model step"
UNIT = "ms"
MOVES = "seg_frames_per_s"
SCOPE = "esp.merge"


@functools.lru_cache(maxsize=4)
def _scope_s(path: str, mtime_ns: int, size: int, scope: str) -> float:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    w0, w1 = program_trace.window(program_trace.host_spans(pd))
    pattern = re.compile(rf"\b{re.escape(scope)}\b")
    total, planes = 0.0, set()
    for plane, s, e, op, _ in program_trace.device_ops(
            pd, program_trace.op_metadata(path)):
        if e <= w0 or s >= w1:
            continue
        planes.add(plane)
        if op and pattern.search(op):
            total += (min(e, w1) - max(s, w0)) * 1e-9
    return total / len(planes) if planes else 0.0


def read(ctx, root=program_trace.TRACE_DIR):
    if program_trace.read(ctx, root) is None or not ctx["units"]:
        return None
    path = program_trace.newest_trace(root)
    st = os.stat(path)
    t = _scope_s(path, st.st_mtime_ns, st.st_size, SCOPE)
    return 1000.0 * t / ctx["units"] if t > 0 else None
