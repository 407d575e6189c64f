#!/usr/bin/env python3
"""Record the small chip trace that ``bench/tests/test_trace_reduce.py`` reads.

  python bench/record_test_trace.py [--out bench/.trace/test_trace]

On one TPU: ENet at 64x64 (19 classes) runs twice through the compiled
Pallas engines under the harness span ``bench.frame``, then a DCGAN-64
generator at ngf=8 answers one batch of 4 through ``GenServer`` under
``bench.step``.  The ``.xplane.pb`` is copied to ``<out>/small.xplane.pb``,
and a summary of its planes, lines and busiest events is printed, so the
trace can be read by hand before code is written against it.  Exits
non-zero where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def summarize(path: str) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)} "
              f"stats={list(plane.stats)[:8]}")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.end_ns for e in evs)
            print(f"  LINE {line.name!r} events={len(evs)} "
                  f"span_ns=({t0}, {t1})")
            by_name = collections.Counter()
            for e in evs:
                by_name[e.name] += e.duration_ns
            for name, ns in by_name.most_common(6):
                ex = next(e for e in evs if e.name == name)
                print(f"    {ns:>12.0f} ns  {name!r}  stats={list(ex.stats)[:10]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(ROOT / "bench" / ".trace" / "test_trace"))
    ns = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["REPRO_AUTOTUNE"] = "off"

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_test_trace: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.serve_gen import GenServer
    from repro.models import dcgan, enet

    params = enet.init_params(jax.random.PRNGKey(0), 19)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64, 3))
    gp = dcgan.init_params(jax.random.PRNGKey(2), size=64, nz=100, ngf=8)
    out = pathlib.Path(ns.out)
    raw = out / "raw"
    shutil.rmtree(out, ignore_errors=True)
    with jax.default_matmul_precision("highest"):
        fwd = lambda: enet.forward(params, x, backend="pallas",
                                   interpret=False)
        jax.block_until_ready(fwd())
        srv = GenServer(batch=4, backend="pallas", interpret=False,
                        dcgan_ngf=8, params={"dcgan64": gp})
        for i in range(4):
            srv.submit("dcgan64", seed=i)
        srv.run()
        jax.profiler.start_trace(str(raw))
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.frame"):
                jax.block_until_ready(fwd())
        for i in range(4):
            srv.submit("dcgan64", seed=10 + i)
        with jax.profiler.TraceAnnotation("bench.step"):
            srv.step()
        jax.profiler.stop_trace()
    path = glob.glob(str(raw / "**" / "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, out / "small.xplane.pb")
    print(f"trace {path} bytes={os.path.getsize(path)}")
    summarize(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
