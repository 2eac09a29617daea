"""Record the small chip trace that ``test_spans.py`` checks the library's
own host spans on, and print what they show.

    python3 benchmark/tests/record_spans.py <out_dir>

Runs on a TPU, under the default validation policy (``raise``): a small
IVF-PQ index searched on its fused scan and refined, then one batch served
through ``serving.Server`` over a small CAGRA index, inside the
benchmark's ``bench.window`` annotation.  Writes
``<out_dir>/spans.xplane.pb`` and prints one JSON document:

- ``spans`` / ``idle_in``: :func:`benchmark.spans.reduce` of the trace;
- ``transfers``: each device-to-host transfer the runtime made, with the
  ``raft_tpu:`` and ``bench.`` spans open on any thread when it began, to
  find the host syncs that no ``*.sync`` or readback span holds;
- ``clock``: where a host annotation opened, by ``time.time_ns()`` taken
  just before it, against the trace's ``profile_start_time`` plus the
  event's offset, in microseconds.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from benchmark import data, spans, trace_reduce  # noqa: E402

TRANSFER = "D2H Dispatch"
CLOCK = "bench.clock"


def transfers(path: str) -> list:
    from jax.profiler import ProfileData

    open_, moves = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith((spans.PREFIX, "bench.")):
                    open_.append((ev.name, ev.start_ns, ev.end_ns))
                elif ev.name == TRANSFER:
                    moves.append(ev.start_ns)
    return [{"at_ms": t * 1e-6,
             "in": sorted(n for n, s, e in open_ if s <= t <= e)}
            for t in sorted(moves)]


def clock_check_us(path: str, wall_ns: int) -> float:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    start = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                 if p.name == "Task Environment")
    ev = next(e for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events if e.name == CLOCK)
    return (start + ev.start_ns - wall_ns) * 1e-3


def main() -> int:
    out_dir = Path(sys.argv[1])
    out_dir.mkdir(parents=True, exist_ok=True)
    from raft_tpu import DeviceResources, serving
    from raft_tpu.neighbors import cagra, ivf_pq
    from raft_tpu.neighbors.refine import refine

    res = DeviceResources(seed=0)
    db, pool = data.make(7, {"n_db": 20000, "n_queries": 256, "dim": 128,
                             "latent_dim": 16, "noise": 0.05})
    pq = ivf_pq.build(res, ivf_pq.IndexParams(n_lists=64, pq_dim=64), db)
    sp = ivf_pq.SearchParams(n_probes=8)
    cg = cagra.build(res, cagra.IndexParams(
        graph_degree=32, intermediate_graph_degree=64), db)
    ex = serving.Executor(res, "cagra", cg, ks=(10,), max_batch=8,
                          search_params=cagra.SearchParams(
                              itopk_size=64, search_width=1), warm="jit")
    srv = serving.Server(ex, serving.ServerConfig(max_batch=8,
                                                  max_wait_us=1000)).start()
    requests = np.asarray(pool[:5])

    def step():
        with TraceAnnotation("bench.search"):
            _, i = ivf_pq.search(res, sp, pq, pool, 20)
        with TraceAnnotation("bench.refine"):
            _, i = refine(res, db, pool, i, 10)
        with TraceAnnotation("bench.readback"):
            np.asarray(i)
        srv.search(requests, 10)

    try:
        step()
        tdir = out_dir / "raw"
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        with TraceAnnotation(trace_reduce.WINDOW):
            wall_ns = time.time_ns()
            with TraceAnnotation(CLOCK):
                pass
            step()
        jax.profiler.stop_trace()
    finally:
        srv.stop()
    path = out_dir / "spans.xplane.pb"
    shutil.copy(trace_reduce.find_xplane(str(tdir)), path)
    shutil.rmtree(tdir)
    s = spans.reduce(str(path))
    print(json.dumps({"window_s": s.window_s, "idle_s": s.idle_s,
                      "spans": s.spans, "idle_in": s.idle_in,
                      "longest": s.longest,
                      "transfers": transfers(str(path)),
                      "clock_check_us": clock_check_us(str(path), wall_ns)},
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
