"""Record the small chip trace that ``test_trace_reduce.py`` checks the
reduction on, and print what the trace holds (planes, lines, the names
and stats of its device events) for reading by hand.

    python3 benchmark/tests/record_trace.py <out_dir>

Runs on a TPU: a small IVF-PQ index searched on its fused scan and
refined, and a small CAGRA index walked on its fused hop, inside the
benchmark's ``bench.window`` annotation.  Writes ``<out_dir>/small.xplane.pb``
and ``<out_dir>/trace_dump.json``.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from benchmark import data, trace_reduce  # noqa: E402


def dump(path: str) -> dict:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        p = {"plane": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for ev in evs:
                if ev.name not in names:
                    names[ev.name] = {
                        "first_start_ns": ev.start_ns,
                        "duration_ns": ev.duration_ns,
                        "stats": {k: str(v) for k, v in ev.stats}}
            p["lines"].append({
                "line": line.name, "events": len(evs),
                "start_ns": min((e.start_ns for e in evs), default=None),
                "end_ns": max((e.start_ns + e.duration_ns for e in evs),
                              default=None),
                "names": dict(list(names.items())[:60])})
        out.append(p)
    return {"planes": out}


def main() -> int:
    out_dir = Path(sys.argv[1])
    out_dir.mkdir(parents=True, exist_ok=True)
    from raft_tpu import DeviceResources
    from raft_tpu.neighbors import cagra, ivf_pq
    from raft_tpu.neighbors.refine import refine

    res = DeviceResources(seed=0)
    db, pool = data.make(7, {"n_db": 20000, "n_queries": 256, "dim": 128,
                             "latent_dim": 16, "noise": 0.05})
    pq = ivf_pq.build(res, ivf_pq.IndexParams(n_lists=64, pq_dim=64), db)
    sp = ivf_pq.SearchParams(n_probes=8)
    cg = cagra.build(res, cagra.IndexParams(
        graph_degree=32, intermediate_graph_degree=64), db)
    csp = cagra.SearchParams(itopk_size=64, search_width=1)

    def step():
        with TraceAnnotation("bench.search"):
            _, i = ivf_pq.search(res, sp, pq, pool, 20)
        with TraceAnnotation("bench.refine"):
            d, i = refine(res, db, pool, i, 10)
        with TraceAnnotation("bench.readback"):
            np.asarray(i)
        with TraceAnnotation("bench.dispatch"):
            _, ci = cagra.search(res, csp, cg, pool[:8], 10)
        with TraceAnnotation("bench.readback"):
            np.asarray(ci)

    step()
    tdir = out_dir / "raw"
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    with TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(2):
            step()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tdir))
    shutil.copy(path, out_dir / "small.xplane.pb")
    shutil.rmtree(tdir)
    with open(out_dir / "trace_dump.json", "w") as f:
        json.dump(dump(str(out_dir / "small.xplane.pb")), f, indent=1)
    s = trace_reduce.reduce(str(out_dir / "small.xplane.pb"))
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s,
                      "n_devices": s.n_devices, "ops": s.top_ops(40),
                      "modules": sorted(s.modules.items(),
                                        key=lambda kv: -kv[1])[:20],
                      "gaps": s.top_gaps()}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
