"""Warm per-stage breakdown of the clustered CAGRA build at 1M.

Round-5 version hand-replicated _build_knn_graph_clustered with forced
syncs between stages; now the build itself is instrumented
(raft_tpu.observability stages fence at every stage boundary when
collection is on), so this just runs the REAL build twice under
``obs.collecting()`` and prints each build's attached stage report —
second run reported warm so compiles are excluded (run 0 also carries
the ``xla.*`` compile timers captured via jax.monitoring).
"""

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main():
    import jax
    from raft_tpu.core.platform import setup_compile_cache
    setup_compile_cache()
    import jax.numpy as jnp
    from raft_tpu import DeviceResources
    from raft_tpu import observability as obs
    from raft_tpu.neighbors import cagra

    n, dim, latent = 1_000_000, 128, 16
    rng = np.random.default_rng(0)
    Z = rng.normal(size=(n, latent)).astype(np.float32)
    A = rng.normal(size=(latent, dim)).astype(np.float32) / np.sqrt(latent)
    X = (Z @ A).astype(np.float32)
    X += 0.05 * rng.normal(size=X.shape).astype(np.float32)
    db = jnp.asarray(X)
    db.block_until_ready()
    res = DeviceResources(seed=0)
    p = cagra.IndexParams(graph_degree=64)

    for run in range(2):
        obs.reset()
        t_all = time.perf_counter()
        with obs.collecting():
            index = cagra.build(res, p, db)
            np.asarray(index.graph[0, 0])
        total_s = time.perf_counter() - t_all
        rep = obs.build_report(index)
        snap = obs.snapshot()
        print(json.dumps({
            "run": run,
            "total_s": round(total_s, 1),
            "stages": {name: {"count": t["count"],
                              "total_s": round(t["total_s"], 1)}
                       for name, t in sorted(rep["stages"].items())},
            "counters": rep["counters"],
            # run 0 only: XLA compile time captured via jax.monitoring
            "xla_compile_s": round(sum(
                t["total_s"] for name, t in snap["timers"].items()
                if name.startswith("xla.")), 1),
        }), flush=True)


if __name__ == "__main__":
    main()
