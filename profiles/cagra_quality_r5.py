"""Bisect the r5 build-quality regression at 1M: which knob recovers
r4's walk recall (0.96 @ itopk 24)?  Variants share the dataset/GT."""

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
    from raft_tpu.neighbors import brute_force, cagra

    n, dim, latent, nq, k = 1_000_000, 128, 16, 5000, 10
    rng = np.random.default_rng(0)
    Z = rng.normal(size=(n + nq, latent)).astype(np.float32)
    A = rng.normal(size=(latent, dim)).astype(np.float32) / np.sqrt(latent)
    X = (Z @ A).astype(np.float32)
    X += 0.05 * rng.normal(size=X.shape).astype(np.float32)
    db = jnp.asarray(X[:n])
    queries = jnp.asarray(X[n:])
    db.block_until_ready()
    res = DeviceResources(seed=0)

    _, gt = brute_force.knn(res, db, queries, k)
    gt = np.asarray(gt)
    sample = np.arange(0, n, 4001)[:250]
    _, ggt = brute_force.knn(res, db, db[sample], 129)
    ggt = np.asarray(ggt)[:, 1:]

    variants = {
        "A_default": {},
        "C_rev2": {"build_reverse_rounds": 2},
        "D_t64": {"build_n_probes": 64},
        "B_maxed": {"build_proj_dim": 128, "build_n_probes": 64,
                    "build_scan_recall": 0.98,
                    "build_reverse_rounds": 2},
    }
    for name, kw in variants.items():
        p = cagra.IndexParams(graph_degree=64, **kw)
        t0 = time.perf_counter()
        knn = cagra.build_knn_graph(res, db, p.intermediate_graph_degree,
                                    params=p)
        np.asarray(knn[0, 0])
        t_graph = time.perf_counter() - t0
        g = np.asarray(knn[sample])
        grec = (sum(len(set(a) & set(b)) for a, b in zip(g, ggt))
                / ggt.size)
        t0 = time.perf_counter()
        graph = cagra.prune(res, knn, p.graph_degree)
        np.asarray(graph[0, 0])
        t_prune = time.perf_counter() - t0
        index = cagra.Index(dataset=db, graph=graph, metric=p.metric)
        out = {"variant": name, "knn_s": round(t_graph, 1),
               "prune_s": round(t_prune, 1),
               "graph_recall128": round(grec, 4)}
        for itopk in (24, 64):
            sp = cagra.SearchParams(itopk_size=itopk, search_width=1)
            i = cagra.search(res, sp, index, queries, k)[1]
            rec = (sum(len(set(a) & set(b)) for a, b in
                       zip(np.asarray(i), gt)) / gt.size)
            out[f"walk_recall@{itopk}"] = round(rec, 4)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
