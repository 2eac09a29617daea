"""Measure graph-walk refinement rounds at 1M: graph recall and walk
recall per round count, with stage timings."""

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

    kg = 129
    p = cagra.IndexParams(graph_degree=64, build_walk_rounds=0)

    def grec(knn):
        g = np.asarray(knn[sample])[:, 1:]  # drop self col for fairness
        return round(sum(len(set(a) & set(b))
                         for a, b in zip(g, ggt)) / ggt.size, 4)

    t0 = time.perf_counter()
    knn = cagra._build_knn_graph_clustered(res, db, kg, p)
    np.asarray(knn[0, 0])
    print(json.dumps({"stage": "scan+rev", "s": round(
        time.perf_counter() - t0, 1), "graph_recall": grec(knn)}),
        flush=True)

    pdim, knn_d = 16, None
    for r in range(1, 4):
        t0 = time.perf_counter()
        knn, knn_d = cagra._graph_refine_round(res, db, knn, kg, p.metric,
                                               pdim, p.build_walk_iters,
                                               knn_d=knn_d)
        np.asarray(knn[0, 0])
        out = {"stage": f"walk_round{r}",
               "s": round(time.perf_counter() - t0, 1),
               "graph_recall": grec(knn)}
        print(json.dumps(out), flush=True)

    # full pipeline check: prune + search recall at the usual points
    ids = jnp.arange(n, dtype=knn.dtype)[:, None]
    order = jnp.argsort(knn == ids, axis=1, stable=True)
    knn_ns = jnp.take_along_axis(knn, order, axis=1)[:, :128].astype(
        jnp.int32)
    t0 = time.perf_counter()
    graph = cagra.prune(res, knn_ns, 64)
    np.asarray(graph[0, 0])
    print(json.dumps({"stage": "prune",
                      "s": round(time.perf_counter() - t0, 1)}),
          flush=True)
    index = cagra.Index(dataset=db, graph=graph, metric=p.metric)
    for itopk in (16, 24, 32, 64):
        sp = cagra.SearchParams(itopk_size=itopk, search_width=1)
        i = index and cagra.search(res, sp, index, queries, k)[1]
        rec = (sum(len(set(a) & set(b)) for a, b in
                   zip(np.asarray(i), gt)) / gt.size)
        t0 = time.perf_counter()
        for _ in range(3):
            i = cagra.search(res, sp, index, queries, k)[1]
        np.asarray(i)
        qps = nq / ((time.perf_counter() - t0) / 3)
        print(json.dumps({"itopk": itopk, "recall": round(rec, 4),
                          "qps": round(qps, 1)}), flush=True)


if __name__ == "__main__":
    main()
