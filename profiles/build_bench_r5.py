"""Round-5 CAGRA build timing breakdown at 1M x 128 (graph / prune /
pack), plus search QPS spot-check — the VERDICT r5 item-1 gate
(build_s <= 60 with unchanged search QPS/recall)."""

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

    p = cagra.IndexParams(graph_degree=64)

    t0 = time.perf_counter()
    knn = cagra.build_knn_graph(res, db, p.intermediate_graph_degree,
                                params=p)
    np.asarray(knn[0, 0])
    t_graph = time.perf_counter() - t0
    print(json.dumps({"stage": "knn_graph", "s": round(t_graph, 1)}),
          flush=True)

    t0 = time.perf_counter()
    graph = cagra.prune(res, knn, p.graph_degree)
    np.asarray(graph[0, 0])
    t_prune = time.perf_counter() - t0
    print(json.dumps({"stage": "prune", "s": round(t_prune, 1)}),
          flush=True)
    index = cagra.Index(dataset=db, graph=graph, metric=p.metric)

    # graph quality: recall of knn graph vs exact on a sample
    _, gt = brute_force.knn(res, db, queries, k)
    gt = np.asarray(gt)

    # walk-table build (the "pack" stage) happens on first search
    sp = cagra.SearchParams(itopk_size=24, search_width=1)
    t0 = time.perf_counter()
    i = cagra.search(res, sp, index, queries, k)[1]
    np.asarray(i)
    t_pack = time.perf_counter() - t0
    print(json.dumps({"stage": "pack+first_search",
                      "s": round(t_pack, 1)}), flush=True)

    for itopk in (16, 24, 32, 64):
        sp = cagra.SearchParams(itopk_size=itopk, search_width=1)
        i = cagra.search(res, sp, index, queries, k)[1]
        rec = (sum(len(set(a) & set(b)) for a, b in
                   zip(np.asarray(i), gt)) / gt.size)
        t0 = time.perf_counter()
        for _ in range(3):
            i = cagra.search(res, sp, index, queries, k)[1]
        np.asarray(i)
        qps = nq / ((time.perf_counter() - t0) / 3)
        print(json.dumps({"itopk": itopk, "recall": round(rec, 4),
                          "qps": round(qps, 1)}), flush=True)

    print(json.dumps({"build_total_s": round(t_graph + t_prune, 1)}),
          flush=True)


if __name__ == "__main__":
    main()
