"""Diagnose the IVF-Flat nlist=16384 regression (VERDICT r5 item 3):
profile the coarse ranking and the grouped scan separately at the two
conf operating points (4096/np128 vs 16384/np256, equal recall)."""

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
    from raft_tpu.neighbors import ivf_flat, grouped

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

    def timeit(fn, reps=5):
        np.asarray(jax.tree_util.tree_leaves(fn())[0])
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        np.asarray(jax.tree_util.tree_leaves(out)[0])
        return (time.perf_counter() - t0) / reps * 1000

    for nlist, nprobe in ((4096, 128), (16384, 256)):
        t0 = time.perf_counter()
        index = ivf_flat.build(
            res, ivf_flat.IndexParams(n_lists=nlist), db)
        np.asarray(index.list_sizes[0])
        build_s = time.perf_counter() - t0
        cap = index.capacity

        coarse_ms = timeit(lambda: ivf_flat._select_clusters(
            index.centers, queries, nprobe, index.metric))
        probes = ivf_flat._select_clusters(index.centers, queries,
                                           nprobe, index.metric)
        ng = int(grouped.num_groups(probes, nlist))
        search_ms = timeit(lambda: ivf_flat.search(
            res, ivf_flat.SearchParams(n_probes=nprobe), index,
            queries, k))
        print(json.dumps({
            "nlist": nlist, "nprobe": nprobe, "cap": cap,
            "build_s": round(build_s, 1), "n_groups": ng,
            "pairs": nq * nprobe,
            "coarse_ms": round(coarse_ms, 1),
            "search_ms": round(search_ms, 1),
            "qps": round(nq / (search_ms / 1000), 1)}), flush=True)


if __name__ == "__main__":
    main()
