"""Measure the clustered-build candidate-pool ceiling at 1M: what
fraction of the exact top-kg neighbors live inside the union of the
query's list's top-t neighbor lists, for a sample of queries."""

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
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.distance.types import DistanceType
    from raft_tpu.neighbors import brute_force, cagra

    n, dim, latent = 1_000_000, 128, 16
    rng = np.random.default_rng(0)
    Z = rng.normal(size=(n, latent)).astype(np.float32)
    A = rng.normal(size=(latent, dim)).astype(np.float32) / np.sqrt(latent)
    X = (Z @ A).astype(np.float32)
    X += 0.05 * rng.normal(size=X.shape).astype(np.float32)
    db = jnp.asarray(X)
    db.block_until_ready()
    res = DeviceResources(seed=0)
    kg = 129

    n_lists = max(min(n // 64, 4 * int(np.sqrt(n))), 8)
    bal = kmeans_balanced.KMeansBalancedParams(
        n_iters=10, metric=DistanceType.L2Expanded)
    n_train = min(n, max(n_lists * 8, max(65536, n // 10)))
    t0 = time.perf_counter()
    trainset = db[::max(n // n_train, 1)][:n_train]
    centers = kmeans_balanced.fit(res, bal, trainset, n_lists)
    labels = np.asarray(kmeans_balanced.predict(res, bal, db, centers))
    print(json.dumps({"cluster_s": round(time.perf_counter() - t0, 1),
                      "n_lists": n_lists}), flush=True)

    sample = np.arange(0, n, 4001)[:250]
    _, gt = brute_force.knn(res, db, db[sample], kg)
    gt = np.asarray(gt)

    for t in (32, 48, 64, 96):
        nbrs = np.asarray(cagra._center_neighbors(centers, t, False))
        ok = tot = 0
        for qi, g in zip(sample, gt):
            cl = set(nbrs[labels[qi]].tolist())
            ok += sum(labels[j] in cl for j in g)
            tot += len(g)
        print(json.dumps({"t": t, "ceiling": round(ok / tot, 4)}),
              flush=True)


if __name__ == "__main__":
    main()
