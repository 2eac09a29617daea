"""Ceiling of the candidate pool vs clustering granularity at 1M:
for several n_lists, what fraction of the exact top-129 lives in
(a) the query's LIST's top-t lists (per-list probing — the r5 scan),
(b) the QUERY's own top-t lists (per-query probing — the reference),
with t sized for a ~8k/16k-row candidate pool."""

import json
import sys
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
    from raft_tpu.neighbors import brute_force

    n, dim, latent = 1_000_000, 128, 16
    rng = np.random.default_rng(0)
    Z = rng.normal(size=(n, latent)).astype(np.float32)
    A = rng.normal(size=(latent, dim)).astype(np.float32) / np.sqrt(latent)
    X = (Z @ A).astype(np.float32)
    X += 0.05 * rng.normal(size=X.shape).astype(np.float32)
    db = jnp.asarray(X)
    db.block_until_ready()
    res = DeviceResources(seed=0)

    sample = np.arange(0, n, 4001)[:250]
    _, ggt = brute_force.knn(res, db, db[sample], 129)
    ggt = np.asarray(ggt)

    bal = kmeans_balanced.KMeansBalancedParams(
        n_iters=10, metric=DistanceType.L2Expanded)
    for n_lists in (500, 1000, 2000, 4000):
        n_train = min(n, max(n_lists * 8, max(65536, n // 10)))
        trainset = db[::max(n // n_train, 1)][:n_train]
        centers = kmeans_balanced.fit(res, bal, trainset, n_lists)
        labels = np.asarray(kmeans_balanced.predict(res, bal, db, centers))
        cnp = np.asarray(centers)
        c_sq = (cnp * cnp).sum(1)
        # per-list ranking (center-center) and per-query ranking
        for pool_target in (8192, 16384):
            t = max(4, int(round(pool_target / (n / n_lists))))
            t = min(t, n_lists)
            dcc = c_sq[None, :] - 2.0 * (cnp @ cnp.T)
            np.fill_diagonal(dcc, -np.inf)
            nbrs = np.argsort(dcc, axis=1)[:, :t]
            member = [set(r.tolist()) for r in nbrs]
            q = X[sample]
            dqc = c_sq[None, :] - 2.0 * (q @ cnp.T)
            qnbrs = np.argsort(dqc, axis=1)[:, :t]
            okl = okq = tot = 0
            for row, qi, g in zip(range(len(sample)), sample, ggt):
                cl = member[labels[qi]]
                cq = set(qnbrs[row].tolist())
                for j in g:
                    lj = labels[j]
                    okl += lj in cl
                    okq += lj in cq
                tot += len(g)
            print(json.dumps({
                "n_lists": n_lists, "t": t, "pool": pool_target,
                "per_list": round(okl / tot, 4),
                "per_query": round(okq / tot, 4)}), flush=True)


if __name__ == "__main__":
    main()
