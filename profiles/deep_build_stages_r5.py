"""Stage-by-stage 10M CAGRA build with forced syncs — pinpoints the
OOM stage the fused conf run hides behind async dispatch."""

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
    from raft_tpu.neighbors import cagra

    n, dim, latent = 10_000_000, 96, 16
    rng = np.random.default_rng(0)
    Z = rng.normal(size=(n, latent)).astype(np.float32)
    A = rng.normal(size=(latent, dim)).astype(np.float32) / np.sqrt(latent)
    X = (Z @ A).astype(np.float32)
    X += 0.05 * rng.normal(size=X.shape).astype(np.float32)
    del Z
    db = jnp.asarray(X)
    del X
    db.block_until_ready()
    res = DeviceResources(seed=0)
    p = cagra.IndexParams(graph_degree=32,
                          intermediate_graph_degree=64,
                          build_n_probes=12)
    kg = 65
    xf = db

    def stage(name, fn):
        t0 = time.perf_counter()
        out = fn()
        print(json.dumps({"stage": name,
                          "s": round(time.perf_counter() - t0, 1)}),
              flush=True)
        return out

    n_lists = max(min(n // 64, 4 * int(np.sqrt(n))), 8)
    C = max(int(p.build_refine_rate * kg), kg)
    pdim, vecs = stage("calib", lambda: cagra._build_pdim(
        db, p.metric, kg, C))
    np.asarray(vecs[0, 0])
    print(json.dumps({"pdim": int(pdim)}), flush=True)
    proj = (vecs[:, dim - pdim:] if pdim < dim
            else jnp.eye(dim, dtype=jnp.float32))
    xp32 = xf @ proj
    bal = kmeans_balanced.KMeansBalancedParams(
        n_iters=10, metric=DistanceType.L2Expanded)
    n_train = min(n, max(n_lists * 8, max(65536, n // 10)))
    trainset = xp32[::max(n // n_train, 1)][:n_train]
    centers = stage("kmeans_fit", lambda: jax.block_until_ready(
        kmeans_balanced.fit(res, bal, trainset, n_lists)))
    labels = stage("predict", lambda: jax.block_until_ready(
        kmeans_balanced.predict(res, bal, xp32, centers)))
    sizes = jax.ops.segment_sum(jnp.ones(n, jnp.int32), labels,
                                num_segments=n_lists)
    cap = max(-(-int(jnp.max(sizes)) // 8) * 8, 8)
    print(json.dumps({"n_lists": n_lists, "cap": cap}), flush=True)
    P_proj, P_sq, P_id = stage("layout", lambda: jax.block_until_ready(
        cagra._build_layout(xf, xp32, labels, n_lists, cap)))
    del xp32
    mean = max(n / n_lists, 1.0)
    t = min(n_lists, max(p.build_n_probes,
                         -(-p.build_candidates // int(mean))))
    nbrs = cagra._center_neighbors(centers, t, False)
    print(json.dumps({"t": t}), flush=True)

    LB = max(1, min(8, (256 << 20) // max(cap * t * cap * 4, 1)))
    CH = cagra._SCAN_LISTS_PER_DISPATCH
    n_pad = -(-n_lists // (LB * CH)) * (LB * CH) \
        if n_lists > LB * CH else -(-n_lists // LB) * LB
    ids = np.minimum(np.arange(n_pad, dtype=np.int32), n_lists - 1)

    def scan():
        knn = jnp.full((n, kg), -1, jnp.int32)
        for s in range(0, n_pad, LB * CH):
            cid = jnp.asarray(ids[s:s + LB * CH])
            out_c = cagra._scan_chunk(P_proj, P_sq, P_id, nbrs, cid,
                                      cap, kg, False, LB,
                                      rt=p.build_scan_recall)
            rows = P_id[cid].reshape(-1)
            rows = jnp.where(rows >= 0, rows, n)
            knn = knn.at[rows].set(out_c.reshape(-1, kg), mode="drop")
        return jax.block_until_ready(knn)

    knn = stage("scan", scan)
    del P_proj, P_sq, P_id
    rev = stage("rev_host", lambda: cagra._reverse_edges_auto(
        knn, n, min(kg, 64)))
    knn = stage("rev_merge", lambda: jax.block_until_ready(
        cagra._merge_refine_inplace(db, knn, rev, kg, False)))
    del rev
    for r in range(p.build_walk_rounds):
        knn = stage(f"walk{r}", lambda: jax.block_until_ready(
            cagra._deep_walk_round(db, knn, kg, p.metric, pdim,
                                   p.build_walk_iters)))
    graph = stage("prune", lambda: jax.block_until_ready(
        cagra.prune(res, jnp.take_along_axis(
            knn, jnp.argsort(knn == jnp.arange(n, dtype=knn.dtype)[:, None],
                             axis=1, stable=True), axis=1
        )[:, :64].astype(jnp.int32), 32)))
    print(json.dumps({"graph_shape": list(graph.shape)}), flush=True)


if __name__ == "__main__":
    main()
