"""MNMG algorithm tests on the virtual 8-device mesh (the reference's
LocalCUDACluster-without-a-cluster strategy, SURVEY.md §4) — distributed
results must match the single-device algorithms.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import observability as obs
from raft_tpu.cluster import KMeansParams, kmeans
from raft_tpu.comms import CommsSession
from raft_tpu.distributed import kmeans as dist_kmeans
from raft_tpu.distributed import knn as dist_knn
from raft_tpu.neighbors import ivf_pq
from raft_tpu.random import make_blobs
from raft_tpu.serving import buckets as serving_buckets


@pytest.fixture
def session(mesh8):
    s = CommsSession(mesh=mesh8, axis_name="data").init()
    yield s
    s.destroy()


@pytest.fixture
def handle(session):
    return session.worker_handle(seed=0)


class TestDistributedKMeans:
    def test_matches_single_device(self, res, handle):
        X, _ = make_blobs(1600, 8, n_clusters=5, cluster_std=0.5, seed=2)
        X = np.asarray(X)
        c0 = X[:5].copy()
        params = KMeansParams(n_clusters=5, max_iter=50, tol=1e-6,
                              init=1)  # will be overridden by Array path
        from raft_tpu.cluster.kmeans_types import InitMethod
        params.init = InitMethod.Array
        dc, dinertia, dn = dist_kmeans.fit(handle, params, X,
                                           centroids=jnp.asarray(c0))
        sc, sinertia, sn = kmeans.fit(res, params, X, centroids=c0)
        # same init, same Lloyd updates -> same fixed point
        np.testing.assert_allclose(float(dinertia), float(sinertia),
                                   rtol=1e-3)
        # centroids equal up to ordering (same init -> same order)
        np.testing.assert_allclose(np.asarray(dc), np.asarray(sc),
                                   rtol=1e-3, atol=1e-3)

    def test_predict(self, handle):
        X, _ = make_blobs(800, 4, n_clusters=4, cluster_std=0.3, seed=3)
        X = np.asarray(X)
        from raft_tpu.cluster.kmeans_types import InitMethod
        params = KMeansParams(n_clusters=4, max_iter=30,
                              init=InitMethod.Array)
        c, _, _ = dist_kmeans.fit(handle, params, X,
                                  centroids=jnp.asarray(X[:4]))
        labels = dist_kmeans.predict(handle, params, X, c)
        labels = np.asarray(labels)
        d = ((X[:, None, :] - np.asarray(c)[None, :, :]) ** 2).sum(-1)
        np.testing.assert_array_equal(labels, d.argmin(1))

    def test_requires_comms(self, res):
        X = np.zeros((64, 4), np.float32)
        from raft_tpu.core.error import RaftError
        with pytest.raises(RaftError, match="comms"):
            dist_kmeans.fit(res, KMeansParams(n_clusters=2), X)


class TestDistributedKnn:
    def test_matches_single_device(self, res, handle):
        rng = np.random.default_rng(0)
        db = rng.normal(size=(1024, 16)).astype(np.float32)
        q = rng.normal(size=(32, 16)).astype(np.float32)
        dd, di = dist_knn.knn(handle, db, q, 8)
        from raft_tpu.neighbors import brute_force
        sd, si = brute_force.knn(res, db, q, 8,
                                 metric=0)  # L2Expanded
        np.testing.assert_allclose(np.asarray(dd), np.asarray(sd),
                                   rtol=1e-3, atol=1e-3)
        # ids may differ on exact ties only
        agree = (np.asarray(di) == np.asarray(si)).mean()
        assert agree > 0.95

    def test_inner_product(self, handle):
        rng = np.random.default_rng(1)
        db = rng.normal(size=(512, 8)).astype(np.float32)
        q = rng.normal(size=(16, 8)).astype(np.float32)
        from raft_tpu.distance.types import DistanceType
        dd, di = dist_knn.knn(handle, db, q, 4,
                              metric=DistanceType.InnerProduct)
        ip = q @ db.T
        ti = np.argsort(-ip, axis=1)[:, :4]
        np.testing.assert_array_equal(np.asarray(di), ti)

    def test_uneven_shards_rejected(self, handle):
        from raft_tpu.core.error import RaftError
        db = np.zeros((100, 4), np.float32)  # 100 % 8 != 0
        q = np.zeros((4, 4), np.float32)
        with pytest.raises(RaftError, match="divide"):
            dist_knn.knn(handle, db, q, 3)


class TestDistributedAnn:
    """Sharded IVF-PQ (the ANN bench 'multigpu' analogue): local indexes
    per shard + all_gather merge must find the same neighbors as a
    single-device index at the same total capacity."""

    def test_recall_matches_single_device(self, res, handle):
        from raft_tpu.distributed import ann as dist_ann
        from raft_tpu.neighbors import brute_force, ivf_pq
        X, _ = make_blobs(4096, 32, n_clusters=64, cluster_std=1.0, seed=7)
        X = jnp.asarray(X)
        Q = X[:64]
        # pq_dim=16 on 32-d keeps quantization fine enough that the 512-row
        # per-shard codebooks don't dominate the recall measurement
        params = ivf_pq.IndexParams(n_lists=8, pq_dim=16, kmeans_n_iters=5)
        dindex = dist_ann.build(handle, params, X)
        assert dindex.n_shards == 8
        sp = ivf_pq.SearchParams(n_probes=8)
        d, i = dist_ann.search(handle, sp, dindex, Q, 10)
        assert d.shape == (64, 10)
        # global ids must be valid and unique per row
        ii = np.asarray(i)
        assert ii.min() >= 0 and ii.max() < 4096
        for row in ii:
            assert len(set(row.tolist())) == 10
        # recall vs exact
        _, gt = brute_force.knn(res, X, Q, 10)
        gt = np.asarray(gt)
        rec = sum(len(set(a) & set(b)) for a, b in zip(ii, gt)) / gt.size
        assert rec >= 0.7   # PQ-limited, same bar as single-device tests

    def test_ids_are_global(self, handle):
        from raft_tpu.distributed import ann as dist_ann
        from raft_tpu.neighbors import ivf_pq
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.random((1024, 16), dtype=np.float32))
        params = ivf_pq.IndexParams(n_lists=4, pq_dim=4, kmeans_n_iters=3)
        dindex = dist_ann.build(handle, params, X)
        ids = np.asarray(dindex.list_indices)
        valid = ids[ids >= 0]
        # every row appears exactly once across all shards
        assert sorted(valid.tolist()) == list(range(1024))
        # shard s only holds ids from its own row range
        per = 1024 // 8
        for s in range(8):
            sv = ids[s][ids[s] >= 0]
            assert sv.min() >= s * per and sv.max() < (s + 1) * per

    def test_uneven_shards_rejected(self, handle):
        from raft_tpu.core.error import RaftError
        from raft_tpu.distributed import ann as dist_ann
        from raft_tpu.neighbors import ivf_pq
        X = jnp.zeros((1001, 8), jnp.float32)
        with pytest.raises(RaftError):
            dist_ann.build(handle, ivf_pq.IndexParams(n_lists=4), X)


class TestDistributedFlat:
    """Sharded IVF-Flat (multigpu parity for raft_ivf_flat)."""

    def test_recall_matches_single_device(self, res, handle):
        from raft_tpu.distributed import ann as dist_ann
        from raft_tpu.neighbors import brute_force, ivf_flat
        X, _ = make_blobs(4096, 32, n_clusters=64, cluster_std=1.0, seed=9)
        X = jnp.asarray(X)
        Q = X[:64]
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=5)
        dindex = dist_ann.build_flat(handle, params, X)
        assert dindex.n_shards == 8
        d, i = dist_ann.search_flat(handle,
                                    ivf_flat.SearchParams(n_probes=8),
                                    dindex, Q, 10)
        ii = np.asarray(i)
        assert ii.min() >= 0 and ii.max() < 4096
        for row in ii:
            assert len(set(row.tolist())) == 10
        _, gt = brute_force.knn(res, X, Q, 10)
        gt = np.asarray(gt)
        rec = sum(len(set(a) & set(b)) for a, b in zip(ii, gt)) / gt.size
        # exact distances within probed lists: all 8 local lists probed,
        # so the sharded search is exhaustive here
        assert rec >= 0.99

    def test_ids_are_global(self, handle):
        from raft_tpu.distributed import ann as dist_ann
        from raft_tpu.neighbors import ivf_flat
        rng = np.random.default_rng(1)
        X = jnp.asarray(rng.random((1024, 16), dtype=np.float32))
        dindex = dist_ann.build_flat(
            handle, ivf_flat.IndexParams(n_lists=4, kmeans_n_iters=3), X)
        ids = np.asarray(dindex.list_indices)
        valid = ids[ids >= 0]
        assert sorted(valid.tolist()) == list(range(1024))


class TestDistributedCagra:
    """Sharded CAGRA graphs + packed walks (the reference's multi-GPU
    seam, graph_core.cuh:333-369)."""

    def test_recall_vs_exact(self, res, handle):
        from raft_tpu.distributed import ann as dist_ann
        from raft_tpu.neighbors import brute_force, cagra
        rng = np.random.default_rng(4)
        n, dim, latent = 4096, 32, 8
        Z = rng.normal(size=(n, latent)).astype(np.float32)
        A = rng.normal(size=(latent, dim)).astype(np.float32) / np.sqrt(latent)
        X = jnp.asarray((Z @ A).astype(np.float32))
        Q = X[:64]
        params = cagra.IndexParams(intermediate_graph_degree=32,
                                   graph_degree=16)
        dindex = dist_ann.build_cagra(handle, params, X)
        assert dindex.n_shards == 8
        d, i = dist_ann.search_cagra(
            handle, cagra.SearchParams(itopk_size=32), dindex, Q, 10)
        ii = np.asarray(i)
        assert ii.min() >= 0 and ii.max() < n
        for row in ii:
            assert len(set(row.tolist())) == 10
        _, gt = brute_force.knn(res, X, Q, 10)
        gt = np.asarray(gt)
        rec = sum(len(set(a) & set(b)) for a, b in zip(ii, gt)) / gt.size
        assert rec >= 0.8

    def test_direct_walk_fallback(self, res, handle, monkeypatch):
        """When the packed table is infeasible (tiny byte gate), the
        sharded search must fall back to the exact direct walk and stay
        correct (the same route single-device search takes)."""
        from raft_tpu.distributed import ann as dist_ann
        from raft_tpu.neighbors import brute_force, cagra
        monkeypatch.setattr(cagra, "_WALK_TABLE_MAX_BYTES", 1)
        rng = np.random.default_rng(6)
        n, dim, latent = 2048, 32, 8
        Z = rng.normal(size=(n, latent)).astype(np.float32)
        A = rng.normal(size=(latent, dim)).astype(np.float32) / np.sqrt(latent)
        X = jnp.asarray((Z @ A).astype(np.float32))
        Q = X[:32]
        params = cagra.IndexParams(intermediate_graph_degree=32,
                                   graph_degree=16)
        dindex = dist_ann.build_cagra(handle, params, X)
        assert not dindex.use_walk
        d, i = dist_ann.search_cagra(
            handle, cagra.SearchParams(itopk_size=32), dindex, Q, 10)
        ii = np.asarray(i)
        assert ii.min() >= 0 and ii.max() < n
        _, gt = brute_force.knn(res, X, Q, 10)
        gt = np.asarray(gt)
        rec = sum(len(set(a) & set(b)) for a, b in zip(ii, gt)) / gt.size
        assert rec >= 0.7, rec


class TestRoutedAnn:
    """PR 8 tentpole: ``placement="by_list"`` index-parallel routing.

    Contracts under test: full-probe routed search is EXACTLY the
    single-index answer (hierarchical top-k over a disjoint list
    partition); per-shard scan work is ~1/n_shards of the probed rows
    (the acceptance tripwire); the candidate exchange is fixed at
    (k, nq) pairs per shard; a failed shard drops only its owned lists;
    the placement map and the whole routed index serialize round-trip.
    """

    N, DIM, NL, NQ, K = 2048, 32, 32, 16, 10

    @pytest.fixture(scope="class")
    def rhandle(self):
        devs = jax.devices()
        if len(devs) < 8:
            devs = jax.devices("cpu")
        if len(devs) < 8:
            pytest.skip("needs 8 devices")
        from raft_tpu.comms import CommsSession
        mesh = jax.sharding.Mesh(np.asarray(devs[:8]), ("data",))
        s = CommsSession(mesh=mesh, axis_name="data").init()
        yield s.worker_handle(seed=0)
        s.destroy()

    @pytest.fixture(scope="class")
    def data(self):
        rng = np.random.default_rng(7)
        db = rng.normal(size=(self.N, self.DIM)).astype(np.float32)
        q = rng.normal(size=(self.NQ, self.DIM)).astype(np.float32)
        return db, q

    @pytest.fixture(scope="class")
    def built(self, rhandle, data):
        from raft_tpu.distributed import ann
        db, _ = data
        params = ivf_pq.IndexParams(n_lists=self.NL, pq_dim=8,
                                    kmeans_n_iters=3,
                                    cache_reconstructions=True)
        base = ivf_pq.build(rhandle, params, db)
        return base, ann.shard_by_list(rhandle, base)

    @staticmethod
    def _recall(found, truth):
        hits = sum(len(set(f.tolist()) & set(t.tolist()))
                   for f, t in zip(found, truth))
        return hits / truth.size

    def test_full_probe_matches_single_index_exactly(self, rhandle, data,
                                                     built):
        from raft_tpu.core.outputs import raw
        from raft_tpu.distributed import ann
        _, q = data
        base, ridx = built
        sp = ivf_pq.SearchParams(n_probes=self.NL, scan_mode="recon")
        bd, bi = raw(ivf_pq.search)(rhandle, sp, base, q, self.K)
        rd, ri = ann.search(rhandle, sp, ridx, q, self.K)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(bi))
        np.testing.assert_allclose(np.asarray(rd), np.asarray(bd),
                                   rtol=1e-5, atol=1e-5)

    def test_straggler_injected_routed_search_is_exact(self, rhandle, data,
                                                       built, monkeypatch):
        """PR 12 satellite: a straggler-injected ROUTED search still
        merges the exact single-index answer — the scripted slow shard
        delays the merge (host-side pause in resilience.faults), it does
        not drop candidates."""
        from raft_tpu.core.outputs import raw
        from raft_tpu.distributed import ann
        from raft_tpu.resilience import FaultPlan, faults
        slept = []
        monkeypatch.setattr(faults, "_sleep", slept.append)
        _, q = data
        base, ridx = built
        sp = ivf_pq.SearchParams(n_probes=self.NL, scan_mode="recon")
        bd, bi = raw(ivf_pq.search)(rhandle, sp, base, q, self.K)
        plan = FaultPlan(seed=3).straggle_shard(1, delay=0.04)
        with plan.active():
            rd, ri = ann.search(rhandle, sp, ridx, q, self.K)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(bi))
        np.testing.assert_allclose(np.asarray(rd), np.asarray(bd),
                                   rtol=1e-5, atol=1e-5)
        assert slept == [0.04]

    def test_scan_work_and_gather_shape_tripwire(self, rhandle, data,
                                                 built):
        """Acceptance criterion: per-shard scanned candidates at the
        operating point stay under (probed_rows / n_shards) * 1.5 and
        the exchange is the fixed (n_shards, nq, k) pair block."""
        from raft_tpu.distributed import ann
        _, q = data
        _, ridx = built
        n_probes = 8
        sp = ivf_pq.SearchParams(n_probes=n_probes)
        _, _, stats = ann.search(rhandle, sp, ridx, q, self.K,
                                 return_stats=True)
        cap = ridx.capacity
        probed_rows = self.NQ * n_probes * cap
        bound = probed_rows / ridx.n_shards * 1.5
        assert stats["gather_shape"] == (ridx.n_shards, self.NQ, self.K)
        assert int(stats["scanned_rows"].sum()) <= probed_rows
        assert int(stats["scanned_rows"].max()) <= bound, (
            f"placement imbalance: {stats['scanned_rows']} vs {bound}")

    def test_recall_parity_with_data_parallel(self, rhandle, data, built):
        from raft_tpu.distributed import ann
        from raft_tpu.neighbors import brute_force
        from raft_tpu.core.outputs import raw
        db, q = data
        _, ridx = built
        params = ivf_pq.IndexParams(n_lists=self.NL, pq_dim=8,
                                    kmeans_n_iters=3,
                                    cache_reconstructions=True)
        dp = ann.build(rhandle, params, db)  # data-parallel replica
        sp = ivf_pq.SearchParams(n_probes=8)
        _, truth = raw(brute_force.knn)(rhandle, db, q, self.K)
        _, ri = ann.search(rhandle, sp, ridx, q, self.K)
        _, di = ann.search(rhandle, sp, dp, q, self.K)
        r_routed = self._recall(np.asarray(ri), np.asarray(truth))
        r_dp = self._recall(np.asarray(di), np.asarray(truth))
        assert r_routed > r_dp - 0.1, (r_routed, r_dp)

    def test_build_by_list_entry_point(self, rhandle, data):
        from raft_tpu.distributed import ann
        db, q = data
        params = ivf_pq.IndexParams(n_lists=self.NL, pq_dim=8,
                                    kmeans_n_iters=3,
                                    cache_reconstructions=True)
        idx = ann.build(rhandle, params, db, placement="by_list")
        assert isinstance(idx, ann.RoutedIndex)
        assert idx.n_shards == 8 and idx.n_lists == self.NL
        d, i, status = ann.search(rhandle, ivf_pq.SearchParams(n_probes=8),
                                  idx, q, self.K, return_status=True)
        assert np.asarray(i).min() >= 0
        np.testing.assert_array_equal(np.asarray(status),
                                      np.full(8, ann.SHARD_OK, np.int8))

    def test_placement_roundtrip(self, rhandle, built):
        import io
        from raft_tpu.distributed import ann
        _, ridx = built
        buf = io.BytesIO()
        ann.placement_to_stream(rhandle, buf, ridx.placement)
        buf.seek(0)
        back = ann.placement_from_stream(rhandle, buf)
        np.testing.assert_array_equal(back.owner, ridx.placement.owner)
        np.testing.assert_array_equal(back.local_slot,
                                      ridx.placement.local_slot)
        assert back.n_shards == ridx.placement.n_shards
        assert back.n_local == ridx.placement.n_local
        assert back.generation == ridx.placement.generation

    def test_routed_serialization_roundtrip(self, rhandle, data, built):
        import io
        from raft_tpu.distributed import ann
        _, q = data
        _, ridx = built
        buf = io.BytesIO()
        ann.serialize_routed(rhandle, buf, ridx)
        buf.seek(0)
        back = ann.deserialize_routed(rhandle, buf)
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d1, i1 = ann.search(rhandle, sp, ridx, q, self.K)
        d2, i2 = ann.search(rhandle, sp, back, q, self.K)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-6)
        np.testing.assert_array_equal(back.placement.owner,
                                      ridx.placement.owner)

    def test_failed_shard_drops_only_owned_lists(self, rhandle, data,
                                                 built):
        from raft_tpu.core.outputs import raw
        from raft_tpu.distributed import ann
        from raft_tpu.neighbors import brute_force
        db, q = data
        base, ridx = built
        dead = 3
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d, i, status = ann.search(rhandle, sp, ridx, q, self.K,
                                  failed_shards=[dead],
                                  return_status=True)
        expect = np.full(8, ann.SHARD_OK, np.int8)
        expect[dead] = ann.SHARD_FAILED
        np.testing.assert_array_equal(np.asarray(status), expect)
        # ids living in the dead shard's owned lists must not appear
        li = np.asarray(base.list_indices)
        owned = ridx.placement.shard_lists(dead)
        lost = set(li[owned][li[owned] >= 0].ravel().tolist())
        found = set(np.asarray(i).ravel().tolist()) - {-1}
        assert not (found & lost)
        # graceful degradation: recall drops by roughly the dead shard's
        # owned share, not to a cliff
        _, truth = raw(brute_force.knn)(rhandle, db, q, self.K)
        rec = self._recall(np.asarray(i), np.asarray(truth))
        assert rec > 0.5, rec

    def test_scan_mode_fallback_reported_in_status(self, rhandle, data,
                                                   built):
        from raft_tpu.distributed import ann
        _, q = data
        _, ridx = built
        # round 10: fused lowers under shard_map at static group
        # capacity — shards report plain SHARD_OK, not FALLBACK
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="fused")
        _, i, status = ann.search(rhandle, sp, ridx, q, self.K,
                                  return_status=True)
        np.testing.assert_array_equal(
            np.asarray(status), np.full(8, ann.SHARD_OK, np.int8))
        assert np.asarray(i).min() >= 0
        # lut stays a genuine lowering under the routed path (its
        # shards hold no packed codes) and keeps the FALLBACK status
        # visible to callers
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="lut")
        _, i, status = ann.search(rhandle, sp, ridx, q, self.K,
                                  return_status=True)
        np.testing.assert_array_equal(
            np.asarray(status),
            np.full(8, ann.SHARD_OK_FALLBACK, np.int8))
        # fallback is a reporting change only: results still valid
        assert np.asarray(i).min() >= 0

    def test_rebalance_placement_preserves_results(self, rhandle, data,
                                                   built):
        from raft_tpu.distributed import ann
        from raft_tpu.neighbors import mutate
        _, q = data
        _, ridx = built
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d1, i1 = ann.search(rhandle, sp, ridx, q, self.K)
        reb = ann.rebalance_placement(rhandle, ridx)
        assert reb.placement.generation == ridx.placement.generation + 1
        assert mutate.generation(reb) == mutate.generation(ridx) + 1
        d2, i2 = ann.search(rhandle, sp, reb, q, self.K)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_aot_export_merges_to_live_answer(self, rhandle, data, built):
        from raft_tpu.core import aot
        from raft_tpu.distributed import ann
        from raft_tpu.matrix.select_k import select_k
        from raft_tpu.neighbors import grouped
        from raft_tpu.distance.types import DistanceType
        _, q = data
        _, ridx = built
        n_probes = 8
        sp = ivf_pq.SearchParams(n_probes=n_probes)
        ld, li = ann.search(rhandle, sp, ridx, q, self.K)
        outs = []
        for s in range(ridx.n_shards):
            buf = aot.export_ivf_pq_routed_search(
                rhandle, ridx, s, n_probes, self.K, self.NQ)
            fn = aot.load_search_fn(buf)
            ds, is_ = fn(jnp.asarray(q))
            outs.append((np.asarray(ds), np.asarray(is_)))
        all_d = jnp.asarray(np.stack([o[0] for o in outs], 0))
        all_i = jnp.asarray(np.stack([o[1] for o in outs], 0))
        md, mi = grouped.finalize_topk(
            all_d.transpose(1, 0, 2), all_i.transpose(1, 0, 2),
            self.NQ, self.K,
            ridx.metric != DistanceType.InnerProduct, False, select_k)
        np.testing.assert_array_equal(np.asarray(mi), np.asarray(li))
        np.testing.assert_allclose(np.asarray(md), np.asarray(ld),
                                   rtol=1e-5, atol=1e-5)

    def test_executor_swap_is_placement_barrier(self, rhandle, data,
                                                built):
        from raft_tpu.distributed import ann
        from raft_tpu.serving.executor import DistributedExecutor
        _, q = data
        _, ridx = built
        ex = DistributedExecutor(
            rhandle, ridx, ks=(self.K,), max_batch=16,
            search_params=ivf_pq.SearchParams(n_probes=8))
        ex.warmup()
        d1, i1 = ex.search_bucket(jnp.asarray(q), self.NQ, self.K)
        reb = ann.rebalance_placement(rhandle, ridx)
        ex.swap_index(reb)
        d2, i2 = ex.search_bucket(jnp.asarray(q), self.NQ, self.K)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    # ---- round 10: sync-free fused grouping under the routed path ----

    def test_routed_fused_full_probe_matches_single_index(self, rhandle,
                                                          data):
        """by_list fused at full probe == the single-index fused answer.

        Per-cluster codebooks keep the index out of the codes/LUT
        branch, so BOTH sides land on the same grouped recon twin — the
        comparison is formulation-for-formulation, not recall-level."""
        from raft_tpu.core.outputs import raw
        from raft_tpu.distributed import ann
        db, q = data
        params = ivf_pq.IndexParams(
            n_lists=self.NL, pq_dim=self.DIM, kmeans_n_iters=3,
            codebook_kind=ivf_pq.CodebookKind.PER_CLUSTER,
            cache_reconstructions=True)
        base = ivf_pq.build(rhandle, params, db)
        ridx = ann.shard_by_list(rhandle, base)
        assert ridx.list_code_lanes is None   # not codes-eligible
        sp = ivf_pq.SearchParams(n_probes=self.NL, scan_mode="fused")
        bd, bi = raw(ivf_pq.search)(rhandle, sp, base, q, self.K)
        rd, ri, status = ann.search(rhandle, sp, ridx, q, self.K,
                                    return_status=True)
        np.testing.assert_array_equal(
            np.asarray(status), np.full(8, ann.SHARD_OK, np.int8))
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(bi))
        np.testing.assert_allclose(np.asarray(rd), np.asarray(bd),
                                   rtol=1e-5, atol=1e-5)

    def test_routed_fused_does_not_tick_lowering(self, rhandle, data,
                                                 built):
        """Round-10 acceptance: scan_mode="fused" on a by_list index no
        longer counts as a distributed lowering — the counter that used
        to tick on every fused routed request must stay silent."""
        from raft_tpu import observability as obs
        from raft_tpu.distributed import ann
        _, q = data
        _, ridx = built
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="fused")
        with obs.collecting():
            low0 = obs.registry().counter(
                "distributed.ann.scan_mode_lowered").value
            _, _, stats = ann.search(rhandle, sp, ridx, q, self.K,
                                     return_stats=True)
            lowered = obs.registry().counter(
                "distributed.ann.scan_mode_lowered").value - low0
        assert lowered == 0, "fused routed search reported a lowering"
        assert stats["scan_mode"] in ("grouped_recon", "fused_recon",
                                      "fused_codes")

    def test_routed_fused_overflow_redispatch_under_skew(
            self, rhandle, data, built, monkeypatch):
        """Calibrated-capacity protocol: a probe distribution wider than
        the estimate must tick ivf_pq.search.group_overflow and
        re-dispatch at the worst bound — results identical to the
        uncalibrated (always-worst) index."""
        import dataclasses
        from raft_tpu import observability as obs
        from raft_tpu.distributed import ann
        from raft_tpu.neighbors import grouped
        _, q = data
        _, ridx = built
        # drop the compile-cache quantum so the class-sized mesh can
        # actually exceed a tightened capacity (at the default 256 the
        # rounded capacity clamps to the worst bound at this scale)
        monkeypatch.setattr(grouped, "_GROUP_ROUND", 1)
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="fused")
        d0, i0 = ann.search(rhandle, sp, ridx, q, self.K)
        tight = dataclasses.replace(ridx, group_est=0.05)
        slots = ridx.local_centers.shape[1]
        cap, exact = grouped.group_capacity(self.NQ, 8, slots, est=0.05)
        worst, _ = grouped.group_capacity(self.NQ, 8, slots)
        assert not exact and cap < worst, (cap, worst)
        with obs.collecting():
            d1, i1 = ann.search(rhandle, sp, tight, q, self.K)
            n_over = obs.registry().counter(
                "ivf_pq.search.group_overflow").value
        assert n_over >= 1, "skewed batch must trip the overflow gate"
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))

    def test_routed_overflow_lands_flight_event(
            self, rhandle, data, built, monkeypatch):
        """The overflow re-dispatch is an anomaly: it must land in the
        always-on flight recorder with both capacities, even with
        metrics collection and tracing disabled."""
        import dataclasses
        from raft_tpu.distributed import ann
        from raft_tpu.neighbors import grouped
        from raft_tpu.observability import flight
        _, q = data
        _, ridx = built
        monkeypatch.setattr(grouped, "_GROUP_ROUND", 1)
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="fused")
        tight = dataclasses.replace(ridx, group_est=0.05)
        flight.clear()
        ann.search(rhandle, sp, tight, q, self.K)
        evs = flight.events("ivf_pq.group_overflow")
        assert len(evs) >= 1
        worst, _ = grouped.group_capacity(
            self.NQ, 8, ridx.local_centers.shape[1])
        assert evs[0]["attrs"]["worst"] == worst
        assert evs[0]["attrs"]["calibrated_groups"] < worst
        assert evs[0]["trace_id"] is None   # no ambient trace active

    def test_routed_serialization_carries_code_leaves_and_est(
            self, rhandle, built):
        """Routed envelope v2: lane-major code leaves, pq_bits and the
        calibrated estimate survive the round trip (v1 streams read back
        as recon-only / uncalibrated — always-correct defaults)."""
        import io
        from raft_tpu.distributed import ann
        _, ridx = built
        assert ridx.list_code_lanes is not None   # codes-eligible base
        buf = io.BytesIO()
        ann.serialize_routed(rhandle, buf, ridx)
        buf.seek(0)
        back = ann.deserialize_routed(rhandle, buf)
        assert back.pq_bits == ridx.pq_bits
        assert back.group_est == ridx.group_est
        np.testing.assert_array_equal(np.asarray(back.list_code_lanes),
                                      np.asarray(ridx.list_code_lanes))
        np.testing.assert_array_equal(np.asarray(back.list_code_rsq),
                                      np.asarray(ridx.list_code_rsq))
        np.testing.assert_array_equal(np.asarray(back.codebooks),
                                      np.asarray(ridx.codebooks))

    def test_serving_dispatch_zero_sync_steady_state(self, rhandle, data,
                                                     built):
        """Round-10 serving acceptance on the routed path: across warmed
        mixed-size batches under scan_mode="fused", steady state sees
        ZERO XLA recompiles and ZERO overflow re-dispatches (the
        uncalibrated index runs the exact worst-bound regime, which
        never reads anything back)."""
        from raft_tpu.serving.executor import DistributedExecutor
        _, q = data
        _, ridx = built
        ex = DistributedExecutor(
            rhandle, ridx, ks=(self.K,), max_batch=16,
            search_params=ivf_pq.SearchParams(n_probes=8,
                                              scan_mode="fused"))
        qn = np.asarray(q)

        def dispatch(m):
            # host-side bucket assembly, exactly as the batcher does it
            # (a jnp.pad here would itself compile per novel (m, bucket)
            # pair — the recompile-hazard class of bug)
            b = serving_buckets.bucket_for(m, 16)
            buf = np.zeros((b, qn.shape[1]), qn.dtype)
            buf[:m] = qn[:m]
            return ex.search_bucket(jnp.asarray(buf), m, self.K)

        with obs.collecting():
            # the registry is global and cumulative — earlier tests
            # legitimately tick group_overflow, so assert deltas only
            over0 = obs.registry().counter(
                "ivf_pq.search.group_overflow").value
            ex.warmup()
            for m in (1, 3, 8, 16, 5, 2):
                dispatch(m)
            c0 = obs.registry().counter("xla.compiles").value
            for m in (2, 16, 1, 7, 4, 16, 3):
                dispatch(m)
            c1 = obs.registry().counter("xla.compiles").value
            n_over = obs.registry().counter(
                "ivf_pq.search.group_overflow").value - over0
        assert c1 == c0, f"{c1 - c0} recompiles in steady state"
        assert n_over == 0, "steady-state dispatch re-dispatched"


class TestReplicatedRouted:
    """PR 17 tentpole: replicated routed placement — recall-preserving
    shard failover, hedged straggler reads, health-tracked lifecycle.

    Contracts under test: with ``replication_factor=2`` and ANY single
    shard failed, full-probe routed search is BIT-IDENTICAL to the
    healthy run (the hierarchical-top-k exactness argument extends to a
    replica serving a superset of lists); each pair kill at r=3 stays
    exact; a kill at every lifecycle boundary (route / scan / gather /
    swap / catch-up) either fails over exactly or degrades gracefully
    with the documented status + flight trail; failover and readmission
    trigger ZERO steady-state recompiles (replica choice is data, not
    shape); hedged reads collapse a straggler's wait to the per-shard
    deadline without changing one bit of the answer.
    """

    N, DIM, NL, NQ, K = 2048, 32, 32, 16, 10

    @pytest.fixture(scope="class")
    def rhandle(self):
        devs = jax.devices()
        if len(devs) < 8:
            devs = jax.devices("cpu")
        if len(devs) < 8:
            pytest.skip("needs 8 devices")
        from raft_tpu.comms import CommsSession
        mesh = jax.sharding.Mesh(np.asarray(devs[:8]), ("data",))
        s = CommsSession(mesh=mesh, axis_name="data").init()
        yield s.worker_handle(seed=0)
        s.destroy()

    @pytest.fixture(scope="class")
    def data(self):
        rng = np.random.default_rng(7)
        db = rng.normal(size=(self.N, self.DIM)).astype(np.float32)
        q = rng.normal(size=(self.NQ, self.DIM)).astype(np.float32)
        return db, q

    @pytest.fixture(scope="class")
    def built(self, rhandle, data):
        from raft_tpu.distributed import ann
        db, _ = data
        params = ivf_pq.IndexParams(n_lists=self.NL, pq_dim=8,
                                    kmeans_n_iters=3,
                                    cache_reconstructions=True)
        base = ivf_pq.build(rhandle, params, db)
        return (base, ann.shard_by_list(rhandle, base),
                ann.shard_by_list(rhandle, base, replication_factor=2))

    # ---- placement invariants -------------------------------------------

    def test_replicated_placement_invariants(self):
        from raft_tpu.distributed import ann
        sizes = np.random.default_rng(11).integers(5, 200, self.NL)
        p1 = ann.compute_placement(sizes, 8)
        for r in (2, 3):
            p = ann.compute_placement(sizes, 8, replication_factor=r)
            assert p.owners.shape == (r, self.NL)
            # rank 0 IS the r=1 placement: a replicated index's healthy
            # routing is bit-identical to the unreplicated one
            np.testing.assert_array_equal(p.owner, p1.owner)
            np.testing.assert_array_equal(p.local_slot, p1.local_slot)
            # replicas of a list are never co-located
            for g in range(self.NL):
                assert len(set(p.owners[:, g].tolist())) == r
            # every rank's slots land inside the shard's slot range
            for s in range(8):
                ls = p.shard_lists(s)
                assert len(ls) == len(set(ls.tolist()))
                for rank in range(r):
                    mine = np.nonzero(p.owners[rank] == s)[0]
                    assert set(mine.tolist()) <= set(ls.tolist())

    def test_healthy_routing_covers_and_reports_residual(self):
        from raft_tpu.distributed import ann
        sizes = np.random.default_rng(12).integers(5, 200, self.NL)
        p = ann.compute_placement(sizes, 8, replication_factor=2)
        eo, es = p.healthy_routing((2,))
        assert 2 not in set(eo.tolist())   # fully covered at r=2
        # the replacement owner really owns the list at some rank, at
        # the slot the tables say
        for g in np.nonzero(p.owner == 2)[0]:
            rank = np.nonzero(p.owners[:, g] == eo[g])[0]
            assert rank.size == 1
            assert es[g] == p.slots[rank[0], g]
        # untouched lists keep the primary routing
        keep = p.owner != 2
        np.testing.assert_array_equal(eo[keep], p.owner[keep])
        np.testing.assert_array_equal(es[keep], p.local_slot[keep])

    def test_replication_needs_by_list_and_fits_mesh(self, rhandle, data):
        from raft_tpu.core.error import RaftError
        from raft_tpu.distributed import ann
        db, _ = data
        params = ivf_pq.IndexParams(n_lists=self.NL, pq_dim=8,
                                    kmeans_n_iters=3,
                                    cache_reconstructions=True)
        with pytest.raises(RaftError):
            ann.build(rhandle, params, db, replication_factor=2)  # by_row
        with pytest.raises(RaftError):
            ann.compute_placement(np.ones(self.NL, np.int64), 8,
                                  replication_factor=9)

    # ---- tentpole: failover exactness -----------------------------------

    def test_single_shard_failover_bit_identical(self, rhandle, data,
                                                 built):
        """Acceptance criterion: r=2, ANY single shard failed, full
        probe — bit-identical to the healthy run, the failed shard
        reported as replica-served (telemetry, not degradation)."""
        from raft_tpu.distributed import ann
        from raft_tpu.observability import flight
        _, q = data
        _, _, r2 = built
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d0, i0 = ann.search(rhandle, sp, r2, q, self.K)
        for dead in range(8):
            flight.clear()
            d1, i1, st = ann.search(rhandle, sp, r2, q, self.K,
                                    failed_shards=[dead],
                                    return_status=True)
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
            np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
            st = np.asarray(st)
            assert st[dead] == ann.SHARD_REPLICA_SERVED
            ok = np.delete(st, dead)
            np.testing.assert_array_equal(
                ok, np.full(7, ann.SHARD_OK, np.int8))
            evs = flight.events("distributed.replica_failover")
            assert evs and evs[0]["attrs"]["covered"] == [dead]
            # a fully covered failover is NOT a degraded search
            assert not flight.events("distributed.degraded_search")

    def test_replicated_healthy_run_matches_single_index(self, rhandle,
                                                         data, built):
        from raft_tpu.core.outputs import raw
        from raft_tpu.distributed import ann
        _, q = data
        base, r1, r2 = built
        sp = ivf_pq.SearchParams(n_probes=self.NL, scan_mode="recon")
        bd, bi = raw(ivf_pq.search)(rhandle, sp, base, q, self.K)
        rd, ri = ann.search(rhandle, sp, r2, q, self.K)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(bi))
        # rank 0 == the r=1 placement: same routing, same answer
        d1, i1 = ann.search(rhandle, sp, r1, q, self.K)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(i1))

    def test_pair_kills_at_r3_bit_identical(self, rhandle, data):
        """Satellite: every shard PAIR killed at r=3 stays exact — two
        replicas lost still leaves one live owner per list."""
        import itertools
        from raft_tpu.distributed import ann
        db, q = data
        params = ivf_pq.IndexParams(n_lists=self.NL, pq_dim=8,
                                    kmeans_n_iters=3,
                                    cache_reconstructions=True)
        base = ivf_pq.build(rhandle, params, db)
        r3 = ann.shard_by_list(rhandle, base, replication_factor=3)
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d0, i0 = ann.search(rhandle, sp, r3, q, self.K)
        for a, b in itertools.combinations(range(8), 2):
            d1, i1, st = ann.search(rhandle, sp, r3, q, self.K,
                                    failed_shards=[a, b],
                                    return_status=True)
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
            st = np.asarray(st)
            assert (st[a] == ann.SHARD_REPLICA_SERVED
                    and st[b] == ann.SHARD_REPLICA_SERVED), (a, b, st)

    def test_fused_path_failover_bit_identical(self, rhandle, data,
                                               built):
        from raft_tpu.distributed import ann
        _, q = data
        _, _, r2 = built
        sp = ivf_pq.SearchParams(n_probes=self.NL, scan_mode="fused")
        d0, i0 = ann.search(rhandle, sp, r2, q, self.K)
        d1, i1 = ann.search(rhandle, sp, r2, q, self.K,
                            failed_shards=[5])
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))

    def test_uncovered_failure_degrades_gracefully(self, rhandle, data,
                                                   built):
        """When replicas do NOT cover the loss (a pair kill at r=2 can
        strand lists), the residual shards report SHARD_FAILED with the
        degraded-search flight event — the PR 8 contract, unchanged."""
        import itertools
        from raft_tpu.distributed import ann
        from raft_tpu.observability import flight
        _, q = data
        base, _, r2 = built
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        p = r2.placement
        # find a pair that strands at least one list (both owners dead)
        stranded_pair = None
        for a, b in itertools.combinations(range(8), 2):
            eo, _ = p.healthy_routing((a, b))
            if set(eo.tolist()) & {a, b}:
                stranded_pair = (a, b)
                break
        assert stranded_pair is not None, "r=2 pair kill always covered?"
        a, b = stranded_pair
        flight.clear()
        d, i, st = ann.search(rhandle, sp, r2, q, self.K,
                              failed_shards=[a, b], return_status=True)
        st = np.asarray(st)
        eo, _ = p.healthy_routing((a, b))
        residual = sorted(set(eo.tolist()) & {a, b})
        for s in (a, b):
            want = (ann.SHARD_FAILED if s in residual
                    else ann.SHARD_REPLICA_SERVED)
            assert st[s] == want, (s, st)
        evs = flight.events("distributed.degraded_search")
        assert evs and evs[0]["attrs"]["failed"] == residual
        # stranded lists' ids are gone; everything else still answers
        li = np.asarray(base.list_indices)
        stranded = [g for g in range(self.NL)
                    if eo[g] in (a, b)]
        lost = set(li[stranded][li[stranded] >= 0].ravel().tolist())
        found = set(np.asarray(i).ravel().tolist()) - {-1}
        assert not (found & lost)

    # ---- kill matrix: lifecycle boundaries ------------------------------

    def test_kill_at_route_boundary_fails_over_this_search(
            self, rhandle, data, built):
        """A kill landing at the ROUTE boundary is seen by the same
        search's failed-set computation — it fails over immediately."""
        from raft_tpu.distributed import ann
        from raft_tpu.resilience import FaultPlan
        _, q = data
        _, _, r2 = built
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d0, i0 = ann.search(rhandle, sp, r2, q, self.K)
        plan = FaultPlan(seed=5).kill_shard_at("distributed.route", 3)
        with plan.active():
            d1, i1, st = ann.search(rhandle, sp, r2, q, self.K,
                                    return_status=True)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        assert np.asarray(st)[3] == ann.SHARD_REPLICA_SERVED

    @pytest.mark.parametrize("site", ["distributed.scan",
                                      "distributed.gather"])
    def test_kill_at_scan_and_gather_boundaries(self, rhandle, data,
                                                built, site):
        """A kill landing mid-SCAN or at the GATHER keeps the in-flight
        search on its pre-kill routing (the shard's answer completes —
        the race a real failure also exposes); the NEXT search routes
        around the dead shard, bit-identically."""
        from raft_tpu.distributed import ann
        from raft_tpu.resilience import FaultPlan
        _, q = data
        _, _, r2 = built
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d0, i0 = ann.search(rhandle, sp, r2, q, self.K)
        plan = FaultPlan(seed=5).kill_shard_at(site, 6)
        with plan.active():
            d1, i1, st1 = ann.search(rhandle, sp, r2, q, self.K,
                                     return_status=True)
            d2, i2, st2 = ann.search(rhandle, sp, r2, q, self.K,
                                     return_status=True)
        # in-flight search: pre-kill routing, all shards OK
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        np.testing.assert_array_equal(
            np.asarray(st1), np.full(8, ann.SHARD_OK, np.int8))
        # next search: failover, still bit-identical
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i0))
        assert np.asarray(st2)[6] == ann.SHARD_REPLICA_SERVED

    def test_kill_at_swap_and_catch_up_boundaries(self, rhandle, data,
                                                  built):
        """Kills landing during READMISSION itself: one shard dies while
        another's catch-up is gathering (catch-up boundary), another
        dies inside the swap barrier — every subsequent search stays
        bit-identical while replicas cover the loss."""
        from raft_tpu.distributed import ann, health
        from raft_tpu.resilience import FaultPlan
        _, q = data
        _, _, r2 = built
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d0, i0 = ann.search(rhandle, sp, r2, q, self.K)

        class _Server:
            def __init__(self):
                self.swapped = []

            def swap_index(self, idx):
                self.swapped.append(idx)

        srv = _Server()
        tr = health.HealthTracker(8, health.HealthConfig(
            suspect_after=1, fail_after=1, ok_to_clear=1, dwell_s=0.0))
        tr.note_timeout(2)
        tr.note_timeout(2)
        assert tr.state(2) == health.FAILED
        plan = (FaultPlan(seed=5)
                .kill_shard_at("distributed.catch_up", 4)
                .kill_shard_at("distributed.swap", 7))
        with plan.active():
            caught = health.catch_up(rhandle, r2, 2, tracker=tr)
            assert tr.state(2) == health.CATCHING_UP
            assert health.readmit(rhandle, srv, caught, 2, tracker=tr)
            assert tr.state(2) == health.HEALTHY
            assert srv.swapped and srv.swapped[0] is caught
            # shards 4 and 7 died at the catch-up / swap boundaries;
            # the published index still answers bit-identically
            live = srv.swapped[0]
            d1, i1, st = ann.search(rhandle, sp, live, q, self.K,
                                    return_status=True)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        st = np.asarray(st)
        assert st[4] == ann.SHARD_REPLICA_SERVED
        assert st[7] == ann.SHARD_REPLICA_SERVED

    # ---- zero recompiles -------------------------------------------------

    def test_failover_and_readmission_zero_recompiles(self, rhandle,
                                                      data, built):
        """Replica choice is data, not shape: a fully covered failover
        reuses the warmed healthy executable (the static ``failed`` key
        stays ``()``), and a readmitted generation's search does too."""
        from raft_tpu.distributed import ann, health
        _, q = data
        _, _, r2 = built
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        ann.search(rhandle, sp, r2, q, self.K)          # warm
        tr = health.HealthTracker(8, health.HealthConfig(
            suspect_after=1, fail_after=1, ok_to_clear=1, dwell_s=0.0))
        with obs.collecting():
            c0 = obs.registry().counter("xla.compiles").value
            _, i1 = ann.search(rhandle, sp, r2, q, self.K,
                               failed_shards=[1])
            c1 = obs.registry().counter("xla.compiles").value
            assert c1 == c0, f"{c1 - c0} recompiles on covered failover"
        # fail -> catch up -> readmit, then the steady-state search
        tr.note_timeout(1)
        tr.note_timeout(1)
        caught = health.catch_up(rhandle, r2, 1, tracker=tr)

        class _Server:
            def swap_index(self, idx):
                pass

        assert health.readmit(rhandle, _Server(), caught, 1, tracker=tr)
        ann.search(rhandle, sp, caught, q, self.K)      # first post-swap
        with obs.collecting():
            c0 = obs.registry().counter("xla.compiles").value
            _, i2 = ann.search(rhandle, sp, caught, q, self.K, health=tr)
            c1 = obs.registry().counter("xla.compiles").value
        assert c1 == c0, f"{c1 - c0} recompiles after readmission"
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i1))

    # ---- hedged reads ----------------------------------------------------

    def test_hedged_read_exact_wait_collapses_to_deadline(
            self, rhandle, data, built, monkeypatch):
        """Satellite: a 10x straggler behind a replica is hedged — the
        wait collapses from the scripted delay to the per-shard
        deadline, the answer stays bit-identical, and the shard reports
        replica-served with the hedged_read + shard_timeout trail."""
        from raft_tpu.distributed import ann
        from raft_tpu.observability import flight
        from raft_tpu.resilience import FaultPlan, faults
        slept = []
        monkeypatch.setattr(faults, "_sleep", slept.append)
        _, q = data
        _, _, r2 = built
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d0, i0 = ann.search(rhandle, sp, r2, q, self.K)
        flight.clear()
        plan = FaultPlan(seed=3).straggle_shard(2, delay=0.5)
        with plan.active():
            d1, i1, st = ann.search(rhandle, sp, r2, q, self.K,
                                    shard_deadline_s=0.05,
                                    return_status=True)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
        assert slept == [0.05], slept
        assert np.asarray(st)[2] == ann.SHARD_REPLICA_SERVED
        hedges = flight.events("distributed.hedged_read")
        assert hedges and hedges[0]["attrs"]["shard"] == 2
        touts = flight.events("distributed.shard_timeout")
        assert touts and touts[0]["attrs"]["shard"] == 2

    def test_straggler_without_replica_waits_in_full(self, rhandle, data,
                                                     built, monkeypatch):
        """No covering replica -> the shard is UN-hedged: slow beats
        dropped, the full scripted delay is paid, results exact (the
        PR 12 contract survives the hedging rewrite)."""
        from raft_tpu.distributed import ann
        from raft_tpu.resilience import FaultPlan, faults
        slept = []
        monkeypatch.setattr(faults, "_sleep", slept.append)
        _, q = data
        _, r1, _ = built
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d0, i0 = ann.search(rhandle, sp, r1, q, self.K)
        plan = FaultPlan(seed=3).straggle_shard(1, delay=0.04)
        with plan.active():
            d1, i1 = ann.search(rhandle, sp, r1, q, self.K,
                                shard_deadline_s=0.01)
        assert slept == [0.04], slept
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))

    # ---- serialization ---------------------------------------------------

    def test_replicated_placement_serialization_roundtrip(self, rhandle,
                                                          built):
        import io
        from raft_tpu.distributed import ann
        _, _, r2 = built
        p = r2.placement
        buf = io.BytesIO()
        ann.placement_to_stream(rhandle, buf, p)
        buf.seek(0)
        back = ann.placement_from_stream(rhandle, buf)
        assert back.replication_factor == 2
        np.testing.assert_array_equal(back.owners, p.owners)
        np.testing.assert_array_equal(back.slots, p.slots)
        np.testing.assert_array_equal(back.owner, p.owner)
        np.testing.assert_array_equal(back.local_slot, p.local_slot)

    def test_replicated_routed_serialization_failover_survives(
            self, rhandle, data, built):
        """A deserialized replicated index re-places from the placement
        envelope alone — failover still bit-identical after reload."""
        import io
        from raft_tpu.distributed import ann
        _, q = data
        _, _, r2 = built
        buf = io.BytesIO()
        ann.serialize_routed(rhandle, buf, r2)
        buf.seek(0)
        back = ann.deserialize_routed(rhandle, buf)
        assert back.placement.replication_factor == 2
        sp = ivf_pq.SearchParams(n_probes=self.NL)
        d0, i0 = ann.search(rhandle, sp, r2, q, self.K)
        d1, i1, st = ann.search(rhandle, sp, back, q, self.K,
                                failed_shards=[0], return_status=True)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        assert np.asarray(st)[0] == ann.SHARD_REPLICA_SERVED

    # ---- prewarm / AOT per replica rank ---------------------------------

    def test_aot_export_replica_rank_serves_failed_primary(
            self, rhandle, data, built):
        """Per-rank exports: merging every shard's rank-appropriate
        program reproduces the failover answer — the artifact set a
        deployment needs to survive a dead primary."""
        from raft_tpu.core import aot
        from raft_tpu.distributed import ann
        _, q = data
        _, _, r2 = built
        buf0 = aot.export_ivf_pq_routed_search(
            rhandle, r2, 0, 8, self.K, self.NQ)
        buf1 = aot.export_ivf_pq_routed_search(
            rhandle, r2, 0, 8, self.K, self.NQ, replica_rank=1)
        d0, i0 = aot.load_search_fn(buf0)(jnp.asarray(q))
        d1, i1 = aot.load_search_fn(buf1)(jnp.asarray(q))
        # rank tables differ -> the same shard answers for different
        # list subsets under the two programs
        assert not np.array_equal(np.asarray(i0), np.asarray(i1))
        with pytest.raises(Exception):
            aot.export_ivf_pq_routed_search(
                rhandle, r2, 0, 8, self.K, self.NQ, replica_rank=2)

    def test_executor_prewarms_per_replica_rank(self, rhandle, data,
                                                built):
        from raft_tpu.serving.executor import DistributedExecutor
        _, _, r2 = built
        ex = DistributedExecutor(
            rhandle, r2, ks=(self.K,), max_batch=16,
            search_params=ivf_pq.SearchParams(n_probes=8))
        n = ex.prewarm_shard_artifacts(scan_mode="recon")
        # buckets x ks x shards x ranks
        assert n == len(ex.buckets) * 1 * 8 * 2
