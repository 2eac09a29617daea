"""Filtered & multi-tenant search (round 20).

The filtered-parity contract is the backbone: a filtered search at full
probe must be BIT-IDENTICAL to taking the unfiltered result at a huge k
and dropping inadmissible rows post-hoc — on every scan formulation
(lut / recon / codes / fused), on brute force, on ivf_flat,
and across the routed distributed dispatch.  Everything else (tenancy,
hybrid dense+sparse, serving integration, zero-recompile) layers on
that seam.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu import serving
from raft_tpu import integrity
from raft_tpu import observability as obs
from raft_tpu.core.error import LogicError
from raft_tpu.filters import (SampleFilter, TenantFilter,
                              candidates_to_filter, query_filter_words)
from raft_tpu.filters import bitset as fb
from raft_tpu.integrity import canary
from raft_tpu.integrity.errors import IntegrityError
from raft_tpu.neighbors import brute_force, grouped, ivf_flat, ivf_pq

N, DIM, NQ, K = 2000, 32, 8, 10
FULL = ivf_pq.SearchParams(n_probes=16, exact_coarse=True)


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(0)
    db = rng.standard_normal((N, DIM)).astype(np.float32)
    q = rng.standard_normal((NQ, DIM)).astype(np.float32)
    mask = rng.random((NQ, N)) < 0.5
    return db, q, mask


@pytest.fixture(scope="module")
def mres():
    from raft_tpu import DeviceResources
    return DeviceResources(seed=42)


@pytest.fixture(scope="module")
def pq_index(mres, dataset):
    db, _, _ = dataset
    return ivf_pq.build(
        mres, ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=4),
        jnp.asarray(db))


def posthoc_reference(d_u, i_u, mask, k):
    """Drop inadmissible rows from a big unfiltered result, keep k."""
    d_u, i_u = np.asarray(d_u), np.asarray(i_u)
    nq = d_u.shape[0]
    ref_d = np.full((nq, k), np.inf, np.float32)
    ref_i = np.full((nq, k), -1, np.int32)
    for qi in range(nq):
        keep = [(d_u[qi, j], i_u[qi, j]) for j in range(d_u.shape[1])
                if i_u[qi, j] >= 0 and mask[qi, i_u[qi, j]]][:k]
        for j, (dv, iv) in enumerate(keep):
            ref_d[qi, j], ref_i[qi, j] = dv, iv
    return ref_d, ref_i


# ---------------------------------------------------------------------------
# the bitset itself


class TestBitset:
    def test_pack_unpack_roundtrip(self):
        rng = np.random.default_rng(3)
        mask = rng.random((4, 70)) < 0.5
        words = fb.pack_mask(jnp.asarray(mask))
        assert words.shape == (4, 3)
        back = np.asarray(fb.unpack_words(words, 70))
        np.testing.assert_array_equal(back != 0, mask)

    def test_from_ids_and_counts(self):
        f = SampleFilter.from_ids([0, 33, 64], 70)
        assert f.n_words == 3
        counts = f.admitted_counts()
        assert counts.tolist() == [3]
        m = np.asarray(fb.unpack_words(f.words, 70))[0] != 0
        assert sorted(np.nonzero(m)[0].tolist()) == [0, 33, 64]

    def test_all_rows_admits_tail_padding_only_to_coverage(self):
        f = SampleFilter.all_rows(40)
        assert f.admitted_counts().tolist() == [40]

    def test_intersect(self):
        a = SampleFilter.from_ids([1, 2, 3], 64)
        b = SampleFilter.from_ids([2, 3, 4], 64)
        assert a.intersect(b).admitted_counts().tolist() == [2]

    def test_query_bits_rejects_out_of_range(self):
        f = SampleFilter.from_ids([0, 1], 64)
        qids = jnp.zeros((1,), jnp.int32)
        ids = jnp.asarray([[0, -1, 63, 10_000]], jnp.int32)
        bits = np.asarray(fb.query_bits(f.words, qids, ids))
        assert bits[0].tolist() == [1, 0, 0, 0]

    def test_query_filter_words_nq_mismatch_raises(self):
        f = SampleFilter.from_mask(np.ones((2, 64), bool))
        with pytest.raises(LogicError):
            query_filter_words(f, 5, "t")   # nq=2 batch=5: not broadcastable

    def test_mask_and_filter_normalize_identically(self):
        rng = np.random.default_rng(4)
        mask = rng.random((3, 50)) < 0.5
        w1 = query_filter_words(jnp.asarray(mask), 3, "t")
        w2 = query_filter_words(SampleFilter.from_mask(jnp.asarray(mask)),
                                3, "t")
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))


# ---------------------------------------------------------------------------
# filtered parity on every ivf_pq scan formulation


class TestFilteredParity:
    @pytest.mark.parametrize(
        "mode", ["lut", "recon", "codes", "fused"])
    def test_scan_mode_bit_identical_to_posthoc(self, mres, pq_index,
                                                dataset, mode):
        db, q, mask = dataset
        p = ivf_pq.SearchParams(n_probes=16, exact_coarse=True,
                                scan_mode=mode)
        d_u, i_u = ivf_pq.search(mres, p, pq_index, jnp.asarray(q), 512)
        ref_d, ref_i = posthoc_reference(d_u, i_u, mask, K)
        d_f, i_f = ivf_pq.search(mres, p, pq_index, jnp.asarray(q), K,
                                 filter=SampleFilter.from_mask(mask))
        np.testing.assert_array_equal(np.asarray(i_f), ref_i)
        np.testing.assert_array_equal(np.asarray(d_f), ref_d)

    @pytest.mark.parametrize("selectivity", [0.001, 0.5, 1.0])
    def test_selectivity_sweep(self, mres, pq_index, dataset, selectivity):
        _, q, _ = dataset
        rng = np.random.default_rng(int(selectivity * 1000))
        mask = rng.random((NQ, N)) < selectivity
        # k = N: at 0.001 selectivity the handful of admitted rows sit
        # far outside any truncated unfiltered prefix
        p = ivf_pq.SearchParams(n_probes=16, exact_coarse=True,
                                scan_mode="lut")
        d_u, i_u = ivf_pq.search(mres, p, pq_index, jnp.asarray(q), N)
        ref_d, ref_i = posthoc_reference(d_u, i_u, mask, K)
        d_f, i_f = ivf_pq.search(mres, p, pq_index, jnp.asarray(q), K,
                                 filter=SampleFilter.from_mask(mask))
        np.testing.assert_array_equal(np.asarray(i_f), ref_i)
        np.testing.assert_array_equal(np.asarray(d_f), ref_d)

    def test_all_rows_filtered_yields_sentinels(self, mres, pq_index,
                                                dataset):
        _, q, _ = dataset
        empty = SampleFilter.from_mask(np.zeros((NQ, N), bool))
        d, i = ivf_pq.search(mres, FULL, pq_index, jnp.asarray(q), K,
                             filter=empty)
        assert (np.asarray(i) == -1).all()
        assert np.isinf(np.asarray(d)).all()

    def test_single_row_filter_broadcasts(self, mres, pq_index, dataset):
        _, q, mask = dataset
        one = np.broadcast_to(mask[:1], (NQ, N))
        d_b, i_b = ivf_pq.search(mres, FULL, pq_index, jnp.asarray(q), K,
                                 filter=SampleFilter.from_mask(mask[:1]))
        d_f, i_f = ivf_pq.search(mres, FULL, pq_index, jnp.asarray(q), K,
                                 filter=SampleFilter.from_mask(one))
        np.testing.assert_array_equal(np.asarray(i_b), np.asarray(i_f))

    def test_filter_composes_with_tombstones(self, mres, pq_index,
                                             dataset):
        _, q, mask = dataset
        # delete half the admitted world; neither deleted nor filtered
        # rows may surface, and parity holds on the surviving set
        doomed = np.nonzero(mask[0])[0][:200].tolist()
        mutated = ivf_pq.delete(mres, pq_index, doomed)
        d_u, i_u = ivf_pq.search(mres, FULL, mutated, jnp.asarray(q), 512)
        ref_d, ref_i = posthoc_reference(d_u, i_u, mask, K)
        d_f, i_f = ivf_pq.search(mres, FULL, mutated, jnp.asarray(q), K,
                                 filter=SampleFilter.from_mask(mask))
        np.testing.assert_array_equal(np.asarray(i_f), ref_i)
        np.testing.assert_array_equal(np.asarray(d_f), ref_d)
        live = np.asarray(i_f)
        assert not np.isin(live[live >= 0], doomed).any()


# ---------------------------------------------------------------------------
# the Pallas kernels (interpret mode) against their XLA twins


class TestPallasAdmissionParity:
    def test_grouped_kernels_match_xla_twin(self, mres):
        from raft_tpu.neighbors.ivf_pq import (
            _search_impl_codes_grouped, _search_impl_fused_codes_grouped,
            _search_impl_fused_recon_grouped, _search_impl_recon_grouped,
            _select_clusters, _with_code_lanes)

        rng = np.random.default_rng(1)
        n, nq, k = 1024, 8, 8
        data = rng.standard_normal((n, DIM)).astype(np.float32)
        q = jnp.asarray(rng.standard_normal((nq, DIM)).astype(np.float32))
        idx = _with_code_lanes(ivf_pq.build(
            mres, ivf_pq.IndexParams(n_lists=8, pq_dim=8), data))
        probes = _select_clusters(idx.centers, idx.rotation, q, 8,
                                  idx.metric, exact=True)
        ng, _ = grouped.group_capacity(nq, 8, idx.n_lists)
        mask = rng.random((nq, n)) < 0.4
        fw = query_filter_words(SampleFilter.from_mask(mask), nq, "t")

        d_ref, i_ref = _search_impl_recon_grouped(
            idx.centers, idx.list_recon, idx.list_recon_sq,
            idx.list_indices, idx.rotation, q, probes, k, idx.metric, ng,
            64, use_pallas=False, filter_words=fw)
        d_ref, i_ref = np.asarray(d_ref), np.asarray(i_ref)

        d_p, i_p = _search_impl_recon_grouped(
            idx.centers, idx.list_recon, idx.list_recon_sq,
            idx.list_indices, idx.rotation, q, probes, k, idx.metric, ng,
            64, use_pallas=True, pallas_interpret=True, filter_words=fw)
        np.testing.assert_array_equal(np.asarray(i_p), i_ref)

        d_f, i_f = _search_impl_fused_recon_grouped(
            idx.centers, idx.list_recon, idx.list_recon_sq,
            idx.list_indices, idx.rotation, q, probes, k, k, idx.metric,
            ng, pallas_interpret=True, filter_words=fw)
        np.testing.assert_array_equal(np.asarray(i_f), i_ref)
        np.testing.assert_allclose(np.asarray(d_f), d_ref,
                                   rtol=1e-5, atol=1e-5)

        d_c, i_c = _search_impl_codes_grouped(
            idx.centers, idx.codebooks, idx.list_code_lanes,
            idx.list_code_rsq, idx.list_indices, idx.rotation, q, probes,
            k, k, idx.metric, ng, idx.pq_bits, pallas_interpret=True,
            filter_words=fw)
        np.testing.assert_array_equal(np.asarray(i_c), i_ref)

        d_fc, i_fc = _search_impl_fused_codes_grouped(
            idx.centers, idx.codebooks, idx.list_code_lanes,
            idx.list_code_rsq, idx.list_indices, idx.rotation, q, probes,
            k, k, idx.metric, ng, idx.pq_bits, pallas_interpret=True,
            filter_words=fw)
        np.testing.assert_array_equal(np.asarray(i_fc), i_ref)


# ---------------------------------------------------------------------------
# brute force / ivf_flat / cagra


class TestOtherIndexKinds:
    def test_brute_force_matches_numpy_reference(self, mres, dataset):
        db, q, mask = dataset
        d, i = brute_force.knn(mres, jnp.asarray(db), jnp.asarray(q), K,
                               filter=SampleFilter.from_mask(mask))
        d, i = np.asarray(d), np.asarray(i)
        dist = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
        ref_d = np.where(mask, dist, np.inf)
        order = np.argsort(ref_d, axis=1, kind="stable")[:, :K]
        rd = np.take_along_axis(ref_d, order, axis=1)
        ri = np.where(np.isinf(rd), -1, order)
        np.testing.assert_array_equal(i, ri)
        np.testing.assert_allclose(np.where(np.isinf(d), 0, d),
                                   np.where(np.isinf(rd), 0, rd),
                                   atol=1e-3)

    def test_brute_force_filter_addresses_global_ids(self, mres, dataset):
        db, q, mask = dataset
        off = 6400   # word-aligned shard offset
        pad = jnp.zeros((NQ, off // 32), jnp.int32)
        base = SampleFilter.from_mask(mask)
        shifted = SampleFilter.from_words(
            jnp.concatenate([pad, base.words], axis=1), off + N)
        d0, i0 = brute_force.knn(mres, jnp.asarray(db), jnp.asarray(q), K,
                                 filter=base)
        d1, i1 = brute_force.knn(mres, jnp.asarray(db), jnp.asarray(q), K,
                                 filter=shifted, global_id_offset=off)
        i0, i1 = np.asarray(i0), np.asarray(i1)
        np.testing.assert_array_equal(np.where(i1 >= 0, i1 - off, -1), i0)

    def test_ivf_flat_full_probe_parity(self, mres, dataset):
        db, q, mask = dataset
        idx = ivf_flat.build(
            mres, ivf_flat.IndexParams(n_lists=16, metric=0),
            jnp.asarray(db))
        sp = ivf_flat.SearchParams(n_probes=16)
        d_u, i_u = ivf_flat.search(mres, sp, idx, jnp.asarray(q), 512)
        ref_d, ref_i = posthoc_reference(d_u, i_u, mask, K)
        d_f, i_f = ivf_flat.search(mres, sp, idx, jnp.asarray(q), K,
                                   filter=SampleFilter.from_mask(mask))
        np.testing.assert_array_equal(np.asarray(i_f), ref_i)
        np.testing.assert_allclose(
            np.where(np.isinf(np.asarray(d_f)), 0, np.asarray(d_f)),
            np.where(np.isinf(ref_d), 0, ref_d), atol=1e-3)

    def test_cagra_admits_only_filtered(self, mres, dataset):
        from raft_tpu.neighbors import cagra
        db, q, mask = dataset
        # admission semantics don't depend on how the graph was built —
        # assemble the Index from an exact numpy kNN graph instead of
        # paying the full cagra.build (the build has its own tests)
        n_sub, deg = 512, 16
        sub, msub = np.asarray(db)[:n_sub], mask[:, :n_sub]
        dist = ((sub[:, None, :] - sub[None, :, :]) ** 2).sum(-1)
        np.fill_diagonal(dist, np.inf)
        graph = np.argsort(dist, axis=1, kind="stable")[:, :deg]
        idx = cagra.Index(dataset=jnp.asarray(sub),
                          graph=jnp.asarray(graph, jnp.int32))
        sp = cagra.SearchParams(itopk_size=64, search_width=4)
        d, i = cagra.search(mres, sp, idx, jnp.asarray(q), K,
                            filter=SampleFilter.from_mask(msub))
        i = np.asarray(i)
        assert all(msub[qi, ii] for qi in range(NQ)
                   for ii in i[qi] if ii >= 0)
        # all-rows filter is the identity
        d1, i1 = cagra.search(mres, sp, idx, jnp.asarray(q), K)
        d2, i2 = cagra.search(mres, sp, idx, jnp.asarray(q), K,
                              filter=SampleFilter.all_rows(n_sub))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        # total rejection folds to sentinels
        d3, i3 = cagra.search(mres, sp, idx, jnp.asarray(q), K,
                              filter=SampleFilter.from_mask(
                                  np.zeros((NQ, n_sub), bool)))
        assert (np.asarray(i3) == -1).all()


# ---------------------------------------------------------------------------
# hybrid dense+sparse


class TestHybrid:
    def test_candidates_to_filter_skips_padding(self):
        f = candidates_to_filter(np.asarray([[3, -1, 5], [0, 1, -1]]), 64)
        assert f.admitted_counts().tolist() == [2, 2]
        m = np.asarray(fb.unpack_words(f.words, 64)) != 0
        assert sorted(np.nonzero(m[0])[0].tolist()) == [3, 5]
        assert sorted(np.nonzero(m[1])[0].tolist()) == [0, 1]

    def test_hybrid_search_restricts_to_sparse_candidates(self, mres,
                                                          pq_index,
                                                          dataset):
        from raft_tpu import sparse as sp_mod
        from raft_tpu.filters import hybrid
        db, q, _ = dataset
        # lexical side: a random nonnegative "term" view of the corpus
        rng = np.random.default_rng(7)
        lex_db = np.maximum(db, 0) * (rng.random((N, DIM)) < 0.3)
        lex_q = np.maximum(q, 0)
        cdb = sp_mod.dense_to_csr(jnp.asarray(lex_db.astype(np.float32)))
        cq = sp_mod.dense_to_csr(jnp.asarray(lex_q.astype(np.float32)))
        k_sparse = 64
        d, i = hybrid.search(mres, FULL, pq_index, jnp.asarray(q), K,
                             sparse_queries=cq, sparse_database=cdb,
                             k_sparse=k_sparse)
        from raft_tpu.distance.types import DistanceType
        _, cand = sp_mod.brute_force_knn_sparse(
            cq, cdb, k_sparse, metric=DistanceType.InnerProduct)
        cand = np.asarray(cand)
        i = np.asarray(i)
        for qi in range(NQ):
            allowed = set(cand[qi][cand[qi] >= 0].tolist())
            assert set(i[qi][i[qi] >= 0].tolist()) <= allowed
        # and parity: hybrid == ivf_pq.search with the candidate filter
        filt = candidates_to_filter(cand, N)
        d2, i2 = ivf_pq.search(mres, FULL, pq_index, jnp.asarray(q), K,
                               filter=filt)
        np.testing.assert_array_equal(i, np.asarray(i2))


# ---------------------------------------------------------------------------
# tenancy: TenantFilter, namespace verification, filtered canaries


class TestTenancy:
    def test_tenant_filter_invariants(self):
        t = TenantFilter(ranges={"a": (0, 100), "b": (100, 256)},
                         n_rows=256)
        assert t.owner_of(0) == "a" and t.owner_of(255) == "b"
        assert t.owner_of(256) is None
        wa = t.words_for("a")
        assert (np.asarray(fb.unpack_words(jnp.asarray(wa)[None], 256))
                [0, :100] != 0).all()
        f = t.filter_for("a", 3)
        assert f.nq == 3 and f.n_rows == 256
        with pytest.raises(LogicError):
            TenantFilter(ranges={"a": (0, 150), "b": (100, 256)},
                         n_rows=256)
        with pytest.raises(LogicError):
            t.words_for("nope")

    def test_verify_namespaces(self, mres, pq_index):
        good = TenantFilter(ranges={"a": (0, 1000), "b": (1000, N)},
                            n_rows=N)
        integrity.verify(pq_index, namespaces=good)
        # a namespace map that strands live ids fails coverage
        short = TenantFilter(ranges={"a": (0, 1000)}, n_rows=N)
        with pytest.raises(IntegrityError) as e:
            integrity.verify(pq_index, namespaces=short)
        assert e.value.invariant == "namespace.coverage"

    def test_canary_filtered_variant(self, mres, pq_index, dataset):
        db, _, _ = dataset
        cs = canary.make(mres, jnp.asarray(db), metric=0)
        tenants = TenantFilter(ranges={"a": (0, 1000), "b": (1000, N)},
                               n_rows=N)
        r = canary.measure(mres, pq_index, cs,
                           filter=tenants.filter_for("a", 1))
        assert 0.0 <= r <= 1.0
        # an all-rejecting filter leaves nothing to find: recall 1.0
        nothing = SampleFilter.from_words(
            jnp.zeros((1, fb.n_words_for(N)), jnp.int32), N)
        assert canary.measure(mres, pq_index, cs, filter=nothing) == 1.0


# ---------------------------------------------------------------------------
# serving: executor parity, tenancy end-to-end, zero recompiles


class TestServing:
    def test_executor_filtered_parity_and_default(self, mres, pq_index,
                                                  dataset):
        _, q, mask = dataset
        ex = serving.Executor(mres, "ivf_pq", pq_index, ks=(K,),
                              max_batch=NQ, search_params=FULL,
                              warm="jit", filter_rows=N)
        fw = query_filter_words(SampleFilter.from_mask(mask), NQ, "t")
        d1, i1 = ex.search_bucket(jnp.asarray(q), NQ, K, filter_words=fw)
        d2, i2 = ivf_pq.search(mres, FULL, pq_index, jnp.asarray(q), K,
                               filter=SampleFilter.from_mask(mask))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        # the implicit all-ones buffer is the unfiltered identity
        d3, i3 = ex.search_bucket(jnp.asarray(q), NQ, K)
        d4, i4 = ivf_pq.search(mres, FULL, pq_index, jnp.asarray(q), K)
        np.testing.assert_array_equal(np.asarray(i3), np.asarray(i4))
        assert ex.operating_knobs(0)["filtered"] is True

    def test_zero_recompiles_across_varying_filters(self, mres, pq_index,
                                                    dataset):
        _, q, _ = dataset
        rng = np.random.default_rng(5)
        with obs.collecting():
            ex = serving.Executor(mres, "ivf_pq", pq_index, ks=(K,),
                                  max_batch=NQ, search_params=FULL,
                                  warm="jit", filter_rows=N)
            warm = query_filter_words(
                SampleFilter.from_mask(rng.random((NQ, N)) < 0.5), NQ, "t")
            ex.search_bucket(jnp.asarray(q), NQ, K,
                             filter_words=warm)[0].block_until_ready()
            c0 = obs.registry().counter("xla.compiles").value
            for _ in range(6):
                fw = query_filter_words(
                    SampleFilter.from_mask(rng.random((NQ, N)) < 0.2),
                    NQ, "t")
                ex.search_bucket(jnp.asarray(q), NQ, K,
                                 filter_words=fw)[0].block_until_ready()
            c1 = obs.registry().counter("xla.compiles").value
        assert c1 - c0 == 0, "filters are data, not shape"

    def test_server_tenant_isolation_and_composition(self, mres, pq_index,
                                                     dataset):
        _, q, _ = dataset
        tenants = TenantFilter(ranges={"a": (0, 1000), "b": (1000, N)},
                               n_rows=N)
        ex = serving.Executor(mres, "ivf_pq", pq_index, ks=(K,),
                              max_batch=NQ, search_params=FULL,
                              warm="jit", filter_rows=N)
        cfg = serving.ServerConfig(max_batch=NQ, max_wait_us=500.0,
                                   tenants=tenants)
        with serving.Server(ex, cfg) as srv:
            _, i_a = srv.search(q[:3], K, tenant="a", timeout=60)
            assert ((i_a >= 0) & (i_a < 1000)).all()
            _, i_b = srv.search(q[:3], K, tenant="b", timeout=60)
            assert ((i_b >= 1000) & (i_b < N)).all()
            # request filter ANDs with the namespace: even ids only
            even = np.arange(N) % 2 == 0
            _, i_e = srv.search(
                q[:3], K, tenant="a", timeout=60,
                filter=SampleFilter.from_mask(even[None]))
            assert ((i_e % 2 == 0) & (i_e < 1000)).all()
            with pytest.raises(LogicError):
                srv.search(q[:3], K, tenant="ghost", timeout=60)

    def test_filter_on_unconfigured_executor_rejected(self, mres,
                                                      pq_index, dataset):
        _, q, _ = dataset
        ex = serving.Executor(mres, "ivf_pq", pq_index, ks=(K,),
                              max_batch=NQ, search_params=FULL,
                              warm="jit")
        cfg = serving.ServerConfig(max_batch=NQ, max_wait_us=500.0)
        with serving.Server(ex, cfg) as srv:
            with pytest.raises(LogicError):
                srv.search(q[:2], K, timeout=60,
                           filter=np.ones(N, bool))


# ---------------------------------------------------------------------------
# distributed: the routed dispatch preserves the parity contract


class TestDistributedFiltered:
    @pytest.fixture(scope="class")
    def session(self):
        import jax
        from raft_tpu.comms import CommsSession
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 devices")
        mesh = jax.sharding.Mesh(np.asarray(devs[:8]), ("data",))
        s = CommsSession(mesh=mesh, axis_name="data").init()
        yield s
        s.destroy()

    @pytest.fixture(scope="class")
    def dist(self, session):
        from raft_tpu.distributed import ann
        rng = np.random.default_rng(0)
        n, dim = 4096, 16
        db = rng.normal(size=(n, dim)).astype(np.float32)
        q = rng.normal(size=(6, dim)).astype(np.float32)
        handle = session.worker_handle(seed=0)
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=8,
                                    kmeans_n_iters=4)
        ridx = ann.build(handle, params, db, placement="by_list")
        mask = rng.random((6, n)) < 0.3
        return handle, ridx, q, mask

    def test_routed_full_probe_bit_identical(self, dist):
        from raft_tpu.distributed import ann
        handle, ridx, q, mask = dist
        sp_full = ann.ground_truth_params(ridx)
        d1, i1, stats = ann.search(handle, sp_full, ridx, q, K,
                                   filter=SampleFilter.from_mask(mask),
                                   return_stats=True)
        i1 = np.asarray(i1)
        d_u, i_u = ann.search(handle, sp_full, ridx, q, 512)
        ref_d, ref_i = posthoc_reference(d_u, i_u, mask, K)
        np.testing.assert_array_equal(i1, ref_i)
        np.testing.assert_allclose(np.asarray(d1), ref_d, atol=1e-5)
        # per-shard admitted-row counters ride along
        adm = stats["admitted_rows"]
        assert adm.shape == (8,) and (adm >= 0).all()

    def test_routed_grouped_matches_lut(self, dist):
        from raft_tpu.distributed import ann
        handle, ridx, q, mask = dist
        filt = SampleFilter.from_mask(mask)
        sp_l = ann.ground_truth_params(ridx)
        _, i_l = ann.search(handle, sp_l, ridx, q, K, filter=filt)
        sp_f = ivf_pq.SearchParams(n_probes=16, scan_mode="fused")
        _, i_f = ann.search(handle, sp_f, ridx, q, K, filter=filt)
        np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_l))

    def test_data_parallel_admits_only(self, dist, session):
        from raft_tpu.distributed import ann
        handle, _, q, mask = dist
        rng = np.random.default_rng(0)
        db = rng.normal(size=(4096, 16)).astype(np.float32)
        didx = ann.build(handle, ivf_pq.IndexParams(
            n_lists=16, pq_dim=8, kmeans_n_iters=4), db)
        filt = SampleFilter.from_mask(mask)
        _, i3 = ann.search(handle, ivf_pq.SearchParams(
            n_probes=16, scan_mode="lut"), didx, q, K, filter=filt)
        i3 = np.asarray(i3)
        assert all(mask[qi, ii] for qi in range(6)
                   for ii in i3[qi] if ii >= 0)
