"""IVF-PQ tests — recall-based per the reference's ANN pattern
(cpp/test/neighbors/ann_ivf_pq.cuh; ground truth from naive brute force,
``eval_neighbours(min_recall)`` assertions), plus refine composition and
serialization round-trip.
"""

import io

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.distance.types import DistanceType
from raft_tpu.neighbors import ivf_pq, refine
from raft_tpu.random import make_blobs


def naive_knn(db, q, k):
    d = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=1), idx


def recall(found, truth):
    hits = sum(len(set(f) & set(t)) for f, t in zip(found, truth))
    return hits / truth.size


@pytest.fixture(scope="module")
def dataset():
    X, _ = make_blobs(4000, 32, n_clusters=64, cluster_std=1.0, seed=5)
    return np.asarray(X[:3800]), np.asarray(X[3800:3850])


class TestIvfPq:
    def test_build_shapes(self, res, dataset):
        db, _ = dataset
        params = ivf_pq.IndexParams(n_lists=32, pq_dim=8, pq_bits=8,
                                    kmeans_n_iters=10)
        index = ivf_pq.build(res, params, db)
        assert index.n_lists == 32
        assert index.pq_dim == 8
        assert index.pq_book_size == 256
        assert index.codebooks.shape == (8, 256, index.rot_dim // 8)
        assert index.size == db.shape[0]
        ids = np.asarray(index.list_indices)
        valid = ids[ids >= 0]
        assert sorted(valid.tolist()) == list(range(db.shape[0]))

    def test_search_recall(self, res, dataset):
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=32, pq_dim=16, pq_bits=8,
                                    kmeans_n_iters=10)
        index = ivf_pq.build(res, params, db)
        d, i = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=16),
                             index, q, 10)
        _, ti = naive_knn(db, q, 10)
        # PQ-compressed distances: recall margin as the reference's
        # low-precision configs (ann_ivf_pq tests allow low_precision_tol)
        assert recall(np.asarray(i), ti) > 0.7

    def test_search_with_refine(self, res, dataset):
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=32, pq_dim=8, pq_bits=8,
                                    kmeans_n_iters=10)
        index = ivf_pq.build(res, params, db)
        # 4x oversample then exact re-rank — the CAGRA-build composition
        d_raw, i_raw = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=16),
                                     index, q, 10)
        _, i0 = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=16),
                              index, q, 40)
        d, i = refine(res, db, q, i0, 10, metric=DistanceType.L2Expanded)
        _, ti = naive_knn(db, q, 10)
        r_refined = recall(np.asarray(i), ti)
        r_raw = recall(np.asarray(i_raw), ti)
        # refinement must not hurt, and lands decent absolute recall
        assert r_refined >= r_raw
        assert r_refined > 0.75

    def test_bf16_lut(self, res, dataset):
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=5)
        index = ivf_pq.build(res, params, db)
        d32, i32 = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=8),
                                 index, q, 10)
        dbf, ibf = ivf_pq.search(
            res, ivf_pq.SearchParams(n_probes=8, lut_dtype=jnp.bfloat16),
            index, q, 10)
        # bf16 LUT stays close to fp32 results
        assert recall(np.asarray(ibf), np.asarray(i32)) > 0.85

    def test_per_cluster_codebooks(self, res, dataset):
        db, q = dataset
        # pq_dim = dim (1 dim/subspace) + exhaustive probes: quantization
        # is the only loss, so recall must be high — a 0.9 floor instead
        # of the old loose 0.4 smoke check
        params = ivf_pq.IndexParams(
            n_lists=16, pq_dim=32, kmeans_n_iters=10,
            codebook_kind=ivf_pq.CodebookKind.PER_CLUSTER)
        index = ivf_pq.build(res, params, db)
        assert index.codebooks.shape[0] == 16
        d, i = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=16),
                             index, q, 10)
        _, ti = naive_knn(db, q, 10)
        assert recall(np.asarray(i), ti) >= 0.9

    def test_extend(self, res, dataset):
        db, q = dataset
        # 1 dim/subspace + exhaustive probes: an index assembled purely
        # by extend() must reach the same high recall a fresh build does
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=32, kmeans_n_iters=10,
                                    add_data_on_build=False)
        index = ivf_pq.build(res, params, db)
        assert index.size == 0
        index = ivf_pq.extend(res, index, db[:2000],
                              jnp.arange(2000, dtype=jnp.int32))
        index = ivf_pq.extend(res, index, db[2000:],
                              jnp.arange(2000, db.shape[0], dtype=jnp.int32))
        assert index.size == db.shape[0]
        _, i = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=16),
                             index, q, 10)
        _, ti = naive_knn(db, q, 10)
        assert recall(np.asarray(i), ti) >= 0.9
        # matches a fresh add_data_on_build build on the same data
        params2 = ivf_pq.IndexParams(n_lists=16, pq_dim=32,
                                     kmeans_n_iters=10)
        idx2 = ivf_pq.build(res, params2, db)
        _, i2 = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=16),
                              idx2, q, 10)
        assert abs(recall(np.asarray(i), ti)
                   - recall(np.asarray(i2), ti)) < 0.1

    def test_grouped_scan_matches_probe_order_scan(self, res, dataset):
        """The list-centric grouped scan must produce the same results as
        the probe-order scan (same quantized distances; differences are
        bf16-accumulation-order level)."""
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=16, kmeans_n_iters=10)
        index = ivf_pq.build(res, params, db)
        from raft_tpu.neighbors import grouped
        probes = ivf_pq._select_clusters(index.centers, index.rotation,
                                         jnp.asarray(q), 8, index.metric)
        n_groups = grouped.round_groups(
            int(grouped.num_groups(probes, index.n_lists)))
        d1, i1 = ivf_pq._search_impl_recon(
            index.centers, index.list_recon, index.list_indices,
            index.rotation, jnp.asarray(q), 10, 8, index.metric)
        d2, i2 = ivf_pq._search_impl_recon_grouped(
            index.centers, index.list_recon, index.list_recon_sq,
            index.list_indices, index.rotation, jnp.asarray(q), probes,
            10, index.metric, n_groups, 16)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-2, atol=1e-2)
        overlap = np.mean([len(set(a) & set(b)) / 10
                           for a, b in zip(np.asarray(i1), np.asarray(i2))])
        assert overlap > 0.95

    def test_pallas_group_scan_matches_xla_scan(self, res):
        """The fused Pallas group-scan kernel (interpret mode on CPU) must
        agree with the XLA grouped scan."""
        from raft_tpu.neighbors import grouped
        rng = np.random.default_rng(3)
        db = rng.normal(size=(2000, 128)).astype(np.float32)
        q = rng.normal(size=(32, 128)).astype(np.float32)
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=16, kmeans_n_iters=5)
        index = ivf_pq.build(res, params, db)
        assert index.rot_dim % 128 == 0 and index.capacity % 16 == 0
        probes = ivf_pq._select_clusters(index.centers, index.rotation,
                                         jnp.asarray(q), 8, index.metric)
        n_groups = grouped.round_groups(
            int(grouped.num_groups(probes, index.n_lists)))
        args = (index.centers, index.list_recon, index.list_recon_sq,
                index.list_indices, index.rotation, jnp.asarray(q), probes,
                10, index.metric, n_groups, 16)
        d1, i1 = ivf_pq._search_impl_recon_grouped(*args)
        d2, i2 = ivf_pq._search_impl_recon_grouped(
            *args, use_pallas=True, pallas_interpret=True)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-2, atol=1e-2)
        overlap = np.mean([len(set(a) & set(b)) / 10
                           for a, b in zip(np.asarray(i1), np.asarray(i2))])
        assert overlap > 0.95

    def test_extend_fast_path_updates_recon_cache(self, res, dataset):
        """A small extend must take the O(n_new) append path (capacity
        unchanged) and keep the bf16 reconstruction cache identical to a
        full re-decode of the codes."""
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=16, kmeans_n_iters=10)
        index = ivf_pq.build(res, params, db[:3000])
        assert index.list_recon is not None
        cap0 = index.capacity
        index = ivf_pq.extend(res, index, db[3000:3040],
                              jnp.arange(3000, 3040, dtype=jnp.int32))
        assert index.capacity == cap0        # fast path: no repack
        assert index.size == 3040
        full = ivf_pq._decode_lists(index.centers, index.codebooks,
                                    index.list_codes, index.codebook_kind,
                                    index.pq_dim, index.pq_bits)
        valid = np.asarray(index.list_indices) >= 0
        np.testing.assert_array_equal(
            np.asarray(index.list_recon, np.float32)[valid],
            np.asarray(full, np.float32)[valid])
        _, i = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=16),
                             index, q, 10)
        _, ti = naive_knn(db[:3040], q, 10)
        assert recall(np.asarray(i), ti) > 0.6

    def test_rotation_orthonormal(self, res, dataset):
        db, _ = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=5, kmeans_n_iters=3,
                                    force_random_rotation=True)
        index = ivf_pq.build(res, params, db)
        # dim=32 not divisible by 5 -> rot_dim=35, rotation (32, 35) with
        # orthonormal rows ... R R^T = I_32
        r = np.asarray(index.rotation)
        assert r.shape == (32, 35)
        np.testing.assert_allclose(r @ r.T, np.eye(32), atol=1e-4)

    def test_serialize_roundtrip(self, res, dataset):
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=3)
        index = ivf_pq.build(res, params, db)
        buf = io.BytesIO()
        ivf_pq.serialize(res, buf, index)
        buf.seek(0)
        index2 = ivf_pq.deserialize(res, buf)
        d1, i1 = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=4),
                               index, q, 5)
        d2, i2 = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=4),
                               index2, q, 5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5)

    def test_recon_path_matches_lut_path(self, res, dataset):
        """The bf16 reconstruction scan computes the same quantized distance
        as the LUT formulation — indices should agree except for bf16
        rounding flips near ties."""
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=5)
        index = ivf_pq.build(res, params, db)
        assert index.list_recon is not None
        assert index.list_recon.dtype == jnp.bfloat16
        k = 10
        d_r, i_r = ivf_pq.search(
            res, ivf_pq.SearchParams(n_probes=8), index, q, k)
        d_l, i_l = ivf_pq.search(
            res, ivf_pq.SearchParams(n_probes=8, scan_mode="lut"),
            index, q, k)
        i_r, i_l = np.asarray(i_r), np.asarray(i_l)
        overlap = sum(len(set(a) & set(b)) for a, b in zip(i_r, i_l))
        assert overlap / i_l.size >= 0.9
        # bf16 reconstructions round the decoded residuals (~0.4%/element);
        # distances agree coarsely — still far tighter than the reference's
        # fp8 LUT option
        np.testing.assert_allclose(np.asarray(d_r), np.asarray(d_l),
                                   rtol=0.15, atol=0.2)

    def test_pq_bits_4(self, res, dataset):
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=32, pq_bits=4,
                                    kmeans_n_iters=10)
        index = ivf_pq.build(res, params, db)
        assert index.pq_book_size == 16
        # bit-packed codes (ivf_pq_codepacking.cuh parity): pq_bits=4
        # stores HALF the bytes of the one-byte-per-subdim layout
        assert index.code_width == 16
        assert index.pq_dim == 32
        d, i = ivf_pq.search(res, ivf_pq.SearchParams(n_probes=16),
                             index, q, 10)
        _, ti = naive_knn(db, q, 10)
        assert recall(np.asarray(i), ti) > 0.5
        # both search formulations agree on the packed codes
        _, i_lut = ivf_pq.search(res, ivf_pq.SearchParams(
            n_probes=16, scan_mode="lut"), index, q, 10)
        overlap = np.mean([len(set(a) & set(b)) / len(a)
                           for a, b in zip(np.asarray(i),
                                           np.asarray(i_lut))])
        assert overlap >= 0.9

    @pytest.mark.parametrize("pq_bits", [4, 5, 6, 7, 8])
    def test_code_packing_roundtrip(self, pq_bits):
        rng = np.random.default_rng(pq_bits)
        codes = rng.integers(0, 1 << pq_bits,
                             size=(37, 24)).astype(np.uint8)
        packed = ivf_pq._pack_codes(jnp.asarray(codes), pq_bits)
        assert packed.shape == (37, ivf_pq.packed_code_width(24, pq_bits))
        out = ivf_pq._unpack_codes(packed, 24, pq_bits)
        np.testing.assert_array_equal(np.asarray(out), codes)


@pytest.fixture(scope="module")
def scan_index(dataset):
    """One small built index per pq_bits, with every scan cache attached,
    plus the recon-grouped reference results — shared across the
    code-scan parity tests (building dominates their runtime)."""
    from raft_tpu import DeviceResources
    from raft_tpu.neighbors import grouped

    res = DeviceResources(seed=42)
    db, q = dataset
    out = {}
    for pq_bits in (8, 4):
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=8, pq_bits=pq_bits,
                                    kmeans_n_iters=5)
        index = ivf_pq.build(res, params, db)
        probes = ivf_pq._select_clusters(index.centers, index.rotation,
                                         jnp.asarray(q), 8, index.metric)
        ng = grouped.round_groups(
            int(grouped.num_groups(probes, index.n_lists)))
        index = ivf_pq._with_code_lanes(index)
        rd, ri = ivf_pq._search_impl_recon_grouped(
            index.centers, index.list_recon, index.list_recon_sq,
            index.list_indices, index.rotation, jnp.asarray(q), probes,
            10, index.metric, ng, 64)
        out[pq_bits] = (index, probes, ng, np.asarray(rd), np.asarray(ri))
    return jnp.asarray(q), out


def _overlap(a, b, k=10):
    return np.mean([len(set(x) & set(y)) / k for x, y in zip(a, b)])


class TestCodeScan:
    """Compact-code scan parity (ops/pq_code_scan_pallas, interpret mode
    on CPU): the in-kernel unpack + one-hot codebook decode must
    reproduce the bf16 recon cache's distances bit-for-bit-close."""

    @pytest.mark.parametrize("pq_bits", [8, 4])
    def test_codes_matches_recon(self, scan_index, pq_bits):
        q, built = scan_index
        index, probes, ng, rd, ri = built[pq_bits]
        cd, ci = ivf_pq._search_impl_codes_grouped(
            index.centers, index.codebooks, index.list_code_lanes,
            index.list_code_rsq, index.list_indices, index.rotation,
            q, probes, 10, 0, index.metric, ng, index.pq_bits,
            pallas_interpret=True)
        cd, ci = np.asarray(cd), np.asarray(ci)
        assert _overlap(ci, ri) > 0.95
        np.testing.assert_allclose(cd, rd, rtol=1e-2, atol=1e-2)

    def test_per_probe_topk_matches_recon_at_same_kt(self, scan_index):
        """kt parity must compare same-kt paths: the codes kernel's
        per-probe top-kt keep-set equals the recon path's at the same
        kt (kt vs full-k is NOT an identity — a query whose true top-k
        concentrates in one probe legitimately loses candidates)."""
        q, built = scan_index
        index, probes, ng, _, _ = built[8]
        _, ki = ivf_pq._search_impl_codes_grouped(
            index.centers, index.codebooks, index.list_code_lanes,
            index.list_code_rsq, index.list_indices, index.rotation,
            q, probes, 10, 4, index.metric, ng, index.pq_bits,
            pallas_interpret=True)
        _, oi = ivf_pq._search_impl_recon_grouped(
            index.centers, index.list_recon, index.list_recon_sq,
            index.list_indices, index.rotation, q, probes, 10,
            index.metric, ng, 64, kt=4)
        assert _overlap(np.asarray(ki), np.asarray(oi)) > 0.95

    def test_rsq_from_codes_matches_recon_sq(self, scan_index):
        """Per-row squared norms derived straight from the packed codes
        (codes mode carries no recon cache) equal the cache-derived
        norms."""
        _, built = scan_index
        for pq_bits in (8, 4):
            index = built[pq_bits][0]
            rsq = ivf_pq._rsq_from_codes(index.codebooks, index.list_codes,
                                         index.pq_dim, index.pq_bits)
            err = np.max(np.abs(np.asarray(rsq)
                                - np.asarray(index.list_recon_sq)))
            assert err < 1e-3, err

    def test_codes_mode_recall_matches_recon_mode(self, res, dataset):
        """Public search(): scan_mode="codes" must land the same recall
        as scan_mode="recon" at identical operating points (on CPU the
        codes mode runs its portable LUT fallback — the contract is the
        same either way)."""
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=32,
                                    kmeans_n_iters=10)
        index = ivf_pq.build(res, params, db)
        _, ti = naive_knn(db, q, 10)
        recalls = {}
        for mode in ("recon", "codes"):
            sp = ivf_pq.SearchParams(n_probes=16, scan_mode=mode)
            _, i = ivf_pq.search(res, sp, index, q, 10)
            recalls[mode] = recall(np.asarray(i), ti)
        assert recalls["recon"] >= 0.9
        assert abs(recalls["codes"] - recalls["recon"]) < 0.05, recalls


class TestFusedScan:
    """Fused in-kernel top-k parity (interpret mode on CPU): the
    per-query accumulator kernels must reproduce the scatter + select
    reference path at matched kt — same candidates kept per (query,
    probe), same final ids and distances."""

    @pytest.mark.parametrize("kt", [0, 4])
    def test_fused_codes_matches_reference_at_same_kt(self, scan_index,
                                                      kt):
        q, built = scan_index
        index, probes, ng, _, _ = built[8]
        rd, ri = ivf_pq._search_impl_codes_grouped(
            index.centers, index.codebooks, index.list_code_lanes,
            index.list_code_rsq, index.list_indices, index.rotation,
            q, probes, 10, kt, index.metric, ng, index.pq_bits,
            pallas_interpret=True)
        fd, fi = ivf_pq._search_impl_fused_codes_grouped(
            index.centers, index.codebooks, index.list_code_lanes,
            index.list_code_rsq, index.list_indices, index.rotation,
            q, probes, 10, kt, index.metric, ng, index.pq_bits,
            pallas_interpret=True)
        rd, ri = np.asarray(rd), np.asarray(ri)
        fd, fi = np.asarray(fd), np.asarray(fi)
        assert _overlap(fi, ri) > 0.95
        fin = np.isfinite(rd) & np.isfinite(fd)
        np.testing.assert_array_equal(np.isfinite(rd), np.isfinite(fd))
        np.testing.assert_allclose(fd[fin], rd[fin], rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("pq_bits", [8, 4])
    def test_fused_recon_matches_reference(self, scan_index, pq_bits):
        q, built = scan_index
        index, probes, ng, rd, ri = built[pq_bits]
        fd, fi = ivf_pq._search_impl_fused_recon_grouped(
            index.centers, index.list_recon, index.list_recon_sq,
            index.list_indices, index.rotation, q, probes, 10,
            0, index.metric, ng, pallas_interpret=True)
        fd, fi = np.asarray(fd), np.asarray(fi)
        assert _overlap(fi, ri) > 0.95
        fin = np.isfinite(rd) & np.isfinite(fd)
        np.testing.assert_allclose(fd[fin], rd[fin], rtol=1e-4, atol=1e-4)

    # interpreter-mode Pallas at kt=cap+7 dominates this module's wall
    # clock on CPU; the CI fused-tripwire step runs it by node id (no
    # marker filter), keeping it out of the fast tier only.
    @pytest.mark.slow
    def test_fused_kt_exceeds_list_length(self, scan_index):
        """kt past the list capacity clips to cap — every candidate of
        every probed list survives to the merge, so the fused result is
        the exact union top-k."""
        q, built = scan_index
        index, probes, ng, _, _ = built[8]
        cap = index.capacity
        rd, ri = ivf_pq._search_impl_recon_grouped(
            index.centers, index.list_recon, index.list_recon_sq,
            index.list_indices, index.rotation, q, probes, 10,
            index.metric, ng, 64, kt=cap + 7)
        fd, fi = ivf_pq._search_impl_fused_recon_grouped(
            index.centers, index.list_recon, index.list_recon_sq,
            index.list_indices, index.rotation, q, probes, 10,
            cap + 7, index.metric, ng, pallas_interpret=True)
        assert _overlap(np.asarray(fi), np.asarray(ri)) > 0.95
        fin = np.isfinite(np.asarray(rd))
        np.testing.assert_allclose(np.asarray(fd)[fin],
                                   np.asarray(rd)[fin],
                                   rtol=1e-4, atol=1e-4)

    def test_fused_sentinel_rows_stay_masked(self, scan_index):
        """id -1 rows (the integrity mask / tombstone contract) must
        never surface from the fused kernel: zapped candidates drop out
        and exhausted ranks keep id -1 / worst (+inf) distance."""
        q, built = scan_index
        index, probes, ng, _, _ = built[8]
        zapped = jnp.asarray(
            np.where(np.arange(index.capacity)[None, :] % 2 == 0,
                     np.asarray(index.list_indices), -1))
        fd, fi = ivf_pq._search_impl_fused_recon_grouped(
            index.centers, index.list_recon, index.list_recon_sq,
            zapped, index.rotation, q, probes, 10, 0, index.metric,
            ng, pallas_interpret=True)
        fd, fi = np.asarray(fd), np.asarray(fi)
        surviving = set(np.asarray(zapped)[np.asarray(zapped) >= 0])
        assert all(int(i) in surviving for i in fi[fi >= 0])
        # exhausted ranks: -1 id paired with +inf distance, never a
        # finite distance with a stale id
        np.testing.assert_array_equal(fi == -1, ~np.isfinite(fd))

    def test_fused_mode_recall_matches_recon_mode(self, res, dataset):
        """Public search(): scan_mode="fused" lands the same recall as
        "recon" at identical operating points (on CPU it falls back to
        the non-fused backing path — same results either way)."""
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=32,
                                    kmeans_n_iters=10)
        index = ivf_pq.build(res, params, db)
        _, ti = naive_knn(db, q, 10)
        sp_r = ivf_pq.SearchParams(n_probes=16, scan_mode="recon")
        _, i_r = ivf_pq.search(res, sp_r, index, q, 10)
        sp_f = ivf_pq.SearchParams(n_probes=16, scan_mode="fused")
        _, i_f = ivf_pq.search(res, sp_f, index, q, 10)
        r_recon = recall(np.asarray(i_r), ti)
        r_fused = recall(np.asarray(i_f), ti)
        assert r_recon >= 0.9
        assert abs(r_fused - r_recon) < 0.05, (r_fused, r_recon)

    def test_fused_fallback_is_counted(self, res, dataset):
        """The CI tripwire's sensor: every dispatch that asked for the
        fused kernel but ran a fallback must tick
        ivf_pq.search.fused_fallback (on CPU that is every fused/auto
        dispatch — on TPU at the flagship shape the counter must stay
        flat, which bench.py asserts at runtime)."""
        from raft_tpu import observability as obs

        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=32,
                                    kmeans_n_iters=2)
        index = ivf_pq.build(res, params, db)
        obs.enable()
        try:
            reg = obs.registry()
            c0 = reg.counter("ivf_pq.search.fused_fallback").value
            r0 = reg.counter(
                "ivf_pq.search.fused_fallback.reason.backend").value
            sp = ivf_pq.SearchParams(n_probes=8, scan_mode="fused")
            ivf_pq.search(res, sp, index, q, 10)
            c1 = reg.counter("ivf_pq.search.fused_fallback").value
            r1 = reg.counter(
                "ivf_pq.search.fused_fallback.reason.backend").value
        finally:
            obs.disable()
        assert c1 == c0 + 1
        # round-14 reason codes: off-TPU misses attribute to "backend"
        assert r1 == r0 + 1

    def test_fused_supported_at_flagship_shape(self):
        """Static tripwire: the fused kernels must accept the flagship
        bench geometry (1M x 128, 4096 lists, pq_dim 64, kt 16, batch
        5000).  If a VMEM-budget or gate edit regresses this,
        scan_mode=auto would silently fall off the fused kernel at the
        headline operating point — fail HERE, not in the QPS number.
        The planner is host-static, so it is asked as a TPU would ask
        it: "auto" takes the recon cache the index carries, "fused" the
        codes."""
        cap = -(-int(1_000_000 / 4096 * 1.35) // 32) * 32
        facts = dict(placement="single", metric=DistanceType.L2Expanded,
                     cap=cap, rot=128, lists=4096, pq_bits=8, nq=5000,
                     k=10, n_probes=72, on_tpu=True, pq_dim=64,
                     per_subspace=True, has_recon=True, ids_exact=True)
        for mode, form in (("auto", "fused_recon"),
                           ("fused", "fused_codes")):
            sp = ivf_pq.SearchParams(n_probes=72, scan_mode=mode,
                                     per_probe_topk=16)
            plan = ivf_pq.plan_scan(sp, **facts)
            assert (plan.form, plan.reason) == (form, ""), mode


@pytest.fixture(scope="module")
def ring_case():
    """Tiny synthetic geometry for the fused merge's accumulator: 7 lists
    of capacity 32 with the last 5 rows of every list tombstoned (id -1),
    9 queries x 3 probes.  k=256 exceeds the live candidate pool, so the
    accumulator tail stays all-sentinel through the whole scan."""
    from raft_tpu.neighbors import grouped

    rng = np.random.default_rng(0)
    n_lists, cap, rot, nq, n_probes = 7, 32, 128, 9, 3
    probes = np.stack([rng.choice(n_lists, size=n_probes, replace=False)
                       for _ in range(nq)]).astype(np.int32)
    n_groups, _ = grouped.group_capacity(nq, n_probes, n_lists)
    gl, sp = grouped.build_groups(jnp.asarray(probes), n_lists, n_groups)
    qrot = rng.standard_normal((nq, rot)).astype(np.float32)
    centers = rng.standard_normal((n_lists, rot)).astype(np.float32)
    recon = jnp.asarray(
        rng.standard_normal((n_lists, cap, rot)).astype(np.float32),
        jnp.bfloat16)
    rsq = jnp.sum(jnp.asarray(recon, jnp.float32) ** 2, axis=-1)
    ids = rng.integers(0, 1 << 20, size=(n_lists, cap)).astype(np.int32)
    ids[:, -5:] = -1
    return dict(gl=gl, sp=sp, qrot=jnp.asarray(qrot),
                centers=jnp.asarray(centers), recon=recon, rsq=rsq,
                ids=jnp.asarray(ids), ids_np=ids, kt=8,
                n_probes=n_probes, nq=nq, P=nq * n_probes)


class TestWindowedMerge:
    """The fused recon scan's per-step merge at large k and with
    tombstoned rows: the row-addressed kernels merge every grid step,
    and their answers must match the non-fused kernel + a host-side sort
    at matched kt.  Exhausted ranks all carry the sentinel value, so
    their relative id order is unspecified; the epilogue maps every such
    rank to +inf / -1."""

    def _run(self, c, k):
        from raft_tpu.ops import pq_group_scan_pallas as pqp

        v, i = pqp.grouped_l2_scan_fused(
            c["gl"], c["sp"], c["qrot"], c["centers"], c["recon"],
            c["rsq"], c["ids"], c["kt"], k, c["n_probes"], interpret=True)
        return np.asarray(v), np.asarray(i)

    @pytest.mark.parametrize("k", [128, 256])
    def test_large_k_windowed_matches_reference(self, ring_case, k):
        """k past the unrolled-merge ceiling takes the fori-loop merge,
        which must agree with the non-fused kernel + host-side sort at
        matched kt."""
        from raft_tpu.neighbors import grouped
        from raft_tpu.ops import pq_group_scan_pallas as pqp

        c = ring_case
        av, ai = self._run(c, k)
        nv, ni = pqp.grouped_l2_scan(
            c["gl"], c["sp"], c["qrot"], c["centers"], c["recon"],
            c["rsq"], c["ids"], c["kt"], c["n_probes"], interpret=True)
        outd, outi = grouped.scatter_packed(nv, ni, c["sp"], c["P"],
                                            True)
        outd, outi = np.asarray(outd), np.asarray(outi)
        npb = c["n_probes"]
        for q in range(c["nq"]):
            cd = outd[q * npb:(q + 1) * npb].reshape(-1)
            ci = outi[q * npb:(q + 1) * npb].reshape(-1)
            fin = np.isfinite(cd)
            order = np.argsort(cd[fin], kind="stable")
            ref_d, ref_i = cd[fin][order][:k], ci[fin][order][:k]
            good = av[q, :k] < pqp._ACC_WORST / 2
            np.testing.assert_array_equal(av[q, :k][good],
                                          ref_d[:good.sum()])
            np.testing.assert_array_equal(ai[q, :k][good],
                                          ref_i[:good.sum()])
            assert good.sum() == min(k, fin.sum())

    def test_tombstones_never_surface_through_staging_ring(self,
                                                           ring_case):
        """The last 5 rows of every list carry id -1 (the tombstone /
        integrity-mask contract): the accumulator's sentinel fill must
        never resurrect them, and every live rank must carry a real
        (non-tombstoned) id."""
        from raft_tpu.ops import pq_group_scan_pallas as pqp

        ids_np = ring_case["ids_np"]
        alive = set(ids_np[ids_np >= 0].tolist())
        v, i = self._run(ring_case, 64)
        live = v < pqp._ACC_WORST / 2
        # the raw kernel output predates the epilogue, so only live
        # ranks carry a contract: a real (non-tombstoned) id, never the
        # -1 fill
        assert live.any()
        assert (i[live] >= 0).all()
        assert all(int(x) in alive for x in i[live])

    def test_fused_codes_windowed_large_k(self, scan_index):
        """Codes kernel at k=128 (the looped merge) lands the same
        candidates as the non-fused codes path at matched kt."""
        q, built = scan_index
        index, probes, ng, _, _ = built[8]
        args = (index.centers, index.codebooks, index.list_code_lanes,
                index.list_code_rsq, index.list_indices, index.rotation,
                q, probes, 128, 4, index.metric, ng, index.pq_bits)
        fd, fi = ivf_pq._search_impl_fused_codes_grouped(
            *args, pallas_interpret=True)
        fd, fi = np.asarray(fd), np.asarray(fi)
        rd, ri = ivf_pq._search_impl_codes_grouped(
            *args, pallas_interpret=True)
        rd, ri = np.asarray(rd), np.asarray(ri)
        both = np.isfinite(fd) & np.isfinite(rd)
        np.testing.assert_allclose(fd[both], rd[both], rtol=1e-4,
                                   atol=1e-4)
        # k=128 exceeds the kt=4 candidate pool (8 probes x 4), so both
        # paths keep EVERY candidate: the finite id sets match exactly
        for r in range(fi.shape[0]):
            assert (set(fi[r][fi[r] >= 0].tolist())
                    == set(ri[r][ri[r] >= 0].tolist()))


class TestListDataHelpers:
    """Public list-data helpers (reference: ivf_pq_helpers.cuh)."""

    @pytest.mark.parametrize("pq_bits", [4, 8])
    def test_unpack_pack_roundtrip(self, res, dataset, pq_bits):
        from raft_tpu.neighbors import ivf_pq_helpers as h

        db, _ = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=16, pq_bits=pq_bits,
                                    kmeans_n_iters=5)
        index = ivf_pq.build(res, params, db)
        label = int(np.argmax(np.asarray(index.list_sizes)))
        size = int(index.list_sizes[label])
        codes = np.asarray(h.unpack_list_data(res, index, label))
        assert codes.shape == (size, index.pq_dim)
        assert codes.max() < (1 << pq_bits)
        # windowed read agrees with the full read
        win = np.asarray(h.unpack_list_data(res, index, label,
                                            offset=2, n_rows=3))
        np.testing.assert_array_equal(win, codes[2:5])
        # pack the same codes back: index unchanged (incl. recon cache)
        before = np.asarray(index.list_recon[label, :size])
        index = h.pack_list_data(res, index, label, codes)
        np.testing.assert_array_equal(
            np.asarray(h.unpack_list_data(res, index, label)), codes)
        np.testing.assert_array_equal(
            np.asarray(index.list_recon[label, :size]), before)

    def test_pack_edits_search_results(self, res, dataset):
        from raft_tpu.neighbors import ivf_pq_helpers as h

        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=16,
                                    kmeans_n_iters=5)
        index = ivf_pq.build(res, params, db)
        label = int(np.argmax(np.asarray(index.list_sizes)))
        size = int(index.list_sizes[label])
        # overwrite every code in the list with code 0: recon cache must
        # follow (searches see the edit), per the reference's contract
        zeros = np.zeros((size, index.pq_dim), np.uint8)
        index = h.pack_list_data(res, index, label, zeros)
        np.testing.assert_array_equal(
            np.asarray(h.unpack_list_data(res, index, label)), zeros)
        got = np.asarray(index.list_recon[label, :size])
        want = np.asarray(ivf_pq._decode_rows(
            index.codebooks, jnp.asarray(zeros),
            jnp.full((size,), label, jnp.int32), index.codebook_kind))
        np.testing.assert_array_equal(got, want)

    def test_reconstruct_list_data(self, res, dataset):
        from raft_tpu.neighbors import ivf_pq_helpers as h

        db, _ = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=16,
                                    kmeans_n_iters=5)
        index = ivf_pq.build(res, params, db)
        label = int(np.argmax(np.asarray(index.list_sizes)))
        size = int(index.list_sizes[label])
        rec = np.asarray(h.reconstruct_list_data(res, index, label))
        assert rec.shape == (size, db.shape[1])
        ids = np.asarray(index.list_indices[label, :size])
        orig = db[ids]
        # PQ reconstruction error is bounded well below the data scale
        rel = (np.linalg.norm(rec - orig, axis=1)
               / np.maximum(np.linalg.norm(orig, axis=1), 1e-6))
        assert float(np.median(rel)) < 0.5


class TestGroupCapacity:
    """Round 10: shape-static group capacity — the grouped dispatch no
    longer syncs a per-batch group count, and a calibrated index's
    tightened capacity is covered by the in-graph overflow fallback."""

    def test_worst_bound_is_exact_and_total(self):
        from raft_tpu.neighbors import grouped
        cap, exact = grouped.group_capacity(16, 8, 32)
        assert exact
        assert cap == -(-16 * 8 // grouped.GROUP) + min(32, 16 * 8)
        # degenerate batch: still a valid (static) dispatch shape
        assert grouped.group_capacity(0, 8, 32) == (1, True)
        # calibrated capacity never exceeds the worst bound
        t, e = grouped.group_capacity(16, 8, 32, est=0.9)
        assert t <= cap and (e or t < cap)

    def test_probe_overlap_order_above_int32_key_range(self):
        """Regression (round 10): at n_lists=65536 the old fused sort
        key r0*(n_lists+1)+r1 wraps int32 — the two-pass stable lexsort
        must match numpy's lexsort exactly."""
        from raft_tpu.neighbors import grouped
        n_lists = 65536
        assert (n_lists + 1) ** 2 > np.iinfo(np.int32).max
        rng = np.random.default_rng(3)
        probes = rng.integers(0, n_lists, size=(512, 4), dtype=np.int32)
        order = np.asarray(grouped.probe_overlap_order(
            jnp.asarray(probes), n_lists))
        r0 = np.minimum(probes[:, 0], n_lists)
        r1 = np.minimum(probes[:, 1], n_lists)
        np.testing.assert_array_equal(order, np.lexsort((r1, r0)))
        # and the small-n_lists fast path agrees with the same model
        small = rng.integers(0, 64, size=(256, 4), dtype=np.int32)
        got = np.asarray(grouped.probe_overlap_order(jnp.asarray(small),
                                                     64))
        np.testing.assert_array_equal(
            got, np.lexsort((np.minimum(small[:, 1], 64),
                             np.minimum(small[:, 0], 64))))

    def test_executable_reuse_across_group_counts(self, res, dataset):
        """Two batches at the SAME shape with DIFFERENT true group
        counts must share one executable — the capacity, not the count,
        is the compiled shape (the round-10 churn fix)."""
        from raft_tpu import observability as obs
        from raft_tpu.neighbors import grouped
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=32, pq_dim=8,
                                    kmeans_n_iters=5,
                                    cache_reconstructions=True)
        index = ivf_pq.build(res, params, db)
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="recon")
        narrow = np.tile(np.asarray(q[:1]), (16, 1))
        spread = np.asarray(q[:16])
        pn = ivf_pq._select_clusters(index.centers, index.rotation,
                                     jnp.asarray(narrow), 8, index.metric)
        ps = ivf_pq._select_clusters(index.centers, index.rotation,
                                     jnp.asarray(spread), 8, index.metric)
        assert (int(grouped.num_groups(pn, 32))
                < int(grouped.num_groups(ps, 32)))
        with obs.collecting():
            ivf_pq.search(res, sp, index, narrow, 10)    # warm the shape
            c0 = obs.registry().counter("xla.compiles").value
            ivf_pq.search(res, sp, index, spread, 10)
            ivf_pq.search(res, sp, index, narrow, 10)
            c1 = obs.registry().counter("xla.compiles").value
        assert c1 == c0, f"{c1 - c0} recompiles across group-count change"
        # the churn mechanism itself is gone: no per-batch group cache
        assert not hasattr(grouped, "cached_groups")
        assert not hasattr(grouped, "commit_groups")

    def test_calibrated_overflow_redispatch_is_exact(self, res, dataset,
                                                     monkeypatch):
        """Calibrate on a narrow batch, then search a wider one: the
        overflow counter must tick and the worst-bound re-dispatch must
        return exactly the uncalibrated answer."""
        from raft_tpu import observability as obs
        from raft_tpu.neighbors import grouped
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=32, pq_dim=8,
                                    kmeans_n_iters=5,
                                    cache_reconstructions=True)
        index = ivf_pq.build(res, params, db)
        # drop the compile-cache quantum so this test-sized index can
        # exceed a tightened capacity (at the default 256 the rounded
        # capacity clamps to the worst bound at this scale)
        monkeypatch.setattr(grouped, "_GROUP_ROUND", 1)
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="recon")
        spread = np.asarray(q)                 # 50 blob queries
        d0, i0 = ivf_pq.search(res, sp, index, spread, 10)
        narrow = np.tile(np.asarray(q[:1]), (len(spread), 1))
        est = ivf_pq.calibrate_group_capacity(res, index, narrow, 8)
        assert 0.0 < est < 1.0
        cap, exact = grouped.group_capacity(len(spread), 8, 32,
                                            est=index.group_est)
        worst, _ = grouped.group_capacity(len(spread), 8, 32)
        assert not exact and cap < worst, (cap, worst)
        with obs.collecting():
            d1, i1 = ivf_pq.search(res, sp, index, spread, 10)
            n_over = obs.registry().counter(
                "ivf_pq.search.group_overflow").value
        assert n_over >= 1, "wide batch must trip the overflow gate"
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
        # repeated calibration ratchets: a wider batch raises the
        # estimate, a narrower one never lowers it
        est2 = ivf_pq.calibrate_group_capacity(res, index, spread, 8)
        assert est2 >= est
        assert ivf_pq.calibrate_group_capacity(res, index, narrow, 8) == est2

    def test_group_est_rides_serialization_v4(self, res, dataset):
        from raft_tpu.neighbors import grouped
        db, q = dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=8,
                                    kmeans_n_iters=4,
                                    cache_reconstructions=True)
        index = ivf_pq.build(res, params, db)
        ivf_pq.calibrate_group_capacity(res, index, np.asarray(q), 8)
        assert index.group_est > 0.0
        buf = io.BytesIO()
        ivf_pq.serialize(res, buf, index)
        buf.seek(0)
        back = ivf_pq.deserialize(res, buf)
        assert back.group_est == index.group_est


def test_encode_chunks_match_rowwise_argmin():
    """_encode splits rows into 65,536-row chunks (one call each); the
    codes of every chunk, the padded tail included, equal the per-row
    argmin over each subspace's codebook."""
    rng = np.random.default_rng(5)
    n, pq_dim, book, pq_len = 70_000, 4, 256, 2
    books = rng.standard_normal((pq_dim, book, pq_len)).astype(np.float32)
    resid = rng.standard_normal((n, pq_dim, pq_len)).astype(np.float32)
    codes = np.asarray(ivf_pq._encode(jnp.asarray(books), jnp.asarray(resid),
                                      ivf_pq.CodebookKind.PER_SUBSPACE))
    assert codes.shape == (n, pq_dim) and codes.dtype == np.uint8
    for rows in (slice(0, 500), slice(65_400, 66_000), slice(n - 500, n)):
        d = ((resid[rows, :, None, :] - books[None]) ** 2).sum(-1)
        np.testing.assert_array_equal(codes[rows], d.argmin(-1))
