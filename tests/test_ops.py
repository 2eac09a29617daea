"""Pallas kernel tests (interpreter mode — CPU-runnable; on-chip parity is
exercised by the same assertions when a TPU backend is present)."""

import numpy as np
import pytest

from raft_tpu.ops import fused_l2_nn_pallas


class TestFusedL2NNPallas:
    @pytest.mark.parametrize("m,n,k", [(300, 700, 64), (256, 512, 128),
                                       (10, 5, 32), (1000, 33, 16)])
    def test_matches_naive(self, m, n, k):
        rng = np.random.default_rng(m + n + k)
        x = rng.random((m, k)).astype(np.float32)
        y = rng.random((n, k)).astype(np.float32)
        d, i = fused_l2_nn_pallas(x, y, interpret=True)
        D = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
        np.testing.assert_allclose(np.asarray(d), D.min(1),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(i), D.argmin(1))

    def test_sqrt_form(self):
        rng = np.random.default_rng(0)
        x = rng.random((64, 8)).astype(np.float32)
        y = rng.random((96, 8)).astype(np.float32)
        d, i = fused_l2_nn_pallas(x, y, sqrt=True, interpret=True)
        D = np.sqrt(((x[:, None, :] - y[None, :, :]) ** 2).sum(-1))
        np.testing.assert_allclose(np.asarray(d), D.min(1),
                                   rtol=1e-4, atol=1e-4)

    def test_dispatch_via_fused_l2_nn(self):
        """fused_l2_nn(use_pallas=True) must agree with the XLA path —
        off-TPU the test asks for the Pallas interpreter, on a TPU
        backend these same assertions check the compiled kernel."""
        from raft_tpu.core.platform import on_tpu
        from raft_tpu.distance import fused_l2_nn
        rng = np.random.default_rng(1)
        x = rng.random((128, 32)).astype(np.float32)
        y = rng.random((256, 32)).astype(np.float32)
        d_x, i_x = fused_l2_nn(x, y)
        d_p, i_p = fused_l2_nn(x, y, use_pallas=True,
                               pallas_interpret=not on_tpu())
        np.testing.assert_allclose(np.asarray(d_x), np.asarray(d_p),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))

    def test_kmeans_fused_assign_update_matches_reference(self):
        """Fused assignment+update pass (interpret mode) vs the plain
        argmin + segment-sum formulation, including row/cluster/dim
        padding and zero-weight rows."""
        import jax.numpy as jnp

        from raft_tpu.ops.kmeans_update_pallas import fused_assign_update

        rng = np.random.default_rng(7)
        n, dim, k = 300, 50, 37
        x = rng.normal(size=(n, dim)).astype(np.float32)
        w = rng.random(n).astype(np.float32)
        w[::11] = 0.0
        c = rng.normal(size=(k, dim)).astype(np.float32)

        sums, counts, dmin = fused_assign_update(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(c), tile=128,
            interpret=True)

        d = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        labels = d.argmin(1)
        ref_sums = np.zeros((k, dim), np.float32)
        ref_counts = np.zeros(k, np.float32)
        np.add.at(ref_sums, labels, x * w[:, None])
        np.add.at(ref_counts, labels, w)
        # bf16 MXU passes: ~1e-3 relative on sums
        np.testing.assert_allclose(np.asarray(sums), ref_sums,
                                   rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(np.asarray(counts), ref_counts,
                                   rtol=1e-5, atol=1e-5)
        # dmin + ||x||^2 must equal the true min squared distance
        np.testing.assert_allclose(
            np.asarray(dmin) + (x * x).sum(-1), d.min(1),
            rtol=2e-2, atol=2e-2)

    def test_kmeans_fused_lloyd_matches_xla_lloyd(self):
        """Fused Lloyd vs the XLA path: bit-equal first step on
        bf16-representable inputs, and equal clustering quality
        (inertia) after a full run — trajectories may legitimately
        diverge on boundary points once centroids stop being
        bf16-representable (means), so element-wise centroid equality
        at iteration 20 is NOT the contract."""
        import jax.numpy as jnp

        from raft_tpu.cluster.kmeans import _lloyd
        from raft_tpu.ops.kmeans_update_pallas import fused_assign_update

        rng = np.random.default_rng(3)
        n, dim, k = 512, 32, 8
        centers = rng.normal(size=(k, dim)).astype(np.float32) * 8
        x = (centers[rng.integers(0, k, n)]
             + rng.normal(size=(n, dim)).astype(np.float32))
        # bf16-representable inputs: the kernel's bf16 rounding of x and
        # c0 is then the identity, so step 1 must agree exactly
        x = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(
            jnp.float32))
        c0 = x[:k].copy()
        w = np.ones(n, np.float32)

        args = (jnp.asarray(x), jnp.asarray(c0), jnp.asarray(w),
                jnp.float32(1e-6), k, 20, 1)        # L2Expanded
        c_ref, _, _, _ = _lloyd(*args, use_fused=False)

        c_cur = jnp.asarray(c0)
        for it in range(20):
            sums, counts, _ = fused_assign_update(
                jnp.asarray(x), jnp.asarray(w), c_cur, tile=128,
                interpret=True)
            means = sums / jnp.maximum(counts, 1.0)[:, None]
            c_cur = jnp.where((counts > 0)[:, None], means, c_cur)
            if it == 0:
                from raft_tpu.cluster.kmeans import (
                    min_cluster_and_distance, update_centroids)
                lab, _ = min_cluster_and_distance(jnp.asarray(x),
                                                  jnp.asarray(c0), metric=1)
                c1, _ = update_centroids(jnp.asarray(x), lab, k,
                                         sample_weight=jnp.asarray(w),
                                         old_centroids=jnp.asarray(c0))
                np.testing.assert_allclose(np.asarray(c_cur),
                                           np.asarray(c1),
                                           rtol=1e-5, atol=1e-5)

        # clustering quality must match: same inertia within bf16 noise
        d_ref = ((x[:, None, :] - np.asarray(c_ref)[None]) ** 2).sum(-1)
        d_fus = ((x[:, None, :] - np.asarray(c_cur)[None]) ** 2).sum(-1)
        np.testing.assert_allclose(d_fus.min(1).sum(), d_ref.min(1).sum(),
                                   rtol=1e-2)

    def test_precision_policy_not_stale(self):
        """Regression: the precision policy keys the jit cache — a call
        under a changed matmul_precision() must not reuse a stale trace."""
        import jax
        from raft_tpu.utils.precision import matmul_precision
        rng = np.random.default_rng(2)
        x = rng.random((64, 16)).astype(np.float32)
        y = rng.random((32, 16)).astype(np.float32)
        d1, _ = fused_l2_nn_pallas(x, y, interpret=True)
        with matmul_precision("default"):
            d2, _ = fused_l2_nn_pallas(x, y, interpret=True)
        with matmul_precision("highest"):
            d3, _ = fused_l2_nn_pallas(x, y, interpret=True)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d3))


class TestExactOneHot:
    """The bf16 three-part split behind every one-hot contraction of the
    scan kernels must hold f32 values and f32-encoded ids exactly."""

    @pytest.mark.parametrize("case", ["wide", "ids", "sentinel"])
    def test_split3_sums_back_exactly(self, case):
        import jax.numpy as jnp

        from raft_tpu.ops import pq_group_scan_pallas as pqp

        rng = np.random.default_rng(5)
        x = {"wide": rng.standard_normal(4096) * 10.0 ** rng.integers(
                 -20, 20, 4096),
             "ids": rng.integers(0, 1 << 24, 4096),
             "sentinel": np.array([pqp._ACC_WORST, -1.0, 0.0, 255.0,
                                   257.0, (1 << 24) - 1])}[case]
        x = jnp.asarray(x.astype(np.float32))
        parts = pqp._split3(x)
        assert all(p.dtype == jnp.bfloat16 for p in parts)
        back = sum(p.astype(jnp.float32) for p in parts)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    def test_query_table_gather_is_exact(self):
        """A one-hot gather of the split query table returns the f32
        rows bit-exactly (the rows a bf16 table would round)."""
        import jax.numpy as jnp

        from raft_tpu.ops import pq_group_scan_pallas as pqp

        rng = np.random.default_rng(6)
        q = rng.standard_normal((300, 128)).astype(np.float32) * 1e3
        table = pqp.query_table(jnp.asarray(q), 384, 128)
        assert table.shape == (3, 384, 128)
        rows = rng.integers(0, 300, 128)
        onehot = jnp.asarray(np.eye(384, dtype=np.float32)[rows])
        got = pqp._gather_rows(onehot, table)
        np.testing.assert_array_equal(np.asarray(got), q[rows])
        assert not np.asarray(table[:, 300:]).any()


class TestFusedScanMatchesXlaTwin:
    """The fused in-kernel top-k scans (interpret mode) against the XLA
    grouped twin at full probe.  k = kt = 10 is not a multiple of the
    8-row sublane tile, so every accumulator row carries pad lanes, and
    eight queries x eight lists leave sentinel slots in every group."""

    @pytest.fixture(scope="class")
    def case(self):
        import jax.numpy as jnp

        from raft_tpu import DeviceResources
        from raft_tpu.filters import SampleFilter, query_filter_words
        from raft_tpu.neighbors import grouped, ivf_pq

        rng = np.random.default_rng(3)
        n, nq = 1024, 8
        data = rng.standard_normal((n, 16)).astype(np.float32)
        q = jnp.asarray(rng.standard_normal((nq, 16)).astype(np.float32))
        idx = ivf_pq._with_code_lanes(ivf_pq.build(
            DeviceResources(seed=0), ivf_pq.IndexParams(n_lists=8, pq_dim=8),
            data))
        probes = ivf_pq._select_clusters(idx.centers, idx.rotation, q, 8,
                                         idx.metric, exact=True)
        ng, _ = grouped.group_capacity(nq, 8, idx.n_lists)
        fw = query_filter_words(
            SampleFilter.from_mask(rng.random((nq, n)) < 0.4), nq, "t")
        return idx, q, probes, ng, fw

    @pytest.mark.parametrize("kernel", ["recon", "codes"])
    @pytest.mark.parametrize("filtered", [False, True])
    def test_fused_equals_xla_twin(self, case, kernel, filtered):
        from raft_tpu.neighbors import ivf_pq

        idx, q, probes, ng, fw = case
        fw = fw if filtered else None
        k = 10
        ref_d, ref_i = ivf_pq._search_impl_recon_grouped(
            idx.centers, idx.list_recon, idx.list_recon_sq,
            idx.list_indices, idx.rotation, q, probes, k, idx.metric, ng,
            64, use_pallas=False, filter_words=fw)
        if kernel == "recon":
            d, i = ivf_pq._search_impl_fused_recon_grouped(
                idx.centers, idx.list_recon, idx.list_recon_sq,
                idx.list_indices, idx.rotation, q, probes, k, k,
                idx.metric, ng, pallas_interpret=True, filter_words=fw)
        else:
            d, i = ivf_pq._search_impl_fused_codes_grouped(
                idx.centers, idx.codebooks, idx.list_code_lanes,
                idx.list_code_rsq, idx.list_indices, idx.rotation, q,
                probes, k, k, idx.metric, ng, idx.pq_bits,
                pallas_interpret=True, filter_words=fw)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))
        np.testing.assert_allclose(np.asarray(d), np.asarray(ref_d),
                                   rtol=1e-5, atol=1e-5)
