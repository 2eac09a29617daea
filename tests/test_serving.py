"""raft_tpu.serving — dynamic batching, admission control, warm executors.

Covers the batcher edge cases the ISSUE names (single in-flight query
hitting max_wait, queue-full shedding, deadline expiry while queued,
per-tenant quota exhaustion, padded-row masking through the integrity
mask path), the zero-recompile steady-state contract, and the
bucket-keyed AOT executable cache (export→load→search round trip per
bucket; distinct batch sizes must not collide).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import observability as obs
from raft_tpu import serving
from raft_tpu.core import aot
from raft_tpu.neighbors import ivf_flat, ivf_pq
from raft_tpu.observability import flight, trace
from raft_tpu.resilience.retry import Deadline, DeadlineExceededError


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.disable()
    obs.reset()
    trace.disable_tracing()
    flight.clear()
    yield
    obs.disable()
    obs.reset()
    trace.disable_tracing()
    flight.clear()


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches():
    # bucket warm-ups and generation swaps compile many executables;
    # release them at teardown so later modules in a full-suite run
    # don't inherit the accumulated JIT code mappings
    yield
    jax.clear_caches()


def _dataset(n=4000, dim=32, seed=0):
    rng = np.random.default_rng(seed)
    db = rng.normal(size=(n, dim)).astype(np.float32)
    q = rng.normal(size=(64, dim)).astype(np.float32)
    return jnp.asarray(db), jnp.asarray(q)


@pytest.fixture(scope="module")
def pq_setup():
    from raft_tpu import DeviceResources
    res = DeviceResources(seed=42)
    db, q = _dataset()
    index = ivf_pq.build(
        res, ivf_pq.IndexParams(n_lists=32, pq_dim=8, kmeans_n_iters=4), db)
    sp = ivf_pq.SearchParams(n_probes=8)
    return res, db, q, index, sp


def _executor(pq_setup, max_batch=16, ks=(5,), warm="aot"):
    res, _, _, index, sp = pq_setup
    return serving.Executor(res, "ivf_pq", index, ks=ks,
                            max_batch=max_batch, search_params=sp,
                            warm=warm)


# ---------------------------------------------------------------------------
# buckets


class TestBuckets:
    def test_bucket_sizes_powers_of_two(self):
        assert serving.bucket_sizes(16) == (1, 2, 4, 8, 16)
        # non-power max_batch is still included (the peak shape)
        assert serving.bucket_sizes(24) == (1, 2, 4, 8, 16, 24)
        assert serving.bucket_sizes(16, min_bucket=4) == (4, 8, 16)

    def test_bucket_for(self):
        assert serving.bucket_for(1, 16) == 1
        assert serving.bucket_for(3, 16) == 4
        assert serving.bucket_for(16, 16) == 16
        with pytest.raises(Exception):
            serving.bucket_for(17, 16)

    def test_pad_rows(self):
        x = jnp.ones((3, 4))
        p = serving.pad_rows(x, 8)
        assert p.shape == (8, 4)
        np.testing.assert_array_equal(np.asarray(p[3:]), 0.0)
        assert serving.pad_rows(x, 3) is x


# ---------------------------------------------------------------------------
# admission


class TestAdmission:
    def test_token_bucket(self):
        t = [0.0]
        tb = serving.TokenBucket(rate=10.0, burst=5.0, clock=lambda: t[0])
        assert tb.try_acquire(5)
        assert not tb.try_acquire(1)      # exhausted
        t[0] += 0.5                       # refills 5 tokens
        assert tb.try_acquire(5)
        assert not tb.try_acquire(1)

    def test_queue_full_shed(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        cfg = serving.ServerConfig(max_batch=16, max_queue_rows=4,
                                   max_wait_us=50_000)
        q = pq_setup[2]
        srv = serving.Server(ex, cfg).start()
        try:
            # park the dispatcher so submissions stay queued
            srv.batcher.stop(drain=False)
            fut = srv.submit(q[:3], 5)
            with pytest.raises(serving.Overloaded):
                srv.submit(q[:3], 5)      # 3 + 3 > 4 -> shed
            srv.batcher.start()           # resume; queued request completes
            d, i = fut.result(timeout=30)
            assert d.shape == (3, 5)
        finally:
            srv.stop()

    def test_oversized_request_rejected(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        with serving.Server(ex, serving.ServerConfig(max_batch=16)) as srv:
            q = pq_setup[2]
            with pytest.raises(serving.Overloaded):
                srv.submit(q[:17], 5)

    def test_tenant_quota_exhaustion(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        cfg = serving.ServerConfig(
            max_batch=16, max_wait_us=100.0,
            tenant_quotas={"metered": (1.0, 4.0)})   # 4-row burst
        q = pq_setup[2]
        with serving.Server(ex, cfg) as srv:
            srv.search(q[:4], 5, tenant="metered")   # spends the burst
            with pytest.raises(serving.QuotaExceeded):
                srv.submit(q[:4], 5, tenant="metered")
            # other tenants are unmetered
            d, i = srv.search(q[:4], 5, tenant="other")
            assert d.shape == (4, 5)

    def test_quota_exceeded_is_overloaded(self):
        assert issubclass(serving.QuotaExceeded, serving.Overloaded)

    def test_expired_deadline_rejected_at_submit(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        q = pq_setup[2]
        with serving.Server(ex, serving.ServerConfig(max_batch=16)) as srv:
            with pytest.raises(serving.Overloaded):
                srv.submit(q[:2], 5, deadline=Deadline(0.0))


# ---------------------------------------------------------------------------
# batcher


class TestBatcher:
    def test_single_query_hits_max_wait(self, pq_setup):
        """One in-flight query must dispatch after ~max_wait_us even with
        no other traffic to fill the bucket."""
        ex = _executor(pq_setup, warm="jit")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=20_000)
        q = pq_setup[2]
        with serving.Server(ex, cfg) as srv:
            srv.search(q[:1], 5)                     # warm the live path
            t0 = time.monotonic()
            d, i = srv.submit(q[:1], 5).result(timeout=10)
            waited = time.monotonic() - t0
            assert d.shape == (1, 5)
            # dispatched by the max_wait timer: NOT immediately (the
            # bucket never fills) and well before the 10s future timeout
            assert waited < 5.0
            assert np.asarray(i).min() >= 0

    def test_full_bucket_dispatches_before_max_wait(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        # absurd max_wait: only the max_batch trigger can dispatch
        cfg = serving.ServerConfig(max_batch=8, max_wait_us=60_000_000)
        q = pq_setup[2]
        with serving.Server(ex, cfg) as srv:
            futs = [srv.submit(q[j:j + 1], 5) for j in range(8)]
            outs = [f.result(timeout=30) for f in futs]
        assert all(o[0].shape == (1, 5) for o in outs)

    def test_deadline_expiry_while_queued(self, pq_setup):
        """A request whose deadline lapses in the queue fails with
        DeadlineExceededError at dispatch, and does not poison the batch."""
        ex = _executor(pq_setup, warm="jit")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=200_000)
        q = pq_setup[2]
        t = [0.0]
        clock = lambda: t[0]                          # noqa: E731
        with serving.Server(ex, cfg) as srv:
            dead = Deadline(0.05, clock=clock)        # 50 ms budget
            doomed = srv.submit(q[:2], 5, deadline=dead)
            t[0] += 1.0                               # budget lapses queued
            ok = srv.submit(q[:3], 5)
            d, i = ok.result(timeout=10)
            assert d.shape == (3, 5)
            with pytest.raises(DeadlineExceededError):
                doomed.result(timeout=10)

    def test_batch_coalescing_matches_direct_search(self, pq_setup):
        res, _, q, index, sp = pq_setup
        ex = _executor(pq_setup, warm="aot")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=50_000)
        with serving.Server(ex, cfg) as srv:
            futs = [srv.submit(q[j * 3:(j + 1) * 3], 5) for j in range(4)]
            outs = [f.result(timeout=30) for f in futs]
        for j, (d, i) in enumerate(outs):
            dd, ii = ivf_pq.search(res, sp, index, q[j * 3:(j + 1) * 3], 5)
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ii))
            np.testing.assert_allclose(np.asarray(d), np.asarray(dd),
                                       rtol=1e-5)

    def test_mixed_k_split_into_separate_batches(self, pq_setup):
        ex = _executor(pq_setup, ks=(5, 10), warm="jit")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=10_000)
        q = pq_setup[2]
        with serving.Server(ex, cfg) as srv:
            f5 = srv.submit(q[:2], 5)
            f10 = srv.submit(q[:2], 10)
            assert f5.result(timeout=10)[0].shape == (2, 5)
            assert f10.result(timeout=10)[0].shape == (2, 10)

    def test_unknown_k_rejected(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        with serving.Server(ex, serving.ServerConfig(max_batch=16)) as srv:
            with pytest.raises(Exception):
                srv.submit(pq_setup[2][:2], 7)


# ---------------------------------------------------------------------------
# padded-row masking (the integrity mask path)


class TestPaddedRows:
    def test_padded_rows_masked(self, pq_setup):
        """Executor-level contract: rows past n_valid return id -1 and
        the worst distance, exactly like boundary-masked rows."""
        ex = _executor(pq_setup, warm="aot")
        ex.warmup()
        q = pq_setup[2]
        padded = ex.pad(q[:3], 8)
        d, i = ex.search_bucket(padded, 3, 5)
        d, i = np.asarray(d), np.asarray(i)
        assert (i[3:] == -1).all()
        assert np.isposinf(d[3:]).all()
        # real rows untouched
        assert (i[:3] >= 0).all()
        assert np.isfinite(d[:3]).all()

    def test_nonfinite_query_rows_masked_under_mask_policy(self, pq_setup):
        from raft_tpu import config
        ex = _executor(pq_setup, warm="jit")
        q = np.asarray(pq_setup[2][:4]).copy()
        q[1] = np.nan
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=5_000)
        with serving.Server(ex, cfg) as srv:
            with config.validation_policy("mask"):
                d, i = srv.search(q, 5)
        d, i = np.asarray(d), np.asarray(i)
        assert (i[1] == -1).all() and np.isposinf(d[1]).all()
        assert (i[[0, 2, 3]] >= 0).all()

    def test_nonfinite_rejected_under_raise_policy(self, pq_setup):
        from raft_tpu import config
        from raft_tpu.integrity import ValidationError
        ex = _executor(pq_setup, warm="jit")
        q = np.asarray(pq_setup[2][:2]).copy()
        q[0] = np.inf
        with serving.Server(ex, serving.ServerConfig(max_batch=16)) as srv:
            with config.validation_policy("raise"):
                with pytest.raises(ValidationError):
                    srv.submit(q, 5)


# ---------------------------------------------------------------------------
# warmup / zero-recompile contract


class TestWarmExecutors:
    def test_zero_recompiles_after_warmup(self, pq_setup):
        ex = _executor(pq_setup, warm="aot")
        with obs.collecting():
            srv = serving.Server(
                ex, serving.ServerConfig(max_batch=16,
                                         max_wait_us=2_000)).start()
            # clients submit host data; a device-side q[:m] would itself
            # compile one slice program per novel m and pollute the count
            q = np.asarray(pq_setup[2])
            try:
                for m in (1, 3, 8, 16, 5, 2):
                    srv.search(q[:m], 5)
                c0 = obs.registry().counter("xla.compiles").value
                for m in (2, 16, 1, 7, 4, 16, 3):
                    srv.search(q[:m], 5)
                c1 = obs.registry().counter("xla.compiles").value
            finally:
                srv.stop()
        assert c1 == c0, f"{c1 - c0} recompiles in steady state"

    def test_zero_recompiles_after_warmup_fused(self, pq_setup):
        """Round-7: scan_mode="fused" rides the same AOT bucket-warmup
        contract — its executables carry a distinct ExecutableCache key
        component and steady state stays recompile-free."""
        res, _, q, index, _ = pq_setup
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="fused",
                                 per_probe_topk=4)
        ex = serving.Executor(res, "ivf_pq", index, ks=(5,),
                              max_batch=16, search_params=sp, warm="aot")
        with obs.collecting():
            srv = serving.Server(
                ex, serving.ServerConfig(max_batch=16,
                                         max_wait_us=2_000)).start()
            q = np.asarray(q)
            try:
                for m in (1, 3, 8, 16, 5, 2):
                    srv.search(q[:m], 5)
                c0 = obs.registry().counter("xla.compiles").value
                for m in (2, 16, 1, 7, 4, 16, 3):
                    srv.search(q[:m], 5)
                c1 = obs.registry().counter("xla.compiles").value
            finally:
                srv.stop()
        assert c1 == c0, f"{c1 - c0} recompiles in steady state"

    def test_fused_prewarm_distinct_cache_key(self, pq_setup):
        """Fused-mode bucket executables must not collide with lut/codes
        entries — scan_mode is part of the ExecutableCache key."""
        res, _, q, index, _ = pq_setup
        from raft_tpu.core.aot import ExecutableCache
        cache = ExecutableCache()
        f1 = cache.get("ivf_pq", res, index, batch=8, k=5, n_probes=8,
                       scan_mode="fused")
        f2 = cache.get("ivf_pq", res, index, batch=8, k=5, n_probes=8,
                       scan_mode="lut")
        f3 = cache.get("ivf_pq", res, index, batch=8, k=5, n_probes=8,
                       scan_mode="fused")
        assert f1 is f3
        assert f1 is not f2
        d, i = f1(jnp.asarray(np.asarray(q)[:8]))
        assert d.shape == (8, 5) and i.shape == (8, 5)

    def test_serving_metrics_recorded(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        with obs.collecting():
            cfg = serving.ServerConfig(max_batch=16, max_wait_us=2_000)
            with serving.Server(ex, cfg) as srv:
                for m in (1, 3, 5):
                    srv.search(pq_setup[2][:m], 5)
            snap = obs.snapshot()
        assert snap["counters"]["serving.admitted"] == 3
        assert snap["counters"]["serving.batches"] >= 1
        assert snap["histograms"]["serving.latency.total"]["count"] == 3
        h = snap["histograms"]["serving.latency.queue"]
        assert h["p99"] >= h["p50"] >= 0.0

    def test_ivf_flat_executor(self, pq_setup):
        res, db, q, _, _ = pq_setup
        index = ivf_flat.build(
            res, ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4), db)
        sp = ivf_flat.SearchParams(n_probes=8)
        ex = serving.Executor(res, "ivf_flat", index, ks=(5,), max_batch=8,
                              search_params=sp)
        with serving.Server(ex, serving.ServerConfig(max_batch=8)) as srv:
            d, i = srv.search(q[:3], 5)
        dd, ii = ivf_flat.search(res, sp, index, q[:3], 5)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ii))

    def test_brute_force_executor(self, pq_setup):
        res, db, q, _, _ = pq_setup
        from raft_tpu.neighbors import brute_force
        ex = serving.Executor(res, "brute_force", db, ks=(5,), max_batch=8)
        with serving.Server(ex, serving.ServerConfig(max_batch=8)) as srv:
            d, i = srv.search(q[:3], 5)
        dd, ii = brute_force.knn(res, db, q[:3], 5)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ii))


# ---------------------------------------------------------------------------
# the AOT executable cache (bucket keying)


class TestExecutableCache:
    def test_round_trip_per_bucket(self, pq_setup):
        """Export→load→search round trip at every bucket size: each
        bucket's executable accepts exactly its shape and reproduces the
        direct search."""
        res, _, q, index, sp = pq_setup
        cache = aot.ExecutableCache()
        for batch in (1, 2, 4, 8):
            g = cache.get("ivf_pq", res, index, batch=batch, k=5,
                          n_probes=8, scan_mode="recon")
            d, i = g(q[:batch])
            dd, ii = ivf_pq.search(res, sp, index, q[:batch], 5)
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ii))
            np.testing.assert_allclose(np.asarray(d), np.asarray(dd),
                                       rtol=1e-5)
        assert len(cache) == 4

    def test_batch_sizes_do_not_collide(self, pq_setup):
        """Same index, different batch sizes -> distinct executables;
        each accepts only its own batch shape."""
        res, _, q, index, _ = pq_setup
        cache = aot.ExecutableCache()
        g2 = cache.get("ivf_pq", res, index, batch=2, k=5, n_probes=8,
                       scan_mode="recon")
        g4 = cache.get("ivf_pq", res, index, batch=4, k=5, n_probes=8,
                       scan_mode="recon")
        assert g2 is not g4
        assert g2(q[:2])[0].shape == (2, 5)
        assert g4(q[:4])[0].shape == (4, 5)
        with pytest.raises(Exception):
            jax.block_until_ready(g2(q[:4]))
        # a repeat lookup is a cache hit
        assert cache.get("ivf_pq", res, index, batch=2, k=5, n_probes=8,
                         scan_mode="recon") is g2

    def test_key_includes_k_and_nprobes(self, pq_setup):
        res, _, q, index, _ = pq_setup
        cache = aot.ExecutableCache()
        a = cache.get("ivf_pq", res, index, batch=2, k=5, n_probes=8,
                      scan_mode="recon")
        b = cache.get("ivf_pq", res, index, batch=2, k=3, n_probes=8,
                      scan_mode="recon")
        c = cache.get("ivf_pq", res, index, batch=2, k=5, n_probes=4,
                      scan_mode="recon")
        assert len({id(a), id(b), id(c)}) == 3
        assert b(q[:2])[0].shape == (2, 3)

    def test_dead_index_never_hits(self, pq_setup):
        """An id()-recycled dead index must miss, not serve stale
        executables (the weakref validation)."""
        res, db, q, _, sp = pq_setup
        cache = aot.ExecutableCache()
        index1 = ivf_pq.build(
            res, ivf_pq.IndexParams(n_lists=16, pq_dim=8,
                                    kmeans_n_iters=2), db[:2000])
        g1 = cache.get("ivf_pq", res, index1, batch=2, k=5, n_probes=4,
                       scan_mode="recon")
        key = next(iter(cache._entries))
        # simulate id reuse: a different index object under the same key
        index2 = ivf_pq.build(
            res, ivf_pq.IndexParams(n_lists=16, pq_dim=8,
                                    kmeans_n_iters=2), db[2000:])
        cache._entries[key] = (cache._entries[key][0], g1)
        g2 = cache.get("ivf_pq", res, index2, batch=2, k=5, n_probes=4,
                       scan_mode="recon")
        assert g2 is not g1


# ---------------------------------------------------------------------------
# generation swaps (mutation satellite)


class TestGenerationSwap:
    """extend/delete land on readers only through ``swap_index``: after a
    swap, every bucket executable serves the fresh generation (zero
    wrong-generation executions) and steady state stays recompile-free."""

    def _far_point(self, dim=32):
        # a row far outside the data cloud: its own nearest neighbor by a
        # huge margin, so any request still served by the OLD generation's
        # executables is caught by a single top-1 check
        return np.full((1, dim), 50.0, np.float32)

    def test_extend_then_swap_hits_fresh_index_every_bucket(self,
                                                            pq_setup):
        res, db, _, index, sp = pq_setup
        new_id = int(db.shape[0])
        ex = _executor(pq_setup, warm="aot")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=2_000)
        probe = self._far_point()
        with serving.Server(ex, cfg) as srv:
            _, before = srv.search(probe, 5)
            assert new_id not in np.asarray(before)
            extended = ivf_pq.extend(
                res, index, jnp.asarray(probe),
                np.asarray([new_id], np.int64))
            n_fns = srv.swap_index(extended)
            assert n_fns == len(ex.buckets) * len(ex.ks)
            assert ex.index is extended
            # every bucket size must route to the new generation: pad the
            # probe into requests landing in each bucket
            for m in (1, 2, 3, 8, 16):
                q = np.repeat(probe, m, axis=0)
                _, ids = srv.search(q, 5)
                ids = np.asarray(ids)
                assert (ids[:, 0] == new_id).all(), (m, ids[:, 0])

    def test_zero_steady_state_recompiles_across_swap(self, pq_setup):
        res, db, _, index, _ = pq_setup
        ex = _executor(pq_setup, warm="aot")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=2_000)
        q = np.asarray(pq_setup[2])
        with obs.collecting():
            with serving.Server(ex, cfg) as srv:
                for m in (1, 3, 8, 16, 5, 2):
                    srv.search(q[:m], 5)
                mutated = ivf_pq.delete(res, index, [0, 1, 2])
                srv.swap_index(mutated)   # re-warm happens HERE, not later
                c0 = obs.registry().counter("xla.compiles").value
                for m in (2, 16, 1, 7, 4, 16, 3):
                    srv.search(q[:m], 5)
                c1 = obs.registry().counter("xla.compiles").value
                swaps = obs.registry().counter(
                    "serving.generation_swaps").value
        assert c1 == c0, f"{c1 - c0} recompiles in post-swap steady state"
        assert swaps == 1

    def test_cache_keys_generations_apart(self, pq_setup):
        """Same index object, different generation stamp -> distinct
        executables (the rebalancer mutates and re-serves the same
        logical index; a stale hit would serve deleted rows)."""
        res, _, q, index, _ = pq_setup
        cache = aot.ExecutableCache()
        a = cache.get("ivf_pq", res, index, batch=2, k=5, n_probes=8,
                      scan_mode="recon")
        gen0 = getattr(index, "generation", 0)
        try:
            index.generation = gen0 + 1
            b = cache.get("ivf_pq", res, index, batch=2, k=5, n_probes=8,
                          scan_mode="recon")
            assert b is not a
            # same generation again -> cache hit
            assert cache.get("ivf_pq", res, index, batch=2, k=5,
                             n_probes=8, scan_mode="recon") is b
        finally:
            index.generation = gen0

    def test_swap_rejects_dim_mismatch(self, pq_setup):
        res, db, _, index, sp = pq_setup
        ex = _executor(pq_setup, warm="jit")
        narrow = ivf_pq.build(
            res, ivf_pq.IndexParams(n_lists=8, pq_dim=4, kmeans_n_iters=2),
            np.asarray(db)[:500, :16])
        with pytest.raises(Exception, match="dim"):
            ex.swap_index(narrow)


# ---------------------------------------------------------------------------
# per-request tracing + flight recorder on the live serving path (PR 11)


class TestServingTracing:
    def test_traced_request_records_full_span_chain(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=2_000)
        q = np.asarray(pq_setup[2])
        with obs.collecting(), trace.tracing_scope():
            with serving.Server(ex, cfg) as srv:
                srv.search(q[:1], 5)              # warm the live path
                flight.clear()
                d, i = srv.search(q[:3], 5, tenant="t0")
        assert d.shape == (3, 5)
        traces = flight.traces()
        assert len(traces) == 1
        rt = traces[0]
        assert rt.name == "serving.request" and rt.t1 is not None
        names = [s.name for s in rt.spans]
        for expected in ("serving.admission", "serving.queue",
                        "serving.batch_cut", "serving.exec",
                        "serving.result_slice"):
            assert expected in names, (expected, names)
        assert rt.attrs["tenant"] == "t0"
        assert rt.attrs["rows"] == 3 and rt.attrs["k"] == 5
        cut = next(s for s in rt.spans if s.name == "serving.batch_cut")
        assert cut.attrs["rows"] == 3

    def test_untraced_requests_record_nothing(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=2_000)
        q = np.asarray(pq_setup[2])
        with serving.Server(ex, cfg) as srv:      # tracing off (default)
            srv.search(q[:3], 5)
        assert flight.traces() == []

    def test_deadline_shed_at_submit_lands_flight_event(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        with serving.Server(ex, serving.ServerConfig(max_batch=16)) as srv:
            with pytest.raises(serving.Overloaded):
                srv.submit(pq_setup[2][:2], 5, deadline=Deadline(0.0))
        evs = flight.events("serving.shed.deadline")
        assert len(evs) == 1
        assert evs[0]["attrs"]["phase"] == "submit"
        assert evs[0]["attrs"]["rows"] == 2

    def test_deadline_expiry_while_queued_lands_flight_event(self,
                                                             pq_setup):
        ex = _executor(pq_setup, warm="jit")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=200_000)
        q = pq_setup[2]
        t = [0.0]
        with trace.tracing_scope(), serving.Server(ex, cfg) as srv:
            dead = Deadline(0.05, clock=lambda: t[0])
            doomed = srv.submit(q[:2], 5, deadline=dead)
            t[0] += 1.0                           # budget lapses queued
            srv.submit(q[:3], 5).result(timeout=10)
            with pytest.raises(DeadlineExceededError):
                doomed.result(timeout=10)
        evs = flight.events("serving.shed.deadline")
        assert [e["attrs"]["phase"] for e in evs] == ["dispatch"]
        # the shed request's trace lands in the ring too, marked shed
        shed = [r for r in flight.traces() if r.attrs.get("shed")]
        assert len(shed) == 1
        assert "serving.queue" in [s.name for s in shed[0].spans]

    def test_queue_full_shed_lands_flight_event(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        cfg = serving.ServerConfig(max_batch=16, max_queue_rows=4,
                                   max_wait_us=50_000)
        q = pq_setup[2]
        srv = serving.Server(ex, cfg).start()
        try:
            srv.batcher.stop(drain=False)
            fut = srv.submit(q[:3], 5)
            with pytest.raises(serving.Overloaded):
                srv.submit(q[:3], 5)
            srv.batcher.start()
            fut.result(timeout=30)
        finally:
            srv.stop()
        evs = flight.events("serving.shed.queue_full")
        assert len(evs) == 1
        assert evs[0]["attrs"]["rows"] == 3
        assert evs[0]["attrs"]["queued_rows"] == 3
        assert evs[0]["attrs"]["bound"] == 4

    def test_quota_shed_lands_flight_event(self, pq_setup):
        ex = _executor(pq_setup, warm="jit")
        cfg = serving.ServerConfig(
            max_batch=16, max_wait_us=100.0,
            tenant_quotas={"metered": (1.0, 4.0)})
        q = pq_setup[2]
        with serving.Server(ex, cfg) as srv:
            srv.search(q[:4], 5, tenant="metered")
            with pytest.raises(serving.QuotaExceeded):
                srv.submit(q[:4], 5, tenant="metered")
        evs = flight.events("serving.shed.quota")
        assert len(evs) == 1
        assert evs[0]["attrs"]["tenant"] == "metered"

    def test_swap_index_lands_generation_swap_event(self, pq_setup):
        res, db, _, index, _ = pq_setup
        ex = _executor(pq_setup, warm="jit")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=2_000)
        with serving.Server(ex, cfg) as srv:
            mutated = ivf_pq.delete(res, index, [0, 1, 2])
            srv.swap_index(mutated)
        evs = flight.events("serving.generation_swap")
        assert len(evs) == 1
        assert evs[0]["attrs"]["generation"] == \
            getattr(mutated, "generation", None)

    def test_zero_recompiles_with_tracing_enabled(self, pq_setup):
        """The PR 11 contract: tracing attaches to timestamps and lazy
        values the serving path already has — enabling it must not
        change bucket shapes or add compiles on warmed traffic."""
        ex = _executor(pq_setup, warm="aot")
        with obs.collecting():
            srv = serving.Server(
                ex, serving.ServerConfig(max_batch=16,
                                         max_wait_us=2_000)).start()
            q = np.asarray(pq_setup[2])
            try:
                for m in (1, 3, 8, 16, 5, 2):
                    srv.search(q[:m], 5)
                c0 = obs.registry().counter("xla.compiles").value
                with trace.tracing_scope():
                    for m in (2, 16, 1, 7, 4, 16, 3):
                        srv.search(q[:m], 5)
                c1 = obs.registry().counter("xla.compiles").value
            finally:
                srv.stop()
        assert c1 == c0, \
            f"{c1 - c0} recompiles on warmed traffic with tracing on"
        assert len(flight.traces()) == 7


# ---------------------------------------------------------------------------
# histogram metric (observability satellite)


class _EchoExecutor:
    """The executor surface the server and batcher read, with no index:
    each row's answer is its own first column."""

    max_batch, ks, dim, buckets = 8, (2,), 4, (1, 2, 4, 8)
    query_dtype, select_min, index = np.float32, True, None

    def warmup(self):
        return 0

    def search_bucket(self, queries, n_valid, k, rung=0):
        col = queries[:, :1]
        return jnp.tile(col, (1, k)), jnp.zeros((queries.shape[0], k),
                                                jnp.int32)


class TestDispatchSpans:
    PHASES = ["serving.wait", "serving.batch_cut", "serving.dispatch",
              "serving.readback", "serving.resolve"]

    def test_dispatcher_opens_one_annotation_per_phase(self, monkeypatch):
        """The dispatcher thread walks wait -> batch_cut -> dispatch ->
        readback -> resolve, each phase closed before the next opens; the
        submit path's boundary check runs inside integrity.sync on the
        caller's thread."""
        import contextlib

        from raft_tpu.core import tracing

        log = []

        @contextlib.contextmanager
        def recording(name, *a):
            log.append(("enter", name, threading.current_thread().name))
            try:
                yield
            finally:
                log.append(("exit", name, threading.current_thread().name))
        monkeypatch.setattr(tracing, "annotation", recording)
        cfg = serving.ServerConfig(max_batch=8, max_wait_us=1_000)
        with serving.Server(_EchoExecutor(), cfg) as srv:
            d, i = srv.search(np.full((3, 4), 7.0, np.float32), 2)
        np.testing.assert_array_equal(d, 7.0)
        batcher = [(what, name) for what, name, th in log
                   if th == "raft-tpu-serving-batcher"]
        # the first batch's phases, each a closed span, in order (the
        # loop then waits again until the server stops)
        assert batcher[:10] == [(w, n) for n in self.PHASES
                                for w in ("enter", "exit")]
        assert batcher[10:] == [("enter", "serving.wait"),
                                ("exit", "serving.wait")]
        caller = threading.current_thread().name
        assert ("enter", "integrity.sync", caller) in log

    def test_callbacks_run_inside_resolve(self, monkeypatch):
        """A request's done-callbacks run on the dispatcher thread inside
        serving.resolve."""
        import contextlib

        from raft_tpu.core import tracing

        current = []

        @contextlib.contextmanager
        def recording(name, *a):
            current.append(name)
            try:
                yield
            finally:
                current.pop()
        monkeypatch.setattr(tracing, "annotation", recording)
        seen_in, gate = [], threading.Event()
        cfg = serving.ServerConfig(max_batch=8, max_wait_us=1_000)
        with serving.Server(_EchoExecutor(), cfg) as srv:
            srv.batcher.stop(drain=False)        # hold the request queued
            fut = srv.submit(np.ones((2, 4), np.float32), 2)
            fut.add_done_callback(
                lambda f: (seen_in.append(list(current)), gate.set()))
            srv.batcher.start()
            assert gate.wait(timeout=10)
        assert seen_in == [["serving.resolve"]]


class TestHistogram:
    def test_observe_and_quantiles(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("lat")
        for v in (0.001, 0.002, 0.004, 0.008, 0.1):
            h.observe(v)
        d = h.as_dict()
        assert d["count"] == 5
        assert d["min"] == pytest.approx(0.001)
        assert d["max"] == pytest.approx(0.1)
        assert 0.0 < d["p50"] <= d["p95"] <= d["p99"] <= 0.1

    def test_custom_bounds_and_overflow(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("x", bounds=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0, 100.0):
            h.observe(v)
        d = h.as_dict()
        assert d["counts"] == [1, 1, 1, 1]     # last = overflow bucket
        assert d["p99"] <= d["max"] == 100.0

    def test_empty_histogram(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("empty")
        assert h.quantile(0.99) == 0.0
        assert h.as_dict()["min"] == 0.0

    def test_get_or_create_identity(self):
        reg = obs.MetricsRegistry()
        assert reg.histogram("h") is reg.histogram("h")

    def test_snapshot_and_prometheus_export(self):
        reg = obs.MetricsRegistry()
        reg.histogram("serving.latency.total").observe(0.01)
        snap = reg.snapshot()
        assert "serving.latency.total" in snap["histograms"]
        text = obs.to_prometheus(snap)
        assert "# TYPE raft_tpu_serving_latency_total histogram" in text
        assert 'raft_tpu_serving_latency_total_bucket{le="+Inf"} 1' in text
        assert "raft_tpu_serving_latency_total_p99" in text
        assert "raft_tpu_serving_latency_total_count 1" in text

    def test_json_roundtrip_with_histogram(self):
        import json
        reg = obs.MetricsRegistry()
        reg.histogram("h").observe(1.0)
        back = json.loads(obs.to_json(reg.snapshot()))
        assert back == reg.snapshot()

    def test_zero_work_while_disabled(self, pq_setup):
        """Counter contract: with collection off, serving records no
        histogram samples (and creates no histograms)."""
        ex = _executor(pq_setup, warm="jit")
        obs.disable()
        obs.reset()
        with serving.Server(ex,
                            serving.ServerConfig(max_batch=16)) as srv:
            srv.search(pq_setup[2][:2], 5)
        assert obs.snapshot()["histograms"] == {}
        assert obs.snapshot()["counters"] == {}


# ---------------------------------------------------------------------------
# concurrency smoke


class TestConcurrentClients:
    def test_many_threads_submit(self, pq_setup):
        res, _, q, index, sp = pq_setup
        ex = _executor(pq_setup, warm="aot")
        cfg = serving.ServerConfig(max_batch=16, max_wait_us=1_000,
                                   max_queue_rows=512)
        errs, results = [], []
        with serving.Server(ex, cfg) as srv:
            def client(j):
                try:
                    for _ in range(5):
                        d, i = srv.search(q[j:j + 2], 5, timeout=30)
                        results.append(np.asarray(i))
                except Exception as e:  # noqa: BLE001
                    errs.append(e)
            threads = [threading.Thread(target=client, args=(j,))
                       for j in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert not errs, errs
        assert len(results) == 40
        for i in results:
            assert (i >= 0).all()
