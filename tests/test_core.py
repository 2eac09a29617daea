"""Core layer tests (reference test analogue: cpp/test/core/)."""

import io
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import DeviceResources, Resources
from raft_tpu.core import (
    LogicError,
    check_matrix,
    check_vector,
    deserialize_mdspan,
    deserialize_scalar,
    expects,
    interruptible,
    InterruptedException,
    make_device_matrix,
    resource_type,
    serialize_mdspan,
    serialize_scalar,
)
from raft_tpu.core import logger as rlog


class TestResources:
    def test_lazy_factory(self):
        r = Resources()
        calls = []
        r.add_resource_factory("thing", lambda: calls.append(1) or "made")
        assert not calls
        assert r.get_resource("thing") == "made"
        assert r.get_resource("thing") == "made"
        assert len(calls) == 1

    def test_missing_resource_raises(self):
        with pytest.raises(LogicError):
            Resources().get_resource("nope")

    def test_copy_shares_factories_not_instances(self):
        r = Resources()
        r.add_resource_factory("x", lambda: object())
        a = r.get_resource("x")
        r2 = Resources(r)
        assert r2.get_resource("x") is not a

    def test_device_resources_defaults(self):
        res = DeviceResources(seed=7)
        assert res.device in jax.devices()
        assert res.mesh.axis_names == ("data",)
        assert res.workspace_bytes > 0

    def test_prng_chain_deterministic(self):
        a = DeviceResources(seed=3)
        b = DeviceResources(seed=3)
        k1, k2 = a.next_key(), a.next_key()
        assert not jnp.array_equal(jax.random.key_data(k1),
                                   jax.random.key_data(k2))
        assert jnp.array_equal(jax.random.key_data(b.next_key()),
                               jax.random.key_data(k1))

    def test_comms_slot(self):
        res = DeviceResources()
        assert not res.comms_initialized()
        res.set_comms("comm")
        assert res.get_comms() == "comm"


class TestContracts:
    def test_check_matrix(self):
        x = jnp.zeros((3, 4))
        assert check_matrix(x, rows=3, cols=4) is x
        with pytest.raises(LogicError):
            check_matrix(jnp.zeros(3))
        with pytest.raises(LogicError):
            check_matrix(x, dtype=jnp.int32)

    def test_check_vector_ingests_numpy(self):
        v = check_vector(np.arange(5.0), size=5)
        assert isinstance(v, jax.Array)

    def test_make_device_matrix(self):
        res = DeviceResources()
        m = make_device_matrix(res, 2, 3)
        assert m.shape == (2, 3)


class TestSerialize:
    def test_mdspan_roundtrip(self):
        buf = io.BytesIO()
        arr = np.random.default_rng(0).normal(size=(5, 7)).astype(np.float32)
        serialize_mdspan(None, buf, jnp.asarray(arr))
        buf.seek(0)
        out = deserialize_mdspan(None, buf)
        np.testing.assert_array_equal(out, arr)

    def test_scalar_roundtrip(self):
        buf = io.BytesIO()
        serialize_scalar(None, buf, np.int64(42))
        serialize_scalar(None, buf, np.float32(1.5))
        buf.seek(0)
        assert deserialize_scalar(None, buf) == 42
        assert deserialize_scalar(None, buf) == np.float32(1.5)


class TestLogger:
    def test_callback_sink(self):
        records = []
        lg = rlog.Logger.get()
        lg.set_callback(lambda lvl, msg: records.append((lvl, msg)))
        try:
            rlog.log_info("hello %d", 5)
        finally:
            lg.set_callback(None)
        assert any("hello 5" in m for _, m in records)

    def test_level_filtering(self):
        lg = rlog.Logger.get()
        old = lg.get_level()
        lg.set_level(rlog.ERROR)
        try:
            assert not lg.should_log_for(rlog.INFO)
            assert lg.should_log_for(rlog.ERROR)
        finally:
            lg.set_level(old)


class TestInterruptible:
    def test_cancel_from_other_thread(self):
        tok = interruptible.get_token()
        t = threading.Thread(target=tok.cancel)
        t.start()
        t.join()
        with pytest.raises(InterruptedException):
            interruptible.synchronize()
        # token cleared after raise
        interruptible.synchronize()


class TestAot:
    """AOT export (core/aot.py) — the instantiation-layer analogue
    (reference: cpp/src precompiled template units; SURVEY §1)."""

    def test_export_roundtrip(self):
        from raft_tpu.core import aot

        def fn(a, b):
            return a @ b + 1.0

        x = jnp.ones((8, 16), jnp.float32)
        y = jnp.ones((16, 4), jnp.float32)
        blob = aot.export_fn(fn, (x, y))
        assert isinstance(blob, bytes) and len(blob) > 0
        g = aot.load_fn(blob)
        np.testing.assert_allclose(np.asarray(g(x, y)),
                                   np.asarray(fn(x, y)), rtol=1e-6)

    def test_ivf_pq_search_artifact(self, res):
        """Flagship deployment artifact: export at fixed shapes, reload
        in a fresh callable, identical results to the live search."""
        from raft_tpu.core import aot
        from raft_tpu.neighbors import ivf_pq

        rng = np.random.default_rng(0)
        db = jnp.asarray(rng.normal(size=(2048, 32)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
        index = ivf_pq.build(
            res, ivf_pq.IndexParams(n_lists=16, pq_dim=8,
                                    kmeans_n_iters=4), db)
        buf = aot.export_ivf_pq_search(res, index, n_probes=8, k=5,
                                       batch=16)
        g = aot.load_search_fn(buf)
        d1, i1 = g(q)
        d2, i2 = ivf_pq._search_impl_recon(
            index.centers, index.list_recon, index.list_indices,
            index.rotation, q, k=5, n_probes=8, metric=index.metric)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-5)

    def test_ivf_pq_codes_artifact(self, res):
        """Compact-code deployment artifact: scan_mode="codes" bakes
        only the packed PQ codes (+codebooks) and round-trips against
        the live code-domain search."""
        from raft_tpu.core import aot
        from raft_tpu.neighbors import ivf_pq

        rng = np.random.default_rng(2)
        db = jnp.asarray(rng.normal(size=(2048, 32)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
        index = ivf_pq.build(
            res, ivf_pq.IndexParams(n_lists=16, pq_dim=8,
                                    kmeans_n_iters=4), db)
        buf = aot.export_ivf_pq_search(res, index, n_probes=8, k=5,
                                       batch=16, scan_mode="codes")
        g = aot.load_search_fn(buf)
        d1, i1 = g(q)
        d2, i2 = ivf_pq._search_impl(
            index.centers, index.codebooks, index.list_codes,
            index.list_indices, index.rotation, q, k=5, n_probes=8,
            metric=index.metric, codebook_kind=index.codebook_kind,
            lut_dtype=jnp.float32, pq_bits=index.pq_bits)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-5)
        # the codes artifact must be materially smaller than the recon
        # one: it carries 1 byte/subspace/row instead of 2 bytes/dim/row
        recon_buf = aot.export_ivf_pq_search(res, index, n_probes=8,
                                             k=5, batch=16)
        assert len(buf.getvalue()) < len(recon_buf.getvalue())

    def test_ivf_flat_search_artifact(self, res):
        from raft_tpu.core import aot
        from raft_tpu.neighbors import ivf_flat

        rng = np.random.default_rng(3)
        db = jnp.asarray(rng.normal(size=(2048, 32)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
        index = ivf_flat.build(
            res, ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4), db)
        buf = aot.export_ivf_flat_search(res, index, n_probes=8, k=5,
                                         batch=16)
        g = aot.load_search_fn(buf)
        d1, i1 = g(q)
        d2, i2 = ivf_flat._search_impl(
            index.centers, index.list_data, index.list_indices, q, k=5,
            n_probes=8, metric=index.metric)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-5)

    def test_brute_force_knn_artifact(self, res):
        from raft_tpu.core import aot
        from raft_tpu.neighbors import brute_force

        rng = np.random.default_rng(4)
        db = jnp.asarray(rng.normal(size=(1024, 32)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
        buf = aot.export_brute_force_knn(res, db, k=7, batch=16)
        g = aot.load_search_fn(buf)
        d1, i1 = g(q)
        d2, i2 = brute_force.knn(res, db, q, 7)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-5)

    def test_cagra_search_artifact(self, res):
        """CAGRA walk deployment artifact: the walk table + entry set +
        exported walk program reload into a callable that matches the
        live packed-walk search exactly."""
        from raft_tpu.core import aot
        from raft_tpu.neighbors import cagra

        rng = np.random.default_rng(1)
        lat = rng.normal(size=(2048 + 16, 8)).astype(np.float32)
        A = rng.normal(size=(8, 32)).astype(np.float32)
        X = jnp.asarray(lat @ A)
        db, q = X[:2048], X[2048:]
        index = cagra.build(
            res, cagra.IndexParams(intermediate_graph_degree=32,
                                   graph_degree=16), db)
        buf = aot.export_cagra_search(res, index, k=5, batch=16,
                                      itopk=32)
        g = aot.load_search_fn(buf)
        d1, i1 = g(q)
        assert np.asarray(i1).shape == (16, 5)
        # live search at the same operating point agrees
        d2, i2 = cagra.search(
            res, cagra.SearchParams(itopk_size=32, search_width=1),
            index, q, 5)
        same = np.mean(np.asarray(i1) == np.asarray(i2))
        assert same == 1.0, same


class TestPlatform:
    @pytest.mark.parametrize("env_dir", [None, "custom"])
    def test_compile_cache_dir(self, monkeypatch, tmp_path, env_dir):
        """$JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache
        sits at the fixed <repo>/.jax_cache."""
        import pathlib

        from raft_tpu.core import platform

        repo = pathlib.Path(__file__).resolve().parents[1]
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(repo / ".jax_cache")
        else:
            want = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        prev = jax.config.jax_compilation_cache_dir
        try:
            assert platform.setup_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)

    def test_on_tpu_follows_default_backend(self):
        from raft_tpu.core.platform import on_tpu
        assert on_tpu() == (jax.default_backend() == "tpu")
