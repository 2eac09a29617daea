"""raft_tpu.observability — registry, stages, exporters, build reports.

Marker-free (tier-1): everything here runs on tiny inputs.  The key
contract under test: collection is OFF by default and the instrumented
hot paths add NO fences (``block_until_ready``) while it is off.
"""

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import observability as obs

# the package re-exports a `stage` FUNCTION that shadows the submodule
# attribute — import the module itself for monkeypatching
stage_mod = importlib.import_module("raft_tpu.observability.stage")


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


class TestRegistry:
    def test_counter_gauge_timer(self):
        reg = obs.MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(4)
        reg.gauge("g").set(2.5)
        reg.timer("t").record(0.5)
        reg.timer("t").record(1.5)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 5
        assert snap["gauges"]["g"] == 2.5
        t = snap["timers"]["t"]
        assert t["count"] == 2
        assert t["total_s"] == pytest.approx(2.0)
        assert t["min_s"] == pytest.approx(0.5)
        assert t["max_s"] == pytest.approx(1.5)
        assert t["last_s"] == pytest.approx(1.5)

    def test_reset(self):
        reg = obs.MetricsRegistry()
        reg.counter("c").inc()
        reg.reset()
        snap = reg.snapshot()
        assert snap["counters"] == {} and snap["gauges"] == {}
        assert snap["timers"] == {} and snap["histograms"] == {}
        assert snap["window"]["counters"] == {}
        assert snap["window"]["histograms"] == {}

    def test_get_or_create_identity(self):
        reg = obs.MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        assert reg.timer("y") is reg.timer("y")


class TestExport:
    def _populated(self):
        reg = obs.MetricsRegistry()
        reg.counter("comms.allreduce.calls").inc(3)
        reg.gauge("cap").set(7.0)
        reg.timer("cagra.build.scan").record(0.25)
        return reg

    def test_json_roundtrip(self):
        snap = self._populated().snapshot()
        back = json.loads(obs.to_json(snap))
        assert back == snap

    def test_prometheus_text(self):
        # registry -> JSON -> Prometheus round-trip: the Prometheus
        # text must be derivable from the JSON-serialized snapshot
        snap = json.loads(obs.to_json(self._populated().snapshot()))
        text = obs.to_prometheus(snap)
        assert "raft_tpu_comms_allreduce_calls_total 3" in text
        assert "raft_tpu_cap 7.0" in text
        assert "raft_tpu_cagra_build_scan_seconds_count 1" in text
        assert "raft_tpu_cagra_build_scan_seconds_total 0.25" in text
        # names sanitized: no dots survive
        for line in text.splitlines():
            if not line.startswith("#"):
                assert "." not in line.split(" ")[0]

    def test_prometheus_global_default(self):
        with obs.collecting():
            obs.registry().counter("k").inc()
        assert "raft_tpu_k_total 1" in obs.to_prometheus()


def _record_annotations(monkeypatch):
    """Route the host-annotation primitive through a recorder; returns the
    list of labels opened."""
    from raft_tpu.core import tracing
    opened, real = [], tracing.annotation

    def recording(name, *a):
        opened.append(name)
        return real(name, *a)
    monkeypatch.setattr(tracing, "annotation", recording)
    return opened


class TestStage:
    def test_disabled_is_noop(self, monkeypatch):
        """Collection off: the stage opens its host annotation and nothing
        else — no named scope, no clock, no fence, no registry write."""
        from types import SimpleNamespace

        from raft_tpu.core import tracing
        from raft_tpu.observability import trace

        def forbidden(*a, **kw):
            raise AssertionError("disabled stage did timing work")
        opened = _record_annotations(monkeypatch)
        monkeypatch.setattr(tracing, "range", forbidden)
        monkeypatch.setattr(stage_mod, "_block_until_ready", forbidden)
        monkeypatch.setattr(stage_mod, "time",
                            SimpleNamespace(perf_counter=forbidden))
        monkeypatch.setattr(trace, "stage_hook", forbidden)
        assert not obs.enabled()
        with obs.stage("nothing") as st:
            st.fence(jnp.zeros(3))
        assert opened == ["nothing"]
        assert obs.snapshot()["timers"] == {}

    def test_enabled_opens_the_same_annotation(self, monkeypatch):
        opened = _record_annotations(monkeypatch)
        with obs.collecting():
            with obs.stage("work") as st:
                st.fence(jnp.arange(4))
        assert opened == ["work"]
        assert obs.snapshot()["timers"]["work"]["count"] == 1

    def test_disabled_shares_singleton(self):
        with obs.stage("a") as h1:
            pass
        with obs.stage("b") as h2:
            pass
        assert h1 is h2                      # shared no-op handle

    def test_enabled_records(self):
        with obs.collecting():
            with obs.stage("work") as st:
                x = jnp.arange(8) * 2
                st.fence(x)
        t = obs.snapshot()["timers"]["work"]
        assert t["count"] == 1
        assert t["total_s"] > 0

    def test_fence_skips_tracers(self):
        @jax.jit
        def f(x):
            obs.fence(x)                     # tracer: must not block
            return x + 1
        with obs.collecting():
            np.testing.assert_array_equal(np.asarray(f(jnp.ones(2))),
                                          [2.0, 2.0])

    def test_collecting_restores_state(self):
        assert not obs.enabled()
        with obs.collecting():
            assert obs.enabled()
        assert not obs.enabled()


class TestNoFencesWhenDisabled:
    """Acceptance criterion: with collection disabled (the default), an
    instrumented CAGRA search performs NO block_until_ready fences."""

    def _index(self, res):
        from raft_tpu.neighbors import cagra
        rng = np.random.default_rng(0)
        db = jnp.asarray(rng.normal(size=(256, 16)).astype(np.float32))
        return cagra, cagra.build(
            res, cagra.IndexParams(graph_degree=8,
                                   intermediate_graph_degree=16), db)

    def test_search_fence_free_when_disabled(self, res, monkeypatch):
        cagra, index = self._index(res)
        q = jnp.asarray(np.random.default_rng(1).normal(
            size=(4, 16)).astype(np.float32))
        sp = cagra.SearchParams(itopk_size=16)
        cagra.search(res, sp, index, q, 4)   # warm (walk-cache attach)
        calls = []
        monkeypatch.setattr(stage_mod, "_block_until_ready",
                            lambda x: calls.append(x) or x)
        assert not obs.enabled()
        cagra.search(res, sp, index, q, 4)
        assert calls == []
        with obs.collecting():
            cagra.search(res, sp, index, q, 4)
        assert len(calls) > 0

    def test_build_report_attached(self, res):
        with obs.collecting():
            cagra, index = self._index(res)
        rep = obs.build_report(index)
        assert rep is not None
        assert rep["name"] == "cagra.build"
        assert rep["total_s"] > 0
        assert "cagra.build.prune" in rep["stages"]
        assert "cagra.build.knn_exact" in rep["stages"]  # n=256 exact path
        assert rep["stages"]["cagra.build.prune"]["count"] == 1

    def test_build_report_absent_when_disabled(self, res):
        _, index = self._index(res)
        assert obs.build_report(index) is None


class TestCompileEvents:
    def test_compile_counter(self):
        # the persistent compile cache can serve the executable without
        # a backend_compile event — force real compiles for this test
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            with obs.collecting():
                @jax.jit
                def f(x):
                    return (x * 3 + 1).sum()
                f(jnp.arange(13.0)).block_until_ready()
            snap = obs.snapshot()
            assert snap["counters"].get("xla.compiles", 0) >= 1
            assert any(n.startswith("xla.") for n in snap["timers"])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


class TestInstrumentedModules:
    def test_kmeans_stage_and_counters(self, res):
        from raft_tpu.cluster import kmeans
        from raft_tpu.cluster.kmeans_types import InitMethod, KMeansParams
        X = jnp.asarray(np.random.default_rng(2).normal(
            size=(512, 8)).astype(np.float32))
        p = KMeansParams(n_clusters=8, max_iter=5, n_init=1,
                         init=InitMethod.Random, tol=0.0)
        with obs.collecting():
            kmeans.fit(res, p, X)
        snap = obs.snapshot()
        assert snap["timers"]["kmeans.fit"]["count"] == 1
        assert snap["counters"]["kmeans.iterations"] >= 1

    def test_comms_record_helper(self):
        comms_mod = importlib.import_module("raft_tpu.comms.comms")
        comms_mod._record_collective("allreduce", jnp.ones(4, jnp.float32))
        assert obs.snapshot()["counters"] == {}      # disabled: no-op
        with obs.collecting():
            comms_mod._record_collective("allreduce",
                                         jnp.ones(4, jnp.float32))
        snap = obs.snapshot()
        assert snap["counters"]["comms.allreduce.calls"] == 1
        assert snap["counters"]["comms.allreduce.bytes"] == 16

    def test_ivf_stages(self, res):
        from raft_tpu.neighbors import ivf_flat
        rng = np.random.default_rng(3)
        db = jnp.asarray(rng.normal(size=(512, 16)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
        with obs.collecting():
            index = ivf_flat.build(
                res, ivf_flat.IndexParams(n_lists=8), db)
            ivf_flat.search(res, ivf_flat.SearchParams(n_probes=4),
                            index, q, 4)
        snap = obs.snapshot()
        assert snap["timers"]["ivf_flat.build.kmeans"]["count"] == 1
        assert snap["timers"]["ivf_flat.search.coarse"]["count"] == 1
        assert snap["timers"]["ivf_flat.search.scan"]["count"] == 1
        rep = obs.build_report(index)
        assert rep is not None and rep["name"] == "ivf_flat.build"
