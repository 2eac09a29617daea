"""Live quality observability (PR 16): Wilson math, the windowed recall
estimator, the operating-point log (RTIE-sealed rotation, torn-tail
tolerance, calibrator-table shape), calibrated-vs-measured drift
detection with injected staleness, and the shadow-replay monitor
end-to-end — live recall estimate with a confidence interval, degraded
verdicts arming the generation watchdog, ground-truth derivation across
generation swaps, and the disabled-cost contract."""

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import DeviceResources, serving
from raft_tpu import observability as obs
from raft_tpu.core.serialize import CorruptIndexError
from raft_tpu.neighbors import ivf_flat, ivf_pq
from raft_tpu.observability import flight, quality
from raft_tpu.serving.shadow import ShadowSample


@pytest.fixture(autouse=True)
def _clean():
    obs.disable()
    obs.reset()
    flight.clear()
    yield
    obs.disable()
    obs.reset()
    flight.clear()


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches():
    yield
    jax.clear_caches()


@pytest.fixture
def clock(monkeypatch):
    t = {"now": 0.0}
    monkeypatch.setattr(quality, "_now", lambda: t["now"])
    return t


DIM = 32


@pytest.fixture(scope="module")
def res():
    return DeviceResources(seed=42)


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(7)
    db = rng.normal(size=(4000, DIM)).astype(np.float32)
    q = rng.normal(size=(256, DIM)).astype(np.float32)
    return jnp.asarray(db), q


@pytest.fixture(scope="module")
def pq_index(res, dataset):
    db, _ = dataset
    return ivf_pq.build(
        res, ivf_pq.IndexParams(n_lists=32, pq_dim=8, kmeans_n_iters=4),
        db)


# ---------------------------------------------------------------------------
# Wilson interval


class TestWilson:
    def test_known_value(self):
        # 50/100 at z=1.96: the textbook Wilson bound
        lo, hi = quality.wilson_interval(50, 100)
        assert lo == pytest.approx(0.4038, abs=1e-3)
        assert hi == pytest.approx(0.5962, abs=1e-3)

    def test_perfect_and_zero_proportions_stay_in_bounds(self):
        lo, hi = quality.wilson_interval(20, 20)
        assert 0.0 < lo < 1.0 and hi == 1.0
        lo, hi = quality.wilson_interval(0, 20)
        assert lo == 0.0 and 0.0 < hi < 1.0

    def test_empty_window_is_vacuous(self):
        assert quality.wilson_interval(0, 0) == (0.0, 1.0)

    def test_more_samples_narrow_the_interval(self):
        lo1, hi1 = quality.wilson_interval(9, 10)
        lo2, hi2 = quality.wilson_interval(900, 1000)
        assert (hi2 - lo2) < (hi1 - lo1)

    def test_interval_brackets_the_proportion(self):
        for hits, total in ((1, 7), (5, 9), (77, 80)):
            lo, hi = quality.wilson_interval(hits, total)
            assert lo <= hits / total <= hi


# ---------------------------------------------------------------------------
# the windowed estimator


class TestRecallEstimator:
    def test_pools_hits_not_averages(self, clock):
        est = quality.RecallEstimator(window_s=60.0)
        # a 1-row window at 0/5 and a 9-row window at 45/45: pooled
        # recall is 45/50, not the 0.5 a window-mean would report
        est.record("a", 10, 0, 5, rows=1)
        est.record("a", 10, 45, 45, rows=9)
        e = est.estimate()
        assert e.recall == pytest.approx(0.9)
        assert e.hits == 45 and e.total == 50 and e.rows == 10
        assert e.lo <= e.recall <= e.hi

    def test_keyed_and_filtered_views(self, clock):
        est = quality.RecallEstimator(window_s=60.0)
        est.record("a", 10, 9, 10)
        est.record("b", 10, 5, 10)
        est.record("a", 100, 80, 100)
        per = est.estimates()
        assert set(per) == {("a", 10), ("b", 10), ("a", 100)}
        assert per[("b", 10)].recall == pytest.approx(0.5)
        assert est.estimate(tenant="a").total == 110
        assert est.estimate(k=10).total == 20
        assert est.estimate(tenant="b", k=100) is None

    def test_samples_age_out(self, clock):
        est = quality.RecallEstimator(window_s=10.0)
        est.record("a", 10, 1, 10)
        clock["now"] = 8.0
        est.record("a", 10, 9, 10)
        assert est.estimate().total == 20
        clock["now"] = 12.0            # first sample beyond the horizon
        assert est.estimate().total == 10
        assert est.estimate().recall == pytest.approx(0.9)
        clock["now"] = 100.0
        assert est.estimate() is None

    def test_reset(self, clock):
        est = quality.RecallEstimator()
        est.record("a", 10, 1, 1)
        est.reset()
        assert est.estimate() is None


# ---------------------------------------------------------------------------
# the operating-point log


def _point(j, knobs=None, **measured):
    measured = {"recall": 0.9, "hits": 9 * (j + 1), "total": 10 * (j + 1),
                **measured}
    return quality.OpPoint(
        t=float(j), generation=j,
        knobs=knobs or {"kind": "ivf_pq", "n_probes": 8, "k": 10},
        measured=measured, tenant="t0")


class TestOperatingPointLog:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "op.jsonl")
        with quality.OperatingPointLog(path) as log:
            for j in range(5):
                log.append(_point(j, p99=0.001 * j))
        pts = quality.read_operating_points(path)
        assert len(pts) == 5
        assert [p.generation for p in pts] == list(range(5))
        assert pts[3].knobs == {"kind": "ivf_pq", "n_probes": 8, "k": 10}
        assert pts[3].measured["p99"] == pytest.approx(0.003)
        assert pts[3].tenant == "t0"

    def test_rotation_seals_segments_and_prunes(self, tmp_path):
        path = str(tmp_path / "op.jsonl")
        with quality.OperatingPointLog(path, max_bytes=256,
                                       keep=2) as log:
            for j in range(40):
                log.append(_point(j))
        segs = quality._segment_paths(path)
        assert len(segs) == 2          # pruned down to keep
        assert all(s.endswith(".rtie") for s in segs)
        pts = quality.read_operating_points(path)
        # oldest segments were pruned, so the tail of the sequence
        # survives contiguously and in order
        gens = [p.generation for p in pts]
        assert gens == sorted(gens)
        assert gens[-1] == 39
        assert 0 < len(gens) < 40

    def test_torn_final_line_tolerated(self, tmp_path):
        path = str(tmp_path / "op.jsonl")
        with quality.OperatingPointLog(path) as log:
            log.append(_point(0))
            log.append(_point(1))
        with open(path, "a") as f:
            f.write('{"t": 2.0, "generation": 2, "kno')   # crash mid-line
        pts = quality.read_operating_points(path)
        assert [p.generation for p in pts] == [0, 1]

    def test_garbage_mid_file_raises(self, tmp_path):
        path = str(tmp_path / "op.jsonl")
        with quality.OperatingPointLog(path) as log:
            log.append(_point(0))
        with open(path, "a") as f:
            f.write("not json at all\n")
            f.write(json.dumps(_point(1).as_dict()) + "\n")
        with pytest.raises(CorruptIndexError, match="line 2"):
            quality.read_operating_points(path)

    def test_corrupt_sealed_segment_rejected(self, tmp_path):
        path = str(tmp_path / "op.jsonl")
        with quality.OperatingPointLog(path, max_bytes=128,
                                       keep=8) as log:
            for j in range(10):
                log.append(_point(j))
        seg = quality._segment_paths(path)[0]
        raw = bytearray(open(seg, "rb").read())
        raw[-3] ^= 0xFF
        open(seg, "wb").write(bytes(raw))
        with pytest.raises(CorruptIndexError):
            quality.read_operating_points(path)

    def test_calibrator_table_pools_by_knobs(self):
        pts = [_point(0, p99=0.002), _point(1, p99=0.004),
               _point(2, knobs={"kind": "ivf_pq", "n_probes": 16,
                                "k": 10})]
        table = quality.calibrator_table(pts)
        assert len(table) == 2
        key8 = tuple(sorted({"kind": "ivf_pq", "n_probes": 8,
                             "k": 10}.items()))
        row = table[key8]
        # hits/total re-pooled across windows, not averaged
        assert row["hits"] == 9 + 18 and row["total"] == 10 + 20
        assert row["recall"] == pytest.approx(27 / 30)
        assert row["recall_lo"] <= row["recall"] <= row["recall_hi"]
        assert row["p99"] == pytest.approx(0.003)
        assert len(row["points"]) == 2


# ---------------------------------------------------------------------------
# drift detection


class _FakeIndex:
    def __init__(self, group_est=0.0):
        self.group_est = group_est


class _FakeMemtable:
    def __init__(self, live, dead):
        self.live_rows = live
        self.n_tombstones = dead


class TestDriftDetector:
    def test_group_est_staleness_flagged(self):
        det = quality.DriftDetector()
        stats = {"touched_fraction": 0.5, "touched_lists": 16.0,
                 "n_probes": 4.0, "n_lists": 32.0}
        # calibrated at 0.1, measured 0.5 > 0.1 * 1.25 -> stale
        fs = det.check(index=_FakeIndex(group_est=0.1), probe_stats=stats)
        assert [f.kind for f in fs] == ["group_est"]
        assert fs[0].measured == pytest.approx(0.5)
        evs = flight.events("serving.quality.drift")
        assert len(evs) == 1 and evs[0]["attrs"]["kind"] == "group_est"

    def test_group_est_within_margin_quiet(self):
        det = quality.DriftDetector()
        stats = {"touched_fraction": 0.5, "touched_lists": 16.0,
                 "n_probes": 4.0, "n_lists": 32.0}
        assert det.check(index=_FakeIndex(group_est=0.45),
                         probe_stats=stats) == []
        # uncalibrated (group_est == 0) must never invent drift
        assert det.check(index=_FakeIndex(), probe_stats=stats) == []

    def test_scan_skew_flagged(self):
        det = quality.DriftDetector()
        stats = {"touched_fraction": 0.2, "touched_lists": 8.0,
                 "n_probes": 4.0, "n_lists": 32.0,
                 "live_rows": 3200.0, "probed_rows_per_query": 900.0}
        # uniform model: 3200 * 4 / 32 = 400; measured 900 > 2x
        fs = det.check(index=_FakeIndex(), probe_stats=stats)
        assert [f.kind for f in fs] == ["scan_skew"]
        assert fs[0].calibrated == pytest.approx(400.0)

    def test_fused_fallback_window_with_reasons(self):
        det = quality.DriftDetector()
        with obs.collecting():
            obs.registry().counter("ivf_pq.search.fused_fallback").inc(3)
            obs.registry().counter(
                "ivf_pq.search.fused_fallback.reason.kt_zero").inc(3)
            fs = det.check()
            assert [f.kind for f in fs] == ["fused_fallback"]
            assert fs[0].measured == 3.0
            assert fs[0].detail["reasons"] == {"kt_zero": 3}
            snap = obs.snapshot()["counters"]
            assert snap["serving.quality.drift"] == 1
            assert snap["serving.quality.drift.fused_fallback"] == 1

    def test_memtable_dead_fraction_flagged(self):
        det = quality.DriftDetector()
        # delete-heavy churn: 8 tombstones over 4 live rows (67% dead)
        fs = det.check(memtable=_FakeMemtable(live=4, dead=8))
        assert [f.kind for f in fs] == ["memtable_dead"]
        assert fs[0].measured == pytest.approx(8 / 12)
        assert det.check(memtable=_FakeMemtable(live=10, dead=1)) == []
        assert det.check(memtable=_FakeMemtable(live=0, dead=0)) == []

    def test_no_signals_no_findings(self):
        assert quality.DriftDetector().check() == []

    def test_measure_probe_stats_on_real_index(self, pq_index, dataset):
        _, q = dataset
        stats = quality.measure_probe_stats(pq_index, q[:16], n_probes=4)
        assert 0.0 < stats["touched_fraction"] <= 1.0
        assert stats["n_lists"] == 32.0 and stats["n_probes"] == 4.0
        assert stats["probed_rows_per_query"] > 0
        assert stats["live_rows"] == 4000.0
        # no coarse structure -> no measurement, never an exception
        assert quality.measure_probe_stats(object(), q[:4], 4) is None

    def test_injected_staleness_on_real_index(self, pq_index, dataset):
        _, q = dataset
        det = quality.DriftDetector()
        stale = dataclasses.replace(pq_index)
        # inject: calibration claims almost no lists are touched
        stale.group_est = 0.01
        fs = det.check(index=stale, queries=q[:16], n_probes=8)
        assert "group_est" in [f.kind for f in fs]


# ---------------------------------------------------------------------------
# ground-truth derivation + operating knobs


class TestGroundTruthParams:
    def test_ivf_pq_full_probe(self, pq_index):
        sp = serving.ground_truth_search_params(
            "ivf_pq", pq_index,
            ivf_pq.SearchParams(n_probes=4, per_probe_topk=4,
                                scan_mode="fused"))
        assert sp.n_probes == pq_index.n_lists
        assert sp.exact_coarse is True
        assert sp.per_probe_topk == 0
        assert sp.scan_mode in ("lut", "recon")

    def test_ivf_flat_full_probe(self, res, dataset):
        db, _ = dataset
        idx = ivf_flat.build(
            res, ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=2), db)
        sp = serving.ground_truth_search_params("ivf_flat", idx)
        assert sp.n_probes == 16

    def test_brute_force_already_exact(self):
        assert serving.ground_truth_search_params("brute_force",
                                                  object()) is None

    def test_underivable_kind_refused(self):
        with pytest.raises(ValueError, match="ground_truth_params"):
            serving.ground_truth_search_params("cagra", object())


class TestOperatingKnobs:
    def test_executor_reports_closed_shape_coordinates(self, res, pq_index):
        ex = serving.Executor(
            res, "ivf_pq", pq_index, ks=(5,), max_batch=16,
            search_params=ivf_pq.SearchParams(n_probes=8,
                                              scan_mode="fused",
                                              per_probe_topk=4),
            warm="jit")
        knobs = ex.operating_knobs(0)
        assert knobs["kind"] == "ivf_pq"
        assert knobs["bucket"] == 16
        assert knobs["rung"] == 0
        assert knobs["n_probes"] == 8
        assert knobs["scan_mode"] == "fused"
        assert knobs["kt"] == 4
        assert json.dumps(knobs)       # op-log serializable as-is


# ---------------------------------------------------------------------------
# the shadow monitor end-to-end


def _shadow_server(res, pq_index, config, n_probes=8):
    sp = ivf_pq.SearchParams(n_probes=n_probes)
    ex = serving.Executor(res, "ivf_pq", pq_index, ks=(5,), max_batch=16,
                          search_params=sp, warm="jit")
    srv = serving.Server(ex, serving.ServerConfig(max_batch=16,
                                                  max_wait_us=500))
    monitor = serving.ShadowMonitor(config)
    srv.attach_shadow(monitor)
    return srv, monitor


def _drain(monitor, timeout=15.0):
    deadline = time.monotonic() + timeout
    while monitor.stats()["backlog"] and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)                    # let an in-flight replay land


class TestShadowMonitor:
    # The three full-loop tests (server + replay thread + ground-truth
    # executor warm) dominate this module's runtime; they run in the CI
    # quality job (which runs this file unfiltered) and stay out of the
    # fast tier.
    @pytest.mark.slow
    def test_live_estimate_with_interval_and_oplog(self, res, pq_index,
                                                   dataset, tmp_path):
        _, q = dataset
        cfg = serving.ShadowConfig(sample_rows_per_s=1e6, burst_rows=1e6,
                                   window_s=3600.0,
                                   op_log_path=str(tmp_path / "op.jsonl"))
        srv, monitor = _shadow_server(res, pq_index, cfg)
        with obs.collecting():
            srv.start()
            try:
                for j in range(6):
                    srv.search(q[j * 8:(j + 1) * 8], 5)
                _drain(monitor)
                records = monitor.flush()
            finally:
                srv.stop()
            snap = obs.snapshot()
        assert snap["counters"]["serving.shadow.replayed"] >= 8
        est = monitor.estimator.estimate()
        assert est is not None
        assert 0.0 <= est.lo <= est.recall <= est.hi <= 1.0
        assert est.rows >= 8
        assert records and records[0]["k"] == 5
        assert snap["gauges"]["serving.quality.recall"] == pytest.approx(
            est.recall)
        # op-point log round-trips into the calibrator shape
        pts = quality.read_operating_points(str(tmp_path / "op.jsonl"))
        assert pts
        assert pts[0].knobs["kind"] == "ivf_pq"
        assert pts[0].knobs["k"] == 5
        assert pts[0].measured["total"] >= 1
        assert quality.calibrator_table(pts)

    @pytest.mark.slow
    def test_degraded_window_arms_watchdog(self, res, pq_index, dataset):
        _, q = dataset
        # injected recall regression: serve at n_probes=1 against the
        # full-probe ground truth, with a floor the estimate can't meet
        cfg = serving.ShadowConfig(sample_rows_per_s=1e6, burst_rows=1e6,
                                   window_s=3600.0, recall_floor=0.99,
                                   arm_watchdog=True)
        srv, monitor = _shadow_server(res, pq_index, cfg, n_probes=1)
        strikes = []
        srv.note_integrity_strike = lambda reason: (strikes.append(reason)
                                                    or True)
        with obs.collecting():
            srv.start()
            try:
                for j in range(6):
                    srv.search(q[j * 8:(j + 1) * 8], 5)
                _drain(monitor)
                records = monitor.flush()
            finally:
                srv.stop()
            snap = obs.snapshot()
        assert any(r["degraded"] for r in records)
        evs = flight.events("serving.quality.degraded")
        assert evs and evs[0]["attrs"]["floor"] == pytest.approx(0.99)
        assert evs[0]["attrs"]["lo"] < 0.99
        assert strikes and "floor" in strikes[0]
        assert snap["counters"]["serving.quality.degraded"] >= 1

    @pytest.mark.slow
    def test_swap_rederives_ground_truth_point(self, res, dataset):
        db, _ = dataset
        a = ivf_pq.build(res, ivf_pq.IndexParams(n_lists=32, pq_dim=8,
                                                 kmeans_n_iters=2), db)
        b = ivf_pq.build(res, ivf_pq.IndexParams(n_lists=16, pq_dim=8,
                                                 kmeans_n_iters=2), db)
        cfg = serving.ShadowConfig(window_s=3600.0)
        srv, monitor = _shadow_server(res, a, cfg)
        srv.start()
        try:
            assert monitor.executor.params.n_probes == 32
            srv.swap_index(b)
            assert monitor.executor.index is b
            assert monitor.executor.params.n_probes == 16
            assert monitor.executor.params.exact_coarse is True
        finally:
            srv.stop()

    def test_stale_generation_sample_dropped(self, res, pq_index, dataset):
        _, q = dataset
        cfg = serving.ShadowConfig(window_s=3600.0)
        srv, monitor = _shadow_server(res, pq_index, cfg)
        with obs.collecting():
            srv.start()
            try:
                stale = ShadowSample(
                    queries=q[:4].copy(),
                    served_ids=np.zeros((4, 5), np.int64), k=5,
                    tenant="default", rung=0, index=object(), t=0.0)
                monitor._replay(stale)
            finally:
                srv.stop()
            snap = obs.snapshot()
        assert snap["counters"]["serving.shadow.dropped.generation"] == 1
        assert monitor.estimator.estimate() is None

    def test_budget_zero_skips_sampling(self, res, pq_index, dataset):
        _, q = dataset
        cfg = serving.ShadowConfig(sample_rows_per_s=1e-9, burst_rows=0.0,
                                   window_s=3600.0)
        srv, monitor = _shadow_server(res, pq_index, cfg)
        with obs.collecting():
            srv.start()
            try:
                for j in range(3):
                    srv.search(q[j * 8:(j + 1) * 8], 5)
                _drain(monitor)
            finally:
                srv.stop()
            snap = obs.snapshot()
        assert snap["counters"].get("serving.shadow.sampled", 0) == 0
        assert snap["counters"]["serving.shadow.skipped.budget"] >= 24

    def test_disabled_offer_is_one_flag_check(self, res, pq_index):
        cfg = serving.ShadowConfig(window_s=3600.0)
        srv, monitor = _shadow_server(res, pq_index, cfg)
        monitor.disable()

        class _Forbidden:
            def __getattr__(self, name):
                raise AssertionError(
                    f"disabled offer() touched {name!r}")

        # with sampling disabled, offer() may read nothing but the flag
        monitor._budget = _Forbidden()
        monitor._tenant_budgets = _Forbidden()
        monitor._cond = _Forbidden()
        monitor._samples = _Forbidden()
        monitor.offer([(object(), None, None)], 5, pq_index)
        monitor.enable()
        monitor._budget = serving.TokenBucket(1.0, 1.0)
        monitor._tenant_budgets = {}

    def test_attach_after_start_refused(self, res, pq_index):
        sp = ivf_pq.SearchParams(n_probes=8)
        ex = serving.Executor(res, "ivf_pq", pq_index, ks=(5,),
                              max_batch=16, search_params=sp, warm="jit")
        srv = serving.Server(ex, serving.ServerConfig(max_batch=16,
                                                      max_wait_us=500))
        srv.start()
        try:
            with pytest.raises(Exception, match="start"):
                srv.attach_shadow(serving.ShadowMonitor())
        finally:
            srv.stop()
