"""Per-request tracing, the flight recorder, and windowed telemetry.

Pins the PR 11 observability contracts: retroactive span recording from
timestamps the serving path already takes, the per-thread ambient
recorder stack, ``stage()`` timers mirroring onto the ambient trace,
the lock-free flight ring (always-on anomaly events, Chrome-trace
dumps, env-gated auto-dump), rotating-window counter/histogram views,
and the disabled-path cost contract (no lock, no fence, no allocation
when collection is off).
"""

import importlib
import json
import os
import threading

import numpy as np
import pytest

from raft_tpu import observability as obs
from raft_tpu.observability import flight, trace

# the package __init__ rebinds the ``registry`` attribute to the accessor
# function, so the module itself must come through importlib
registry_mod = importlib.import_module("raft_tpu.observability.registry")
stage_mod = importlib.import_module("raft_tpu.observability.stage")


@pytest.fixture(autouse=True)
def _clean():
    obs.disable()
    obs.reset()
    trace.disable_tracing()
    flight.clear()
    yield
    obs.disable()
    obs.reset()
    trace.disable_tracing()
    flight.clear()


# ---------------------------------------------------------------------------
# span / recorder model


class TestTraceModel:
    def test_retroactive_spans_from_timestamps(self):
        rec = trace.SpanRecorder("serving.request", t0=1.0)
        s = rec.span("serving.exec", 2.0, 2.5, rows=4)
        rec.close(3.0)
        assert s.duration == pytest.approx(0.5)
        assert s.attrs == {"rows": 4}
        assert rec.duration == pytest.approx(2.0)
        assert [x.name for x in rec.spans] == ["serving.exec"]

    def test_trace_ids_are_unique_and_increasing(self):
        a = trace.start_request()
        b = trace.start_request()
        assert a.name == "serving.request"
        assert b.trace_id > a.trace_id

    def test_adopt_shares_spans_and_merges_attrs(self):
        batch = trace.SpanRecorder("serving.batch")
        shared = batch.span("serving.exec", 0.0, 1.0)
        batch.annotate("bucket", 16)
        rt = trace.start_request()
        rt.annotate("tenant", "t0")
        rt.adopt(batch)
        assert shared in rt.spans          # shared, not copied
        assert rt.attrs == {"tenant": "t0", "bucket": 16}

    def test_gate_and_ambient_stack(self):
        rec = trace.SpanRecorder("serving.request")
        # tracing off: current() is None even with a pushed recorder
        trace.push_active(rec)
        assert trace.current() is None
        trace.pop_active()
        trace.enable_tracing()
        assert trace.current() is None
        with trace.activating(rec):
            assert trace.current() is rec
            trace.annotate_current("k", 5)
        assert trace.current() is None
        assert rec.attrs == {"k": 5}

    def test_ambient_stack_is_per_thread(self):
        trace.enable_tracing()
        rec = trace.SpanRecorder("serving.request")
        seen = []
        with trace.activating(rec):
            t = threading.Thread(target=lambda: seen.append(trace.current()))
            t.start()
            t.join()
        assert seen == [None]

    def test_stage_hook_mirrors_stage_timers_as_spans(self):
        rec = trace.SpanRecorder("serving.request")
        with obs.collecting(), trace.tracing_scope(), trace.activating(rec):
            with obs.stage("tracetest.phase"):
                pass
        assert [s.name for s in rec.spans] == ["tracetest.phase"]
        assert rec.spans[0].duration >= 0.0

    def test_tracing_scope_restores_previous_state(self):
        assert not trace.tracing()
        with trace.tracing_scope():
            assert trace.tracing()
            with trace.tracing_scope():
                assert trace.tracing()
            assert trace.tracing()        # outer scope still active
        assert not trace.tracing()


# ---------------------------------------------------------------------------
# flight recorder


class TestFlightRecorder:
    def test_events_always_on(self):
        # neither metrics collection nor tracing is enabled here
        flight.record_event("serving.shed.deadline", tenant="t0", rows=4)
        evs = flight.events("serving.shed.deadline")
        assert len(evs) == 1
        assert evs[0]["attrs"] == {"tenant": "t0", "rows": 4}
        assert evs[0]["trace_id"] is None

    def test_ring_keeps_last_capacity_records(self):
        fr = flight.FlightRecorder(capacity=4)
        for j in range(10):
            fr.record_event("serving.shed.quota", j=j)
        evs = fr.events()
        assert [e["attrs"]["j"] for e in evs] == [6, 7, 8, 9]

    def test_trace_records_and_event_filter(self):
        rec = trace.start_request()
        rec.span("serving.exec", 0.0, 1.0)
        flight.record_trace(rec.close())
        flight.record_event("serving.generation_swap", generation=2)
        flight.record_event("serving.shed.quota", tenant="t")
        assert [t.trace_id for t in flight.traces()] == [rec.trace_id]
        assert len(flight.events()) == 2
        assert len(flight.events("serving.generation_swap")) == 1

    def test_clear(self):
        flight.record_event("serving.shed.quota")
        flight.clear()
        assert flight.events() == [] and flight.traces() == []

    def test_dump_chrome_trace_format(self, tmp_path):
        rec = trace.start_request()
        rec.span("serving.exec", rec.t0, rec.t0 + 0.25)
        # lazy array attribute: only dump() may materialize it
        rec.annotate("distributed.shard_status", np.asarray([1, 1, 0]))
        flight.record_trace(rec.close())
        flight.record_event("distributed.degraded_search",
                            trace_id=rec.trace_id, failed=[2])
        path = tmp_path / "flight.json"
        doc = json.loads(flight.dump(str(path), reason="unit"))
        assert path.exists()
        assert doc["otherData"]["reason"] == "unit"
        complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        instant = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        names = {e["name"] for e in complete}
        assert {"serving.request", "serving.exec"} <= names
        root = next(e for e in complete if e["name"] == "serving.request")
        assert root["tid"] == rec.trace_id
        assert root["args"]["distributed.shard_status"] == [1, 1, 0]
        exec_ev = next(e for e in complete if e["name"] == "serving.exec")
        assert exec_ev["dur"] == pytest.approx(0.25 * 1e6)
        assert instant[0]["name"] == "distributed.degraded_search"
        assert instant[0]["args"] == {"failed": [2]}

    def test_dump_lays_over_a_profiler_trace(self, tmp_path):
        """otherData's clock offset puts a flight event recorded inside a
        profiler annotation inside that annotation on the trace's host
        clock (the trace's profile_start_time plus the event's offset)."""
        import glob

        import jax
        from jax.profiler import ProfileData

        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("raft_tpu:flight.probe"):
                flight.record_event("serving.batch_error", error="probe")
        finally:
            jax.profiler.stop_trace()
        doc = json.loads(flight.dump())
        ev = next(e for e in doc["traceEvents"] if e["ph"] == "i")
        at_ns = (ev["ts"] + doc["otherData"]["profiler_clock_offset_us"]) * 1e3
        pd = ProfileData.from_file(glob.glob(
            str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
        start = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                     if p.name == "Task Environment")
        probe = next(e for p in pd.planes if p.name.startswith("/host:")
                     for line in p.lines for e in line.events
                     if e.name == "raft_tpu:flight.probe")
        slack_ns = 1e6
        assert start + probe.start_ns - slack_ns <= at_ns
        assert at_ns <= start + probe.end_ns + slack_ns

    def test_maybe_auto_dump_env_gated(self, tmp_path, monkeypatch):
        monkeypatch.delenv(flight.DUMP_ENV, raising=False)
        assert flight.maybe_auto_dump("x") is None
        out = tmp_path / "auto.json"
        monkeypatch.setenv(flight.DUMP_ENV, str(out))
        flight.record_event("serving.batch_error", error="boom")
        assert flight.maybe_auto_dump("unit-test") == str(out)
        doc = json.loads(out.read_text())
        assert doc["otherData"]["reason"] == "unit-test"
        # an unwritable path must not raise (the recorder never masks
        # the original serving error)
        monkeypatch.setenv(flight.DUMP_ENV,
                           str(tmp_path / "no" / "such" / "dir" / "f.json"))
        assert flight.maybe_auto_dump("x") is None

    def test_maybe_auto_dump_directory_rotates(self, tmp_path, monkeypatch):
        d = tmp_path / "dumps"
        # trailing separator selects directory mode before the dir exists
        monkeypatch.setenv(flight.DUMP_ENV, str(d) + os.sep)
        monkeypatch.setenv(flight.DUMP_KEEP_ENV, "3")
        flight.record_event("serving.batch_error", error="boom")
        paths = [flight.maybe_auto_dump(f"r{j}") for j in range(5)]
        assert paths[0].endswith("flight-000000.json")
        # only the newest RAFT_TPU_FLIGHT_DUMP_KEEP dumps survive
        assert sorted(os.listdir(d)) == [
            "flight-000002.json", "flight-000003.json", "flight-000004.json"]
        doc = json.loads((d / "flight-000004.json").read_text())
        assert doc["otherData"]["reason"] == "r4"
        # an existing directory without the trailing separator also rotates
        monkeypatch.setenv(flight.DUMP_ENV, str(d))
        p = flight.maybe_auto_dump("r5")
        assert p.endswith("flight-000005.json")
        assert sorted(os.listdir(d)) == [
            "flight-000003.json", "flight-000004.json", "flight-000005.json"]
        # an unparseable keep bound falls back to the default, not a raise
        monkeypatch.setenv(flight.DUMP_KEEP_ENV, "bananas")
        assert flight.maybe_auto_dump("r6").endswith("flight-000006.json")
        assert len(os.listdir(d)) == 4     # 4 <= DEFAULT_DUMP_KEEP: no prune


# ---------------------------------------------------------------------------
# windowed telemetry


@pytest.fixture
def clock(monkeypatch):
    t = {"now": 0.0}
    monkeypatch.setattr(registry_mod, "_now", lambda: t["now"])
    return t


class TestWindowedTelemetry:
    def test_counter_window_ages_out(self, clock):
        reg = registry_mod.MetricsRegistry(window_interval_s=1.0,
                                           window_slots=4)
        c = reg.counter("w.c")
        clock["now"] = 0.5
        c.inc(3)
        clock["now"] = 1.5
        c.inc(2)
        assert c.windowed() == 5
        clock["now"] = 4.2          # window covers epochs 1..4: drops the 3
        assert c.windowed() == 2
        clock["now"] = 9.0
        assert c.windowed() == 0
        assert c.value == 5         # lifetime total persists

    def test_counter_slot_reuse_zeroes_stale_epoch(self, clock):
        reg = registry_mod.MetricsRegistry(window_interval_s=1.0,
                                           window_slots=2)
        c = reg.counter("w.c")
        c.inc(7)                    # epoch 0, slot 0
        clock["now"] = 2.1          # epoch 2 reuses slot 0
        c.inc(1)
        assert c.windowed() == 1    # the stale 7 must not leak in

    def test_histogram_window_quantiles(self, clock):
        reg = registry_mod.MetricsRegistry(window_interval_s=1.0,
                                           window_slots=4)
        h = reg.histogram("w.h")
        for v in (0.001, 0.002, 0.004):
            h.observe(v)
        clock["now"] = 1.5
        h.observe(0.064)
        w = h.windowed_dict()
        assert w["count"] == 4
        assert w["sum"] == pytest.approx(0.071)
        assert w["max"] == pytest.approx(0.064)
        assert 0.001 <= w["p50"] <= 0.004 < w["p99"] <= 0.064
        clock["now"] = 4.8          # window is epochs 1..4: drops epoch 0
        w = h.windowed_dict()
        assert w["count"] == 1
        assert w["p50"] == pytest.approx(0.064, rel=0.5)
        assert h.count == 4         # lifetime view unchanged

    def test_snapshot_window_section(self, clock):
        reg = registry_mod.MetricsRegistry(window_interval_s=2.0,
                                           window_slots=3)
        reg.counter("w.c").inc(4)
        reg.histogram("w.h").observe(0.01)
        snap = reg.snapshot()
        assert snap["window"]["interval_s"] == 2.0
        assert snap["window"]["span_s"] == 6.0
        assert snap["window"]["counters"] == {"w.c": 4}
        assert snap["window"]["histograms"]["w.h"]["count"] == 1

    def test_counter_backwards_clock_drops_future_slots(self, clock):
        # a clock that steps backwards (suspend/resume, test clocks) must
        # never raise, and slots stamped with a now-future epoch are
        # excluded from the sum rather than double-counted
        reg = registry_mod.MetricsRegistry(window_interval_s=1.0,
                                           window_slots=4)
        c = reg.counter("w.c")
        clock["now"] = 10.0
        c.inc(3)
        clock["now"] = 1.0
        assert c.windowed() == 0        # the epoch-10 slot is in the future
        c.inc(1)                        # lands in the earlier epoch cleanly
        assert c.windowed() == 1
        clock["now"] = 10.0             # forward again: future slot intact,
        assert c.windowed() == 3        # the old epoch-1 slot aged out
        assert c.value == 4             # lifetime total saw everything

    def test_counter_jump_beyond_span_empties_window(self, clock):
        reg = registry_mod.MetricsRegistry(window_interval_s=1.0,
                                           window_slots=4)
        c = reg.counter("w.c")
        c.inc(5)
        clock["now"] = 1e9              # jump far past the window span
        assert c.windowed() == 0
        assert c.value == 5

    def test_histogram_clock_jumps(self, clock):
        reg = registry_mod.MetricsRegistry(window_interval_s=1.0,
                                           window_slots=4)
        h = reg.histogram("w.h")
        clock["now"] = 10.0
        h.observe(0.01)
        clock["now"] = 1.0
        assert h.windowed_dict()["count"] == 0    # future slot excluded
        h.observe(0.02)
        w = h.windowed_dict()
        assert w["count"] == 1
        assert w["max"] == pytest.approx(0.02)
        clock["now"] = 1e9
        assert h.windowed_dict()["count"] == 0
        assert h.count == 2             # lifetime view unaffected

    def test_empty_window_shape(self, clock):
        # windowed views on a never-observed metric: zeros, not NaN/None
        reg = registry_mod.MetricsRegistry(window_interval_s=1.0,
                                           window_slots=4)
        assert reg.counter("w.c").windowed() == 0
        w = reg.histogram("w.h").windowed_dict()
        assert w == {"count": 0, "sum": 0.0, "max": 0.0,
                     "p50": 0.0, "p95": 0.0, "p99": 0.0}

    def test_prometheus_exports_window_series(self):
        with obs.collecting() as reg:
            reg.counter("w.c").inc(2)
            reg.histogram("w.h").observe(0.5)
            text = obs.to_prometheus(reg.snapshot())
        assert "raft_tpu_w_c_window 2" in text
        assert "raft_tpu_w_h_window_count 1" in text
        assert "raft_tpu_w_h_window_p99" in text


# ---------------------------------------------------------------------------
# disabled-path cost (the contract the registry docstrings pin)


class _ForbiddenLock:
    """Stand-in lock that fails the test on any acquisition."""

    def __enter__(self):
        raise AssertionError("metric lock acquired while collection is off")

    __exit__ = None

    def acquire(self, *a, **k):
        raise AssertionError("metric lock acquired while collection is off")

    release = acquire


class TestDisabledPathCost:
    def test_stage_yields_shared_noop_and_never_fences(self, monkeypatch):
        def _no_fence(x):
            raise AssertionError("fence on the disabled path")

        monkeypatch.setattr(stage_mod, "_block_until_ready", _no_fence)
        with obs.stage("serving.cut") as a, obs.stage("serving.cut2") as b:
            a.fence(object())
            assert a is b is stage_mod._NOOP   # singleton: no allocation

    def test_disabled_serving_path_never_touches_metric_locks(self,
                                                              monkeypatch):
        """The gate contract: with collection off, the hot path performs
        no lock acquisition and no metric mutation — pinned by swapping
        every metric's lock for one that raises on acquire."""
        reg = registry_mod.MetricsRegistry()
        c = reg.counter("serving.admitted")
        h = reg.histogram("serving.latency.total")
        monkeypatch.setattr(c, "_lock", _ForbiddenLock())
        monkeypatch.setattr(h, "_lock", _ForbiddenLock())

        # the library's gated call-site idiom, off-path
        for _ in range(3):
            if obs.enabled():
                c.inc()
                h.observe(0.001)
            with obs.stage("serving.cut"):
                pass
        assert c.value == 0 and h.count == 0

    def test_stage_hook_is_one_flag_check_when_tracing_off(self,
                                                           monkeypatch):
        # tracing off: stage_hook must not touch thread-local state
        def _no_tls():
            raise AssertionError("ambient stack touched with tracing off")

        monkeypatch.setattr(trace, "_stack", _no_tls)
        trace.stage_hook("serving.cut", 0.001)
        assert trace.current() is None
