"""Device idle time inside the library's host spans, and the trace
reduction it must agree with."""

from pathlib import Path

import pytest

from benchmark import spans, trace_reduce

DATA = Path(__file__).resolve().parent / "data"
SMALL = DATA / "small.xplane.pb"
SPANS = DATA / "spans.xplane.pb"


def test_overlap_of_interval_lists():
    assert spans.overlap([(0, 2), (5, 9)], [(1, 6), (8, 20)]) == 1 + 1 + 1
    assert spans.overlap([(0, 1)], [(1, 2)]) == 0
    assert spans.overlap([], [(0, 1)]) == 0


def test_idle_gaps_fill_the_window_around_busy_time():
    assert spans.idle_gaps([(2, 4), (3, 5), (8, 12)], 0, 10) == [
        (0, 2), (5, 8)]
    assert spans.idle_gaps([], 0, 10) == [(0, 10)]


def test_idle_inside_one_span():
    # busy 0-4 and 6-10: one 2 s gap, all of it inside the sync span
    s = spans.summarize((0, 10), [[(0, 4), (6, 10)]],
                        [("raft_tpu:integrity.sync", 3, 7),
                         ("raft_tpu:serving.dispatch", 0, 2)])
    assert s.idle_s == 2
    assert s.idle_in == {"raft_tpu:integrity.sync": 2,
                         "raft_tpu:serving.dispatch": 0}
    assert s.spans["raft_tpu:integrity.sync"] == [1, 4]
    assert s.share(["raft_tpu:integrity.sync"]) == pytest.approx(20.0)


def test_a_gap_inside_two_spans_on_two_threads_counts_for_both():
    # threads are not told apart: each name's intervals are unioned, and
    # a gap under two names counts for each
    s = spans.summarize((0, 10), [[(0, 4), (6, 10)]],
                        [("raft_tpu:serving.dispatch", 3, 8),
                         ("raft_tpu:serving.dispatch", 3.5, 7),
                         ("raft_tpu:serving.wait", 4.5, 5.5)])
    assert s.idle_in["raft_tpu:serving.dispatch"] == 2
    assert s.idle_in["raft_tpu:serving.wait"] == 1
    assert s.spans["raft_tpu:serving.dispatch"] == [2, 8.5]
    assert s.longest == [{"at_s": 4, "idle_s": 2,
                          "in": ["raft_tpu:serving.dispatch",
                                 "raft_tpu:serving.wait"]}]


def test_a_span_crossing_the_window_edge_counts_inside_it_only():
    s = spans.summarize((10, 20), [[(12, 20)]],
                        [("raft_tpu:serving.wait", 5, 11),
                         ("raft_tpu:refine", 21, 30)])
    assert s.window_s == 10 and s.idle_s == 2
    assert s.idle_in == {"raft_tpu:serving.wait": 1}
    assert s.spans == {"raft_tpu:serving.wait": [1, 1]}


def test_idle_is_averaged_over_devices():
    s = spans.summarize((0, 10), [[(0, 10)], [(0, 6)]],
                        [("raft_tpu:serving.readback", 5, 10)], scale=2.0)
    assert s.n_devices == 2
    assert s.window_s == 20
    assert s.idle_s == pytest.approx(4.0)
    assert s.idle_in["raft_tpu:serving.readback"] == pytest.approx(4.0)


@pytest.fixture(scope="module")
def small():
    return trace_reduce.reduce(str(SMALL))


def test_recorded_trace_reduction_is_pinned(small):
    # the device-side reduction of the recorded fixture, as the
    # benchmark's metrics read it; spans must not move any of it
    assert small.window_s == pytest.approx(0.018388579, rel=1e-9)
    assert small.busy_s == pytest.approx(0.002491773, rel=1e-9)
    assert small.n_devices == 1
    assert len(small.ops) == 226
    assert sum(small.ops.values()) == pytest.approx(0.003438697, rel=1e-9)
    assert len(small.modules) == 11
    assert sum(small.modules.values()) == pytest.approx(0.002495262,
                                                       rel=1e-9)
    assert small.gaps == pytest.approx({
        "bench.search": 0.002223568, "bench.refine": 0.005490817,
        "bench.readback": 0.005432567, "bench.dispatch": 0.002749854},
        rel=1e-9)


def test_spans_agree_with_the_device_reduction(small):
    s = spans.reduce(str(SMALL))
    assert s.window_s == pytest.approx(small.window_s, rel=1e-12)
    assert s.idle_s == pytest.approx(small.window_s - small.busy_s,
                                     rel=1e-9)
    # the fixture predates the stage and sync spans: only the library's
    # entry-point ranges are in it
    assert set(s.spans) == {"raft_tpu:ivf_pq::search", "raft_tpu:refine",
                            "raft_tpu:cagra::search"}
    assert all(c == 2 for c, _ in s.spans.values())
    for name, idle in s.idle_in.items():
        assert 0 <= idle <= s.spans[name][1] * (1 + 1e-9)


def test_recorded_library_spans():
    # recorded on one v5e by record_spans.py under the default policy: a
    # fused IVF-PQ search and refine, then one batch served through
    # serving.Server over CAGRA
    s = spans.reduce(str(SPANS))
    t = trace_reduce.reduce(str(SPANS))
    assert s.idle_s == pytest.approx(t.window_s - t.busy_s, rel=1e-9)
    assert {"raft_tpu:serving.batch_cut", "raft_tpu:serving.dispatch",
            "raft_tpu:serving.readback", "raft_tpu:serving.resolve",
            "raft_tpu:integrity.sync", "raft_tpu:ivf_pq.search.coarse",
            "raft_tpu:ivf_pq.search.fused_scan", "raft_tpu:refine",
            "raft_tpu:cagra.search.fused_walk"} <= set(s.spans)
    for name, idle in s.idle_in.items():
        assert 0 <= idle <= s.idle_s * (1 + 1e-9)
        assert idle <= s.spans[name][1] * (1 + 1e-9)
    # the dispatcher's phases are disjoint, so their idle adds up
    assert s.share(["raft_tpu:serving.batch_cut", "raft_tpu:serving.dispatch",
                    "raft_tpu:serving.readback", "raft_tpu:serving.resolve"]
                   ) <= 100 * s.idle_s / s.window_s * (1 + 1e-9)


@pytest.mark.parametrize("name,want", [
    ("sift1m-ivfpq.batch5k", {"raft_tpu:integrity.sync", "raft_tpu:refine",
                              "raft_tpu:ivf_pq::search"}),
    ("sift1m-cagra.online", {"raft_tpu:serving.wait",
                             "raft_tpu:serving.batch_cut",
                             "raft_tpu:serving.dispatch",
                             "raft_tpu:serving.readback",
                             "raft_tpu:serving.resolve",
                             "raft_tpu:integrity.sync"})])
def test_traced_rehearsal_logs_the_library_spans(name, want, on_cpu,
                                                 monkeypatch, tmp_path):
    # the script's wrapper around the benchmark's own reduction, on a
    # small CPU cell: the host spans are there, the device plane is not
    import time

    from benchmark import run
    from benchmark.tests import small as cells

    lines = []
    # a trace directory of its own: the rehearsals of test_rehearsal.py
    # may trace the same cell at the same time in another worker
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(trace_reduce, "reduce", spans._logging_reduce(
        lambda what, **kv: lines.append((what, kv))))
    out = run.run_cell(cells.small(run.cell_spec(name)), cells.SEED, 1.0,
                       True, cells.PEAK, time.perf_counter())
    assert out["correct"], out["checks"]
    [(what, kv)] = lines
    assert what == "library spans"
    assert want <= set(kv["spans"])
    assert all(c >= 1 and s >= 0 for c, s in kv["spans"].values())
