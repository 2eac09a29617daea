"""The traffic generator and the open loop's due-time accounting."""

import concurrent.futures
import threading
import time

import numpy as np
import pytest

from benchmark import generator, loops, run

# the online cell's own mix, at a rate a test holds
MIX = dict(run.load_json(run.HERE / "traffic" / "online.json"),
           rate_rows_per_s=2000.0)


def test_every_seed_gets_the_same_work_in_another_order():
    d1, s1 = generator.open_schedule(MIX, 10.0, 1)
    d2, s2 = generator.open_schedule(MIX, 10.0, 2**31 + 7)
    assert len(d1) == len(d2)
    assert sorted(s1) == sorted(s2) and not np.array_equal(s1, s2)
    # the same gaps, all but the one after the last request
    g1, g2 = (np.round(np.diff(d), 9) for d in (d1, d2))
    assert np.isin(g1, g2).sum() >= len(g1) - 1
    for d, s in ((d1, s1), (d2, s2)):
        assert d[0] == 0.0 and np.all(np.diff(d) >= 0) and d[-1] < 10.0
        assert s.min() >= 1 and s.max() <= 64
    # the offered rate is the mix's
    assert s1.sum() / 10.0 == pytest.approx(2000.0, rel=0.02)


def test_zipf_sizes():
    assert generator.mean_rows(MIX["rows"]) == pytest.approx(6.18, abs=0.05)
    _, s = generator.open_schedule(MIX, 10.0, 3)
    assert np.median(s) == 2


def test_row_stream_sends_every_row_equally_often():
    st = generator.RowStream(10, generator.rng_for(5))
    rows = st.take(30)
    assert np.bincount(rows, minlength=10).tolist() == [3] * 10


class _SlowServer:
    """Answers each request after ``exec_s``, one at a time, and blocks
    the caller of the first submit for ``stall_s``."""

    def __init__(self, exec_s, stall_s):
        self.exec_s, self.stall_s, self.n = exec_s, stall_s, 0
        self.pool = concurrent.futures.ThreadPoolExecutor(1)
        self.lock = threading.Lock()

    def submit(self, q, k):
        with self.lock:
            self.n += 1
            first = self.n == 1
        if first:
            time.sleep(self.stall_s)

        def answer():
            time.sleep(self.exec_s)
            n = q.shape[0]
            return (np.zeros((n, k), np.float32),
                    np.tile(np.arange(k), (n, 1)))
        return self.pool.submit(answer)


class _Overloaded(Exception):
    pass


def _open_loop(server, mix):
    lp = loops.OpenLoop.__new__(loops.OpenLoop)
    lp.server, lp.k, lp.mix, lp.seed = server, 3, mix, 9
    lp.pool_host = np.zeros((100, 4), np.float32)

    class _Serving:
        Overloaded = _Overloaded
    lp.serving = _Serving
    return lp


def test_latency_counts_from_the_due_time_and_lateness_is_reported():
    mix = dict(MIX, rate_rows_per_s=1000.0)
    server = _SlowServer(exec_s=0.001, stall_s=0.3)
    due, sizes = generator.open_schedule(mix, 1.0, 9)
    win = _open_loop(server, mix).window(1.0)
    # a stall of the generator shows as lateness, and every request due
    # during the stall is late by what it waited: about a third of the
    # requests are due in the first 0.3 s, so the 99th percentile is late
    # by nearly the whole stall
    assert (due < 0.25).mean() > 0.1
    assert win.notes["generator_late_max_ms"] >= 250
    assert win.notes["p99_ms"] >= 250
    assert 0 < win.metrics["p50_ms"] <= win.notes["p99_ms"]
    assert win.attempted == len(due) and win.failed == 0 and win.lost == 0
    assert win.rows.shape == (sizes.sum(),)
    assert win.ids.shape == (sizes.sum(), 3)


def test_unanswered_requests_are_lost_and_infinitely_late(monkeypatch):
    mix = dict(MIX, rate_rows_per_s=100.0)

    class _Never(_SlowServer):
        def submit(self, q, k):
            if self.n == 3:
                self.n += 1
                return concurrent.futures.Future()      # never resolves
            return super().submit(q, k)
    monkeypatch.setattr(loops.OpenLoop, "DRAIN_S", 0.2)
    win = _open_loop(_Never(0.001, 0.0), mix).window(0.5)
    assert win.lost == 1 and win.failed == 1
    assert win.notes["p99_ms"] == float("inf")


def test_shed_requests_are_lost_and_infinitely_late():
    mix = dict(MIX, rate_rows_per_s=100.0)

    class _Shedding(_SlowServer):
        calls = 0

        def submit(self, q, k):
            self.calls += 1
            if self.calls == 2:
                raise _Overloaded("queue full")
            return super().submit(q, k)
    win = _open_loop(_Shedding(0.001, 0.0), mix).window(0.5)
    assert win.notes["shed"] == 1
    assert win.lost == 1 and win.failed == 1
    assert win.notes["p99_ms"] == float("inf")


def test_a_traced_open_loop_starts_its_trace_for_the_last_seconds():
    mix = dict(MIX, rate_rows_per_s=1000.0, trace_seconds=0.4)
    lp = _open_loop(_SlowServer(0.001, 0.0), mix)
    started = []
    t0 = time.perf_counter()
    win = lp.window(1.0, start_trace=lambda: started.append(
        time.perf_counter() - t0))
    assert len(started) == 1 and 0.55 <= started[0] <= 0.75
    assert win.failed == 0
