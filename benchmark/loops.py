"""The two ways a window drives the system: a closed loop of batches and
an open loop of requests into ``serving.Server``.

Both are built from a traffic mix (:mod:`benchmark.generator`), warm
every shape the window uses in :meth:`warm`, and return a
:class:`Window` with every answer for the comparison.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from benchmark import generator


@dataclasses.dataclass
class Window:
    seconds: float                 # length of the measured window
    rows: np.ndarray               # query-pool row of each answer (n,)
    ids: np.ndarray                # (n, k)
    dists: np.ndarray              # (n, k)
    batches: List[np.ndarray]      # pool rows of each device call (closed)
    attempted: int
    failed: int
    lost: int                      # shed, never resolved, or in error
    metrics: Dict[str, float]      # the loop's own end-to-end numbers
    notes: Dict[str, object]       # printed on an earlier line


@jax.jit
def _take(pool, rows):
    return pool[rows]


class ClosedLoop:
    """One stream of back-to-back batches of ``mix["batch"]`` pool rows,
    each ending in a host readback of ids and distances."""

    def __init__(self, res, system, cfg, mix, index, db, pool, seed):
        self.fn = system.batch_fn(res, cfg, index, db)
        self.pool = pool
        self.batch = int(mix["batch"])
        self.stream = generator.RowStream(pool.shape[0],
                                          generator.rng_for(seed))
        self.n_shapes = 1

    def _call(self, rows):
        with TraceAnnotation("bench.batch_cut"):
            q = _take(self.pool, jnp.asarray(rows, jnp.int32))
        d, i = self.fn(q)
        with TraceAnnotation("bench.readback"):
            return np.asarray(d), np.asarray(i)

    def warm(self) -> None:
        rows = np.arange(self.batch) % self.pool.shape[0]
        for _ in range(2):
            self._call(rows)

    def window(self, seconds: float, start_trace=None) -> Window:
        """``start_trace``, where given, is called as the window starts:
        a traced run traces the whole of a closed loop."""
        batches, ids, dists = [], [], []
        if start_trace is not None:
            start_trace()
        t0 = time.perf_counter()
        while True:
            rows = self.stream.take(self.batch)
            d, i = self._call(rows)
            batches.append(rows)
            ids.append(i)
            dists.append(d)
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        n = sum(b.size for b in batches)
        return Window(seconds=elapsed, rows=np.concatenate(batches),
                      ids=np.concatenate(ids), dists=np.concatenate(dists),
                      batches=batches, attempted=n, failed=0, lost=0,
                      metrics={"qps": n / elapsed},
                      notes={"batches": len(batches)})

    def close(self) -> None:
        self.fn = None


def _answer(j, lo, hi, ids, dists, done, state, future) -> None:
    """Done-callback of request ``j`` (rows ``lo:hi`` of the window): its
    answer, its time and whether it failed."""
    done[j] = time.perf_counter()
    if future.exception() is not None:
        state[j] = 2
        return
    d, i = future.result()
    dists[lo:hi] = d
    ids[lo:hi] = i
    state[j] = 1


def _annotated(fn, name):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


class OpenLoop:
    """Requests due on the mix's schedule, submitted to a
    ``serving.Server`` whatever its state.  Each request is timed from the
    moment it was due to the moment its result reached the host; a request
    shed at admission or never answered counts as infinitely late, and as
    lost."""

    LEAD_S = 0.01          # first request is due this long after start
    DRAIN_S = 60.0         # answers are awaited this long past the close

    def __init__(self, res, system, cfg, mix, index, db, pool, seed):
        from raft_tpu import serving

        self.serving = serving
        ex = system.executor(res, cfg, index, mix)
        ex.search_bucket = _annotated(ex.search_bucket, "bench.dispatch")
        self.server = serving.Server(ex, serving.ServerConfig(
            max_batch=int(mix["max_batch"]),
            max_wait_us=float(mix["max_wait_us"]),
            max_queue_rows=int(mix["max_queue_rows"])))
        self.k = int(cfg["index"]["k"])
        self.mix, self.seed = mix, seed
        self.pool_host = np.asarray(pool)
        self.n_shapes = len(ex.buckets)
        self._buckets = ex.buckets

    def warm(self) -> None:
        self.server.start()
        # one request at each bucket size settles the host-side one-time
        # work (transfers, masks) outside the window
        for b in self._buckets:
            self.server.search(self.pool_host[:b], self.k)

    def window(self, seconds: float, rate_rows_per_s: float = None,
               start_trace=None) -> Window:
        """``start_trace``, where given, is called where the window's
        traced span begins: its last ``trace_seconds`` of the mix (all of
        it where the mix names none), since a trace of every request's
        device ops takes minutes to write and read."""
        due, sizes = generator.open_schedule(self.mix, seconds, self.seed,
                                             rate_rows_per_s)
        trace_from = seconds - float(self.mix.get("trace_seconds", seconds))
        n = len(due)
        stream = generator.RowStream(
            self.pool_host.shape[0], np.random.default_rng([self.seed, 1]))
        rows = stream.take(int(sizes.sum()))
        off = np.concatenate([[0], np.cumsum(sizes)])
        # answers land in preallocated arrays and no future outlives its
        # request: the loop leaves no garbage that would make the
        # collector pause the process in the window
        ids = np.full((rows.size, self.k), -1, np.int64)
        dists = np.full((rows.size, self.k), np.inf, np.float32)
        done = np.full(n, np.nan)
        sub = np.full(n, np.nan)
        state = np.zeros(n, np.int8)          # 1 answered, 2 failed
        queued = np.zeros(n, bool)
        shed = 0
        if start_trace is not None and trace_from <= 0:
            start_trace()
            start_trace = None
        t0 = time.perf_counter() + self.LEAD_S
        for j in range(n):
            if start_trace is not None and due[j] >= trace_from:
                start_trace()
                start_trace = None
            wait = t0 + due[j] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sub[j] = time.perf_counter()
            with TraceAnnotation("bench.submit"):
                try:
                    f = self.server.submit(
                        self.pool_host[rows[off[j]:off[j + 1]]], self.k)
                except self.serving.Overloaded:
                    shed += 1
                    continue
            queued[j] = True
            f.add_done_callback(functools.partial(
                _answer, j, off[j], off[j + 1], ids, dists, done, state))
            del f
        with TraceAnnotation("bench.drain"):
            close = t0 + seconds + self.DRAIN_S
            while (np.any(queued & (state == 0))
                   and time.perf_counter() < close):
                time.sleep(0.005)
        ok = state == 1
        lost = int(np.count_nonzero(~ok))
        latency = np.where(ok, done - (t0 + due), np.inf)
        late = sub - (t0 + due)
        answered = np.repeat(ok, sizes)
        return Window(
            seconds=seconds, rows=rows[answered], ids=ids[answered],
            dists=dists[answered], batches=[], attempted=n,
            failed=int(n - ok.sum()), lost=lost,
            metrics={"p50_ms": float(np.quantile(latency, 0.5,
                                                 method="higher")) * 1e3},
            notes={"requests": n, "rows": int(sizes.sum()), "shed": shed,
                   "offered_rows_per_s": float(sizes.sum() / seconds),
                   "p99_ms": float(np.quantile(latency, 0.99,
                                               method="higher")) * 1e3,
                   "generator_late_p99_ms": float(np.nanquantile(
                       late, 0.99)) * 1e3,
                   "generator_late_max_ms": float(np.nanmax(late)) * 1e3,
                   "completed_rows_per_s": float(
                       sizes[ok & (done <= t0 + seconds)].sum() / seconds),
                   "last_answer_after_close_s": float(
                       np.nanmax(done) - (t0 + seconds))
                   if ok.any() else None})

    def close(self) -> None:
        self.server.stop()
        self.server = None


LOOPS = {"closed": ClosedLoop, "open": OpenLoop}
