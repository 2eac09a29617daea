"""The one traffic generator: turns a traffic mix's parameters and a seed
into the queries a window sends.

Every seed gets the same amount of work: the same multiset of request
sizes and inter-arrival gaps, taken at the quantiles of the mix's
distributions, in an order drawn from the seed.  Which query-pool rows a
request carries is drawn from the seed as well.

Mix parameters (``traffic/<mix>.json``):

- ``loop``: ``"closed"`` (back-to-back batches, one stream) or
  ``"open"`` (requests due on a fixed schedule, whatever the system does).
- closed: ``batch`` rows per call.
- open: ``rate_rows_per_s`` offered rows per second; ``rows``, the rows
  per request, ``{"dist": "zipf", "s": ..., "min": ..., "max": ...}``
  (truncated Zipf); ``arrivals`` ``"poisson"`` (exponential gaps);
  ``max_batch``, ``max_wait_us`` and ``max_queue_rows`` configure the
  server; ``trace_seconds``, the window's last seconds that a traced run
  traces (all of it where absent).
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed))


class RowStream:
    """Query-pool rows in seeded random order: each pass over the pool is
    a fresh permutation, so every row is sent equally often."""

    def __init__(self, n_pool: int, rng: np.random.Generator) -> None:
        self.n_pool, self.rng = n_pool, rng
        self._buf = np.empty(0, np.int64)

    def take(self, n: int) -> np.ndarray:
        while self._buf.size < n:
            self._buf = np.concatenate(
                [self._buf, self.rng.permutation(self.n_pool)])
        out, self._buf = self._buf[:n], self._buf[n:]
        return out


def size_pmf(rows: dict) -> tuple:
    """``(sizes, probabilities)`` of the request-size distribution."""
    if rows["dist"] != "zipf":
        raise ValueError(f"unknown request-size distribution "
                         f"{rows['dist']!r}")
    sizes = np.arange(int(rows["min"]), int(rows["max"]) + 1)
    w = sizes.astype(np.float64) ** -float(rows["s"])
    return sizes, w / w.sum()


def mean_rows(rows: dict) -> float:
    sizes, p = size_pmf(rows)
    return float(np.sum(sizes * p))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def open_schedule(mix: dict, seconds: float, seed: int,
                  rate_rows_per_s: float = None) -> tuple:
    """``(due offsets in seconds (n,), rows per request (n,))`` for an
    open loop of ``seconds``, all due inside ``[0, seconds)``.  The rate
    defaults to the mix's own."""
    rate = float(rate_rows_per_s or mix["rate_rows_per_s"])
    rate_req = rate / mean_rows(mix["rows"])
    # the quantile gaps sum to less than n / rate_req <= seconds, so
    # every request is due inside the window
    n = max(1, int(rate_req * seconds))
    rng = rng_for(seed)
    sizes, p = size_pmf(mix["rows"])
    req_sizes = sizes[np.searchsorted(np.cumsum(p), _quantiles(n),
                                      side="right").clip(0, sizes.size - 1)]
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    gaps = -np.log1p(-_quantiles(n)) / rate_req
    req_sizes = rng.permutation(req_sizes)
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due, req_sizes.astype(np.int64)
