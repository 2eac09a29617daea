"""The comparison that decides ``correct``.

Every answer the window returned is compared with the plain reference
(:mod:`benchmark.reference`), once the window has closed:

- ``invalid``: answers (query rows) with an id outside the database, a
  repeated id, or a distance that is not finite.  Exact: limit 0.
- ``recall_shortfall``: 1 - pooled recall@k, hits counted against the
  reference's exact k nearest neighbours of the same query.
- ``dist_gap``: the widest relative gap between a distance the program
  returned and the exact squared L2 from the query to the id it returned,
  over every valid answer.  It holds each returned (id, distance) pair to
  the reference one by one, so an altered answer shows even where recall
  cannot see it.
- ``dist_gap_mean``: the mean of the same relative gaps: the steady
  reading that a distance computed in a lower precision moves.
- ``lost``: requests shed at admission, never resolved, or resolved
  with an error (open loops only).  Exact: limit 0.

Each number is compared with its limit from the configuration file;
``PERF.md`` gives the readings each limit was set from.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference

# distances below this are compared absolutely (no valid pair of distinct
# rows of the generated data comes near it)
DIST_FLOOR = 1e-6


def numbers(pool, db, rows, ids, dists, ref_ids) -> dict:
    """The compared numbers for answers ``ids``/``dists`` (n, k) to the
    queries ``pool[rows]``; ``ref_ids`` is the reference's (n_pool, k)."""
    rows = np.asarray(rows)
    ids = np.asarray(ids)
    dists = np.asarray(dists, np.float32)
    n, k = ids.shape
    n_db = db.shape[0]
    in_range = ((ids >= 0) & (ids < n_db)).all(axis=1)
    s = np.sort(ids, axis=1)
    repeated = (s[:, 1:] == s[:, :-1]).any(axis=1)
    finite = np.isfinite(dists).all(axis=1)
    ok = in_range & ~repeated & finite
    truth = np.asarray(ref_ids)[rows]
    hits = int((ids[:, :, None] == truth[:, None, :]).any(axis=2).sum())
    exact = reference.pair_distances(pool, rows, db, ids)
    gap = np.abs(dists - exact) / np.maximum(exact, DIST_FLOOR)
    return {"invalid": int((~ok).sum()),
            "recall": hits / float(n * k),
            "recall_shortfall": 1.0 - hits / float(n * k),
            "dist_gap": float(gap[ok].max()) if ok.any() else float("inf"),
            "dist_gap_mean": (float(gap[ok].mean()) if ok.any()
                              else float("inf"))}


def judge(values: dict, limits: dict) -> tuple:
    """``(correct, checks)``: ``checks`` maps each limited number to its
    value and limit, in the order of ``limits``."""
    checks = {name: {"value": values[name], "limit": limit}
              for name, limit in limits.items() if name in values}
    missing = [name for name in limits if name not in values]
    if missing:
        raise KeyError(f"no reading for limited numbers {missing}")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return correct, checks
