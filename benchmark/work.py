"""Operation and byte counts for the rooflines.

The IVF-PQ list scan's work is counted from the index's list sizes and
the probe sets that a plain host coarse selection on ``index.centers``
gives, so it reads the same whatever scan formulation runs:

- operations: ``2 * dim`` per (query, probed row) pair;
- bytes: the PQ codes plus a 4-byte id of every row of every distinct
  list the batch probes, read once per batch;
- least time: the larger of operations over the bf16 peak and bytes over
  the HBM peak.
"""

from __future__ import annotations

import numpy as np

ID_BYTES = 4


def coarse_probes(queries, centers, rotation, n_probes: int,
                  block: int = 2048) -> np.ndarray:
    """Exact top-``n_probes`` lists by squared L2 between the rotated
    queries and the list centers: host numpy (nq, n_probes)."""
    q = np.asarray(queries, np.float64) @ np.asarray(rotation, np.float64)
    c = np.asarray(centers, np.float64)
    c_sq = np.sum(c * c, axis=1)
    out = []
    for s in range(0, q.shape[0], block):
        d = c_sq[None, :] - 2.0 * (q[s:s + block] @ c.T)
        out.append(np.argpartition(d, n_probes - 1, axis=1)[:, :n_probes])
    return np.concatenate(out)


def scan_work(batches, probes, list_sizes, dim: int,
              code_bytes: int) -> dict:
    """Summed work of the list scans of ``batches`` (each an array of
    query-pool rows); ``probes`` (n_pool, n_probes) from
    :func:`coarse_probes`; ``list_sizes`` (n_lists,) live rows per list."""
    sizes = np.asarray(list_sizes, np.int64)
    pairs = 0
    rows_read = 0
    for rows in batches:
        p = probes[np.asarray(rows)]
        pairs += int(sizes[p].sum())
        rows_read += int(sizes[np.unique(p)].sum())
    return {"pairs": pairs, "flops": 2.0 * dim * pairs,
            "bytes": float(rows_read * (code_bytes + ID_BYTES))}


def least_seconds(work: dict, peak: dict) -> tuple:
    """``(seconds, bound)``: the least time the chip could take for
    ``work`` and which peak bounds it (``"compute"`` or ``"memory"``)."""
    t_ops = work["flops"] / peak["bf16_flops_per_s"]
    t_mem = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
