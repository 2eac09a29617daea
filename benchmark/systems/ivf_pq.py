"""IVF-PQ: build, search refined to k, and a serving executor."""

from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

from raft_tpu import serving
from raft_tpu.neighbors import ivf_pq
from raft_tpu.neighbors.refine import refine
from raft_tpu.ops import pq_code_scan_pallas as pcs
from raft_tpu.ops import pq_group_scan_pallas as pgs

KERNELS = ((pgs, "grouped_l2_scan_fused"), (pcs, "grouped_code_scan_fused"))
FALLBACK_EVENT = "ivf_pq.fused_fallback"


def build(res, cfg, db):
    return ivf_pq.build(res, ivf_pq.IndexParams(**cfg["index"]["build"]), db)


def _params(cfg):
    return ivf_pq.SearchParams(**cfg["index"]["search"])


def batch_fn(res, cfg, index, db):
    """``ivf_pq.search`` at ``k * refine_ratio``, then ``refine`` to k."""
    sp, k = _params(cfg), int(cfg["index"]["k"])
    kk = k * int(cfg["index"].get("refine_ratio", 1))

    def run(q):
        with TraceAnnotation("bench.search"):
            d, i = ivf_pq.search(res, sp, index, q, kk)
        if kk > k:
            with TraceAnnotation("bench.refine"):
                d, i = refine(res, db, q, i, k)
        return d, i
    return run


def executor(res, cfg, index, mix):
    """Serves ``ivf_pq.search`` at k through the live kernels (no
    refine: the server has no refine stage)."""
    return serving.Executor(res, "ivf_pq", index, ks=(int(cfg["index"]["k"]),),
                            max_batch=int(mix["max_batch"]),
                            search_params=_params(cfg), warm="jit")


def layout(index, cfg):
    return {"centers": np.asarray(index.centers),
            "rotation": np.asarray(index.rotation),
            "list_sizes": np.asarray(index.list_sizes),
            "dim": int(index.dim), "code_bytes": int(index.code_width),
            "n_probes": int(cfg["index"]["search"]["n_probes"])}
