"""CAGRA: graph build, graph-walk search and a serving executor."""

from __future__ import annotations

from jax.profiler import TraceAnnotation

from raft_tpu import serving
from raft_tpu.neighbors import cagra
from raft_tpu.ops import cagra_hop_pallas as chp

KERNELS = ((chp, "fused_hop"),)
FALLBACK_EVENT = None


def build(res, cfg, db):
    return cagra.build(res, cagra.IndexParams(**cfg["index"]["build"]), db)


def _params(cfg):
    return cagra.SearchParams(**cfg["index"]["search"])


def batch_fn(res, cfg, index, db):
    sp, k = _params(cfg), int(cfg["index"]["k"])

    def run(q):
        with TraceAnnotation("bench.search"):
            return cagra.search(res, sp, index, q, k)
    return run


def executor(res, cfg, index, mix):
    return serving.Executor(res, "cagra", index, ks=(int(cfg["index"]["k"]),),
                            max_batch=int(mix["max_batch"]),
                            search_params=_params(cfg), warm="jit")


def layout(index, cfg):
    return None
