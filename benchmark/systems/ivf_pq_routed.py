"""Routed IVF-PQ over several chips: the single-chip index's lists placed
by owner (``placement="by_list"``) with replicas, a load-aware routing
policy on every search, and the refine run on the shard that owns the
row (``distributed.ann.search(..., refine_ratio=r)``).

The configuration's ``index.placement`` gives the chips and the
replication factor; the base index is built once (the same call, seed
and parameters as the single-chip ``ivf_pq`` adapter's) and then placed
over a comms session on the first ``chips`` devices."""

from __future__ import annotations

import jax
from jax.profiler import TraceAnnotation

from raft_tpu.comms import CommsSession
from raft_tpu.distributed import ann
from raft_tpu.distributed.ann import global_list_sizes
from raft_tpu.distributed.routing import RoutingPolicy
from raft_tpu.neighbors import ivf_pq
from raft_tpu.ops import pq_code_scan_pallas as pcs
from raft_tpu.ops import pq_group_scan_pallas as pgs

KERNELS = ((pgs, "grouped_l2_scan_fused"), (pcs, "grouped_code_scan_fused"))
FALLBACK_EVENT = "ivf_pq.fused_fallback"


def _handle(devices):
    """A comms handle over ``devices`` as one 1-D mesh axis."""
    return CommsSession(devices=list(devices),
                        axis_name="data").init().worker_handle()


def build(res, cfg, db):
    placed = cfg["index"]["placement"]
    handle = _handle(jax.devices()[:int(placed["chips"])])
    base = ivf_pq.build(res, ivf_pq.IndexParams(**cfg["index"]["build"]),
                        db)
    return ann.shard_by_list(
        handle, base, replication_factor=int(placed["replication_factor"]),
        dataset=db)


def batch_fn(res, cfg, index, db):
    """One routed search per batch: the shards scan at ``k * ratio``,
    re-rank against the rows they hold, and the merge returns k."""
    sp = ivf_pq.SearchParams(**cfg["index"]["search"])
    k, ratio = int(cfg["index"]["k"]), int(cfg["index"]["refine_ratio"])
    # the session over the devices the index was placed on (an equal mesh)
    handle = _handle(index.list_indices.sharding.mesh.devices.ravel())
    # refresh() is never called in the window: maintenance pays it, not
    # the query path
    policy = RoutingPolicy(index.n_shards)

    def run(q):
        with TraceAnnotation("bench.search"):
            return ann.search(handle, sp, index, q, k, refine_ratio=ratio,
                              routing=policy)
    return run


def layout(index, cfg):
    books = index.codebooks
    return {"centers": jax.device_get(index.coarse_centers),
            "rotation": jax.device_get(index.rotation),
            "list_sizes": global_list_sizes(index),
            "dim": int(index.dim),
            "code_bytes": int(books.shape[0]) * int(index.pq_bits) // 8,
            "n_probes": int(cfg["index"]["search"]["n_probes"]),
            "n_devices": int(index.n_shards)}
