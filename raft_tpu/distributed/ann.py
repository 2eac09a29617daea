"""Multi-device (MNMG) IVF-PQ: sharded build + search-with-merge.

The reference ships the seam, not the algorithm: row-sharded ANN with
per-part search and a top-k merge (``knn_merge_parts``,
neighbors/brute_force.cuh:80; the ANN bench's ``multigpu`` option,
docs/source/cuda_ann_benchmarks.md:163; CAGRA's explicit multi-GPU chunking,
detail/cagra/graph_core.cuh:333-369).  raft_tpu provides the full algorithm:

- **build**: rows are split across the mesh axis; each shard trains its own
  local IVF-PQ index over its rows (ids pre-offset to global), and the local
  indexes are stacked leaf-wise into one device-sharded pytree — shard i's
  leaves live on device i (``P(axis)`` on the stacked axis).
- **search**: one ``shard_map`` — every device searches its local shard with
  the single-chip kernel (queries replicated), then an ``all_gather`` of the
  (q, k) candidates (tiny payload over ICI) and a replicated merge-select.

This is the same shard → local select_k → all_gather → merge shape as
:mod:`raft_tpu.distributed.knn`, applied to the compressed index.

Two placements coexist (round 8):

- ``placement="by_row"`` (the original data-parallel mode above): every
  shard scans its whole local index for every query — per-chip scan work
  is constant in the chip count.
- ``placement="by_list"`` (index-parallel, :class:`RoutedIndex`): ONE
  global coarse quantizer, replicated on every chip, with the IVF lists
  partitioned across shards balanced by live list size
  (:func:`compute_placement`).  Search *routes* each query's ``n_probes``
  probe set: a shard scans only the probed lists it owns (unowned probes
  lower to an always-empty dummy list slot — the same ``id < 0`` /
  worst-distance padded-row path tombstones ride, zero kernel changes),
  then the k-bounded candidate exchange — per-shard local top-k,
  fixed-size ``all_gather`` of (q, k) pairs, replicated
  ``grouped.finalize_topk`` merge — replaces the full-index gather.
  Per-chip candidate work drops by ~``n_shards`` at identical results:
  any global top-k candidate is in its owning shard's local top-k, so
  the routed search is exactly the single-index search.

Scan formulations under ``shard_map`` (round 10): group construction is
now fully traceable at a static capacity
(:func:`raft_tpu.neighbors.grouped.group_capacity`), so the grouped and
fused scans lower under ``shard_map`` for both placements —
``scan_mode="fused"`` runs the same formulation ladder the single-index
search picks (fused Pallas kernels on TPU, the XLA grouped twin
elsewhere) instead of the pre-round-10 blanket lowering to the
probe-order recon scan.  :func:`raft_tpu.neighbors.ivf_pq.plan_scan`
decides the formulation for both placements and the single index;
:data:`SHARD_OK_FALLBACK` marks only the genuinely unsupported
combinations (the code modes on a routed index, which holds no packed
codes, or on a stacked index without PQ metadata).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import serialize as ser
from raft_tpu.core import platform as _platform
from raft_tpu.core.compat import shard_map
from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import ensure_array
from raft_tpu.core.tracing import annotation as _annotation
from raft_tpu.core.tracing import range as named_range
from raft_tpu.distance.types import DistanceType
from raft_tpu.filters import bitset as _fbits
from raft_tpu.matrix.select_k import select_k
from raft_tpu.neighbors import grouped
from raft_tpu.neighbors import ivf_pq
from raft_tpu.neighbors import mutate as _mutate
from raft_tpu.neighbors.refine import exact_distances as _exact_distances
from raft_tpu.observability import flight as _flight
from raft_tpu.observability import trace as _rtrace
from raft_tpu.resilience import faults
from raft_tpu.resilience import retry as _retry

P = jax.sharding.PartitionSpec

# per-shard status codes (the ``return_status=True`` vector).  OK_FALLBACK
# marks a LIVE shard whose requested ``scan_mode`` has no distributed
# formulation and was lowered to the probe-order recon scan — since
# round 10 the exception, not the rule (fused/grouped scans lower under
# ``shard_map`` at the static group capacity; results are correct either
# way, only the formulation differs).  REPLICA_SERVED marks a shard that
# did not answer (failed, or hedged around as a straggler) but whose
# owned lists were scanned by healthy replicas — results are COMPLETE,
# the code is routing telemetry, not a degradation signal.
SHARD_FAILED = 0
SHARD_OK = 1
SHARD_OK_FALLBACK = 2
SHARD_REPLICA_SERVED = 3


def _entry(site, fn, retry_policy, deadline):
    """Run an entry point under retry/deadline with a host-side fault
    site checked per attempt (jit caching never skips it, unlike the
    trace-time ``comms.*`` sites)."""
    def attempt():
        faults.maybe_fail(site)
        return fn()
    return _retry.retry_call(attempt, site=site, policy=retry_policy,
                             deadline=deadline)


def _dispatch(fn, retry_policy, deadline):
    """The search's device dispatch: :func:`_entry` at the
    ``distributed.ann.search`` site, inside the host span
    ``raft_tpu:distributed.dispatch``."""
    with _annotation("distributed.dispatch"):
        return _entry("distributed.ann.search", fn, retry_policy, deadline)


def _degraded_set(n_shards: int, failed_shards: Sequence[int]
                  ) -> Tuple[int, ...]:
    """Union of caller-flagged shards and the active fault plan's
    ``fail_shards``, clipped to range and sorted (a static jit key)."""
    flagged = {int(s) for s in failed_shards if 0 <= int(s) < n_shards}
    return tuple(sorted(flagged | set(faults.failed_shards(n_shards))))


def _status_vector(n_shards: int, failed: Tuple[int, ...],
                   lowered: bool,
                   replica_served: Tuple[int, ...] = ()) -> jax.Array:
    """(n_shards,) int8 per-shard status: failed shards report
    :data:`SHARD_FAILED`; shards whose owned lists replicas covered
    (failover or a hedged read) report :data:`SHARD_REPLICA_SERVED`;
    live shards report :data:`SHARD_OK_FALLBACK` when the requested scan
    mode was lowered, else :data:`SHARD_OK`."""
    status = np.full(n_shards,
                     SHARD_OK_FALLBACK if lowered else SHARD_OK, np.int8)
    status[list(failed)] = SHARD_FAILED
    status[list(replica_served)] = SHARD_REPLICA_SERVED
    return jnp.asarray(status)


def _note_lowered(mode: str) -> None:
    from raft_tpu import observability as obs
    if obs.enabled():
        obs.registry().counter("distributed.ann.scan_mode_lowered").inc()
    rec = _rtrace.current()
    _flight.record_event("distributed.scan_mode_lowered",
                         trace_id=rec.trace_id if rec else None,
                         requested=mode)


def _note_routed_groups(sizes, needed, failed) -> None:
    """Tick ``distributed.routed.groups_dispatched`` / ``.groups_skipped``
    for routed fused dispatches at the group counts ``sizes``, summed
    over the live shards from the gathered per-shard ``needed`` groups:
    each shard's fused scan skips the steps past its ``min(needed, n)``
    live groups.  Reads ``needed`` only while collection is on."""
    from raft_tpu import observability as obs
    if not obs.enabled():
        return
    live = np.delete(np.asarray(needed), list(failed))
    reg = obs.registry()
    for n in sizes:
        reg.counter("distributed.routed.groups_dispatched").inc(
            n * live.size)
        reg.counter("distributed.routed.groups_skipped").inc(
            int(np.sum(n - np.minimum(live, n))))


def _plan(params, index, nq: int, n_probes: int, k: int) -> ivf_pq.ScanPlan:
    """The scan plan of one sharded search: :func:`ivf_pq.plan_scan`
    over the facts of the index's placement, all host-static (shapes,
    flags, the calibrated estimate, the id-exactness verdict cached at
    shard time), so the jitted dispatch carries the plan as static
    arguments and the request path does no device sync.  A lowered
    plan is noted here (``distributed.ann.scan_mode_lowered``)."""
    on_tpu = _platform.on_tpu()
    ids_exact = on_tpu and grouped.ids_f32_exact(index, index.list_indices)
    if isinstance(index, RoutedIndex):
        books = index.codebooks
        facts = dict(
            placement="by_list", lists=index.local_centers.shape[1],
            cap=index.capacity, rot=index.rotation.shape[1],
            pq_dim=books.shape[0] if books is not None else 0,
            per_subspace=books is not None,
            code_lanes=(index.list_code_lanes is not None
                        and index.list_code_rsq is not None),
            group_est=index.group_est)
    else:
        facts = dict(placement="by_row", lists=index.centers.shape[1],
                     cap=index.list_recon.shape[2],
                     rot=index.rotation.shape[2])
    plan = ivf_pq.plan_scan(params, metric=index.metric,
                            pq_bits=int(index.pq_bits), nq=nq, k=k,
                            n_probes=n_probes, on_tpu=on_tpu,
                            ids_exact=ids_exact, **facts)
    if plan.lowered:
        _note_lowered(getattr(params, "scan_mode", "auto"))
    return plan


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DistributedIndex:
    """Leaf-stacked local IVF-PQ indexes: every leaf carries a leading
    mesh-axis dimension (n_dev, ...) sharded one shard per device."""

    centers: jax.Array        # (n_dev, n_lists, rot_dim)
    codebooks: jax.Array
    list_codes: jax.Array     # (n_dev, n_lists, cap, pq_dim)
    list_indices: jax.Array   # (n_dev, n_lists, cap) — GLOBAL ids
    list_sizes: jax.Array
    rotation: jax.Array       # (n_dev, dim, rot_dim)
    list_recon: jax.Array     # (n_dev, n_lists, cap, rot_dim) bf16
    metric: int = DistanceType.L2Expanded
    size: int = 0
    # static PQ metadata (round 10): lets the sharded search run the
    # traceable LUT formulation for codes/lut scan modes instead of
    # lowering to probe-order recon.  Zero on legacy stacked pytrees,
    # which keep the pre-round-10 fallback.
    pq_bits: int = 0
    codebook_kind: int = 0
    # per-shard recall canaries (tuple of integrity.CanarySet / None) —
    # host-side metadata, NOT a pytree leaf, so jax transforms drop it;
    # build / health_check carry it explicitly
    shard_canaries: Optional[tuple] = None

    @property
    def n_shards(self) -> int:
        return self.centers.shape[0]

    def tree_flatten(self):
        return ((self.centers, self.codebooks, self.list_codes,
                 self.list_indices, self.list_sizes, self.rotation,
                 self.list_recon),
                (self.metric, self.size, self.pq_bits, self.codebook_kind))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        # aux may be the legacy (metric, size) pair — callers that
        # round-trip flatten/unflatten through stored aux keep working
        return cls(*leaves, metric=aux[0], size=aux[1],
                   pq_bits=aux[2] if len(aux) > 2 else 0,
                   codebook_kind=aux[3] if len(aux) > 3 else 0)


def build(handle, params: ivf_pq.IndexParams, dataset, *,
          placement: str = "by_row", replication_factor: int = 1,
          retry_policy: Optional[_retry.RetryPolicy] = None,
          deadline: Optional[_retry.Deadline] = None):
    """Build a sharded IVF-PQ index over the handle's mesh.

    ``placement="by_row"`` (default): rows are split across shards and
    each shard trains its own local index (ids globally offset);
    ``params.n_lists`` is per shard.  PER_SUBSPACE builds run as ONE
    two-phase ``shard_map`` — every shard's k-means, codebook training
    and encoding execute SPMD across the mesh simultaneously, with a
    single tiny host sync (the global max list size) between encoding
    and list packing.  The round-3 host loop built shards one after
    another — 8x the build latency on a v5e-8 for no reason (VERDICT
    r3).  Other codebook kinds and mesocluster-scale n_lists fall back
    to the sequential per-shard loop.

    ``placement="by_list"``: ONE global index is trained (so
    ``params.n_lists`` is GLOBAL) and its lists are partitioned across
    shards balanced by list size — returns a :class:`RoutedIndex` whose
    search routes probes to owning shards (see module docstring).
    ``replication_factor=r > 1`` (by_list only) places ``r`` copies of
    every list on distinct shards for recall-preserving shard failover
    (see :func:`compute_placement`).

    Transient faults at entry (site ``distributed.ann.build``) are
    retried under ``retry_policy`` / ``deadline``.
    """
    expects(placement in ("by_row", "by_list"),
            f"distributed.ann.build: placement must be 'by_row' or "
            f"'by_list', got {placement!r}")
    expects(replication_factor == 1 or placement == "by_list",
            "distributed.ann.build: replication_factor > 1 requires "
            "placement='by_list' (by_row is already fully replicated "
            "per shard's rows)")
    if placement == "by_list":
        return _entry("distributed.ann.build",
                      lambda: _build_by_list(
                          handle, params, dataset,
                          replication_factor=replication_factor),
                      retry_policy, deadline)
    return _entry("distributed.ann.build",
                  lambda: _build_impl(handle, params, dataset),
                  retry_policy, deadline)


def _build_impl(handle, params: ivf_pq.IndexParams,
                dataset) -> DistributedIndex:
    with named_range("distributed::ivf_pq_build"):
        expects(handle.comms_initialized(),
                "distributed.ann.build: handle has no comms (use "
                "CommsSession.worker_handle())")
        dataset = ensure_array(dataset, "dataset")
        comms, mesh, axis, n, n_dev, per, devs = _shard_layout(
            handle, dataset)
        expects(params.cache_reconstructions,
                "distributed.ann: the sharded search kernel runs the "
                "reconstruction path; cache_reconstructions must be True")

        from raft_tpu.cluster import kmeans_balanced as kb

        if (params.codebook_kind == ivf_pq.CodebookKind.PER_SUBSPACE
                and params.n_lists < kb._MESO_THRESHOLD
                and params.n_lists <= per
                and params.add_data_on_build
                # canaries need per-shard exact ground truth, which only
                # the sequential per-shard build computes
                and params.canary_queries == 0):
            return _build_spmd(handle, params, dataset, mesh, axis, n,
                               n_dev, per)

        locals_ = []
        for s in range(n_dev):
            shard = dataset[s * per:(s + 1) * per]
            idx = ivf_pq.build(handle, params, shard)
            # globalize ids: local slot ids are 0..per-1 over the shard
            idx.list_indices = jnp.where(
                idx.list_indices >= 0, idx.list_indices + s * per, -1)
            locals_.append(idx)

        cap = max(ix.capacity for ix in locals_)

        def pad_cap(a, fill):
            return jnp.pad(a, ((0, 0), (0, cap - a.shape[1]))
                           + ((0, 0),) * (a.ndim - 2),
                           constant_values=fill)

        per_shard_leaves = [
            (ix.centers, ix.codebooks, pad_cap(ix.list_codes, 0),
             pad_cap(ix.list_indices, -1), ix.list_sizes, ix.rotation,
             pad_cap(ix.list_recon, 0))
            for ix in locals_]

        placed = _stack_leaves(per_shard_leaves, mesh, axis, devs)
        out = DistributedIndex.tree_unflatten(
            (params.metric, n, int(locals_[0].pq_bits),
             int(locals_[0].codebook_kind)), tuple(placed))
        out.shard_canaries = _collect_canaries(locals_, per,
                                               offset_ids=True)
        return out


def _stack_leaves(per_shard_leaves, mesh, axis, devs):
    """Assemble (n_dev, ...) stacked leaves from per-device shards —
    never materializing the full stack on one device, whose HBM the
    full index may not fit (the regime MNMG sharding exists for)."""
    n_dev = len(per_shard_leaves)
    placed = []
    for li in range(len(per_shard_leaves[0])):
        shards = [jax.device_put(per_shard_leaves[s][li][None],
                                 devs[s]) for s in range(n_dev)]
        shape = (n_dev,) + per_shard_leaves[0][li].shape
        # graftlint: disable=recompile-hazard -- len() is the static
        sharding = jax.sharding.NamedSharding(  # leaf rank at build time
            mesh, P(axis, *([None] * (len(shape) - 1))))
        placed.append(jax.make_array_from_single_device_arrays(
            shape, sharding, shards))
    return placed


def _build_spmd(handle, params: ivf_pq.IndexParams, dataset, mesh, axis,
                n, n_dev, per) -> DistributedIndex:
    """Two-phase SPMD build (see :func:`build`).

    Phase A (per shard, no collectives): coarse balanced k-means,
    per-subspace codebooks, encode + bit-pack, per-list counts.
    Host: one (n_dev, n_lists) readback picks the global static list
    capacity.  Phase B: pack lists + decode the bf16 recon cache.
    """
    from raft_tpu.cluster import kmeans_balanced as kb
    from raft_tpu.neighbors.ivf_flat import _LIST_ALIGN, _pack_lists

    dim = dataset.shape[1]
    pq_dim = params.pq_dim or max(dim // 4, 1)
    rot_dim = ivf_pq._round_up(dim, pq_dim)
    rotation = ivf_pq._make_rotation(
        dim, rot_dim, params.force_random_rotation or rot_dim != dim,
        seed=7)
    n_train = min(per, max(params.n_lists,
                           int(per * params.kmeans_trainset_fraction)))
    n_lists = params.n_lists
    book = 1 << params.pq_bits
    base_key = handle.next_key()

    def spec(ndim):
        return P(axis, *([None] * (ndim - 1)))

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(axis), P()),
        out_specs=(spec(3), spec(4), spec(3), spec(2), spec(2)),
        check_vma=False)
    def phase_a(shard, rot):
        s = jax.lax.axis_index(axis)
        k1, k2 = jax.random.split(jax.random.fold_in(base_key, s))
        xf = shard.astype(jnp.float32) @ rot
        stride_t = max(per // n_train, 1)
        train = xf[::stride_t][:n_train]
        stride_c = max(n_train // n_lists, 1)
        c0 = train[::stride_c][:n_lists]
        centers, labels_t = kb._balanced_loop(
            train, c0, k1, n_lists, params.kmeans_n_iters, params.metric)
        resid_t = ivf_pq._subspace_split(train - centers[labels_t], pq_dim)
        books = ivf_pq._train_books_per_subspace(
            jnp.transpose(resid_t, (1, 0, 2)), jax.random.split(k2, pq_dim),
            book, params.kmeans_n_iters)
        labels, _ = kb._assign(xf, centers, params.metric)
        resid = ivf_pq._subspace_split(xf - centers[labels], pq_dim)
        codes = ivf_pq._pack_codes(
            ivf_pq._encode(books, resid, params.codebook_kind, labels),
            params.pq_bits)
        sizes = jax.ops.segment_sum(jnp.ones(per, jnp.int32), labels,
                                    num_segments=n_lists)
        return (centers[None], books[None], codes[None], labels[None],
                sizes[None])

    centers_a, books_a, codes_a, labels_a, sizes_a = phase_a(
        dataset, rotation)

    # the ONE host sync: global static list capacity
    capacity = ivf_pq._round_up(
        max(int(jnp.max(sizes_a)), _LIST_ALIGN), _LIST_ALIGN)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec(3), spec(4), spec(3), spec(2)),
        out_specs=(spec(4), spec(3), spec(2), spec(4)),
        check_vma=False)
    def phase_b(centers, books, codes, labels):
        s = jax.lax.axis_index(axis)
        gids = (s * per + jnp.arange(per)).astype(jnp.int32)
        lc, li, sz = _pack_lists(codes[0], labels[0], gids, n_lists,
                                 capacity)
        recon = ivf_pq._decode_lists(centers[0], books[0], lc,
                                     params.codebook_kind, pq_dim,
                                     params.pq_bits)
        return lc[None], li[None], sz[None], recon[None]

    list_codes, list_indices, list_sizes, list_recon = phase_b(
        centers_a, books_a, codes_a, labels_a)

    rot_stack = jax.device_put(
        jnp.broadcast_to(rotation[None], (n_dev,) + rotation.shape),
        jax.sharding.NamedSharding(mesh, P(axis, None, None)))
    return DistributedIndex.tree_unflatten(
        (params.metric, n, int(params.pq_bits),
         int(params.codebook_kind)),
        (centers_a, books_a, list_codes, list_indices, list_sizes,
         rot_stack, list_recon))


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "metric",
                                             "axis_name", "mesh", "failed"))
def _dist_search(index_leaves, queries, k, n_probes, metric, axis_name,
                 mesh, failed=(), filter_words=None):
    # only the leaves the recon search kernel consumes are threaded through
    specs = tuple(P(axis_name, *([None] * (leaf.ndim - 1)))
                  for leaf in index_leaves)
    # filtered search (round 20): the bitset addresses GLOBAL row ids —
    # exactly what every shard's list_indices store — so one replicated
    # (q, n_words) buffer serves all shards unsliced.  Presence is part
    # of the trace signature: the unfiltered graph is unchanged.
    has_f = filter_words is not None
    in_specs = (specs, P()) + ((P(),) if has_f else ())
    out_specs = (P(), P()) + ((P(),) if has_f else ())

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    def run(leaves, q, *rest):
        centers, list_indices, rotation, list_recon = leaves
        ld, li = ivf_pq._search_impl_recon(
            centers[0], list_recon[0], list_indices[0], rotation[0], q,
            k, n_probes, metric,
            filter_words=rest[0] if has_f else None)
        select_min = metric != DistanceType.InnerProduct
        if failed:
            # degraded mode: a failed shard contributes only sentinel
            # candidates, so the replicated merge ranks every live
            # shard's hits first and pads the tail with id -1.  `failed`
            # is a static jit key — the no-fault compiled path is
            # byte-identical to before this feature existed.
            s = jax.lax.axis_index(axis_name)
            bad = jnp.any(jnp.asarray(failed, jnp.int32) == s)
            sentinel = jnp.inf if select_min else -jnp.inf
            ld = jnp.where(bad, jnp.full_like(ld, sentinel), ld)
            li = jnp.where(bad, jnp.full_like(li, -1), li)
        all_d = jax.lax.all_gather(ld, axis_name)   # (n_dev, q, k)
        all_i = jax.lax.all_gather(li, axis_name)
        nq = q.shape[0]
        md, mi = select_k(
            jnp.transpose(all_d, (1, 0, 2)).reshape(nq, -1), k,
            in_idx=jnp.transpose(all_i, (1, 0, 2)).reshape(nq, -1),
            select_min=select_min)
        if has_f:
            # per-shard admitted-row counter: candidates this shard
            # contributed to the exchange after the admission fold
            # (starved slots are already id -1)
            admitted = jax.lax.all_gather(
                jnp.sum((li >= 0).astype(jnp.int32)), axis_name)
            return md, mi, admitted
        return md, mi

    args = (index_leaves, queries) + ((filter_words,) if has_f else ())
    return run(*args)


def _recon_sq_stack(index: DistributedIndex) -> jax.Array:
    """Stacked (n_dev, n_lists, cap) recon row norms, computed once and
    cached on the index object (the stacked pytree has no recon_sq leaf;
    the grouped scan's distance decomposition needs it)."""
    rsq = getattr(index, "_list_recon_sq_stack", None)
    if rsq is None:
        rsq = ivf_pq._recon_sq(index.list_recon)
        object.__setattr__(index, "_list_recon_sq_stack", rsq)
    return rsq


def _merge_gathered(ld, li, q, k, metric, axis_name, failed):
    """Shared shard_map epilogue: degraded-shard masking, the k-bounded
    all_gather, and the replicated merge-select (see :func:`_dist_search`
    for the exactness argument)."""
    select_min = metric != DistanceType.InnerProduct
    if failed:
        s = jax.lax.axis_index(axis_name)
        bad = jnp.any(jnp.asarray(failed, jnp.int32) == s)
        sentinel = jnp.inf if select_min else -jnp.inf
        ld = jnp.where(bad, jnp.full_like(ld, sentinel), ld)
        li = jnp.where(bad, jnp.full_like(li, -1), li)
    all_d = jax.lax.all_gather(ld, axis_name)   # (n_dev, q, k)
    all_i = jax.lax.all_gather(li, axis_name)
    nq = q.shape[0]
    # sqrt=False: the shard-local epilogue already applied it for the
    # sqrt metrics, and the merge is monotone
    return grouped.finalize_topk(
        jnp.transpose(all_d, (1, 0, 2)), jnp.transpose(all_i, (1, 0, 2)),
        nq, k, select_min, False, select_k)


def _merge_refined(rows, row_pos, q, ld, li, k, metric, axis_name):
    """The refined candidate exchange, after a shard's scan at k*r:

    1. every shard's top-(k*r) scan candidates ``(ld, li)`` are
       all_gathered and the replicated merge keeps the global top-(k*r)
       by the scan's distance — the set ``ivf_pq.search`` at k*r returns
       on one chip, whichever replica served each list;
    2. the shard that scanned a kept candidate computes its exact f32
       distance against the raw rows it holds (``rows``, ``row_pos``),
       with the arithmetic of :func:`raft_tpu.neighbors.refine.refine`;
    3. one psum assembles the exact distances (every other shard adds
       zero) and the replicated exact top-k is returned.

    Every shard re-ranks the same (q, k*r) layout, so the answer is
    bit-identical under any assignment of lists to replicas; a shard's
    own exact top-k would make it depend on which replica scanned which
    list."""
    nq, kr = ld.shape
    select_min = metric != DistanceType.InnerProduct

    def gathered(x):                     # (n_dev, q, kr) -> (q, n_dev*kr)
        return jnp.transpose(jax.lax.all_gather(x, axis_name),
                             (1, 0, 2)).reshape(nq, -1)
    all_d, all_i = gathered(ld), gathered(li)
    _, pos = select_k(all_d, kr, select_min=select_min)
    cand_d = jnp.take_along_axis(all_d, pos, axis=1)
    cand_i = jnp.take_along_axis(all_i, pos, axis=1)
    mine = pos // kr == jax.lax.axis_index(axis_name)
    # only a slot the scan filled (a real id at a finite distance) is
    # re-ranked: exhausted, tombstoned and filter-rejected slots stay at
    # the worst value
    at = row_pos[jnp.where(mine & (cand_i >= 0), cand_i, 0)]
    valid = (cand_i >= 0) & jnp.isfinite(cand_d) & (at >= 0)
    flat = rows.reshape(-1, rows.shape[-1])
    exact = _exact_distances(q, flat[jnp.maximum(at, 0)], valid, metric)
    exact = jax.lax.psum(jnp.where(mine, exact, 0.0), axis_name)
    return grouped.finalize_topk(exact, cand_i, nq, k, select_min, False,
                                 select_k)


@functools.partial(jax.jit, static_argnames=(
    "k", "kt", "n_probes", "metric", "axis_name", "mesh", "n_groups",
    "form", "use_pallas", "failed"))
def _dist_search_grouped(index_leaves, queries, k, kt, n_probes, metric,
                         axis_name, mesh, n_groups, form,
                         use_pallas=False, failed=(), filter_words=None):
    """Data-parallel grouped/fused scan under ``shard_map`` (round 10):
    every shard runs the SAME formulation ladder the single-index search
    picks, at the worst-case static group capacity — the capacity is a
    pure function of (nq, n_probes, n_lists), so overflow is impossible
    and this jitted function carries no overflow plumbing at all."""
    specs = tuple(P(axis_name, *([None] * (leaf.ndim - 1)))
                  for leaf in index_leaves)
    has_f = filter_words is not None
    in_specs = (specs, P()) + ((P(),) if has_f else ())
    out_specs = (P(), P()) + ((P(),) if has_f else ())

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    def run(leaves, q, *rest):
        centers, list_recon, list_recon_sq, list_indices, rotation = leaves
        fw = rest[0] if has_f else None
        probes = ivf_pq._select_clusters(centers[0], rotation[0], q,
                                         n_probes, metric)
        cap, rot = list_recon.shape[2], list_recon.shape[3]
        if form == "fused_recon":
            ld, li = ivf_pq._search_impl_fused_recon_grouped(
                centers[0], list_recon[0], list_recon_sq[0],
                list_indices[0], rotation[0], q, probes, k, kt, metric,
                n_groups, filter_words=fw)
        else:
            G = grouped.GROUP
            block = grouped.block_size(n_groups, G * cap * 8,
                                       cap * rot * 2, G * rot * 4)
            ld, li = ivf_pq._search_impl_recon_grouped(
                centers[0], list_recon[0], list_recon_sq[0],
                list_indices[0], rotation[0], q, probes, k, metric,
                n_groups, block, use_pallas=use_pallas, kt=kt,
                filter_words=fw)
        md, mi = _merge_gathered(ld, li, q, k, metric, axis_name, failed)
        if has_f:
            admitted = jax.lax.all_gather(
                jnp.sum((li >= 0).astype(jnp.int32)), axis_name)
            return md, mi, admitted
        return md, mi

    args = (index_leaves, queries) + ((filter_words,) if has_f else ())
    return run(*args)


@functools.partial(jax.jit, static_argnames=(
    "k", "n_probes", "metric", "codebook_kind", "lut_dtype", "pq_bits",
    "axis_name", "mesh", "failed"))
def _dist_search_lut(index_leaves, queries, k, n_probes, metric,
                     codebook_kind, lut_dtype, pq_bits, axis_name, mesh,
                     failed=(), filter_words=None):
    """Data-parallel LUT scan under ``shard_map``: the traceable LUT
    formulation computes the same quantized distance the codes kernel
    streams, so a ``codes``/``lut`` request answers with code-domain
    distances instead of lowering to the recon scan."""
    specs = tuple(P(axis_name, *([None] * (leaf.ndim - 1)))
                  for leaf in index_leaves)
    has_f = filter_words is not None
    in_specs = (specs, P()) + ((P(),) if has_f else ())
    out_specs = (P(), P()) + ((P(),) if has_f else ())

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    def run(leaves, q, *rest):
        centers, codebooks, list_codes, list_indices, rotation = leaves
        ld, li = ivf_pq._search_impl(
            centers[0], codebooks[0], list_codes[0], list_indices[0],
            rotation[0], q, k, n_probes, metric, codebook_kind,
            lut_dtype, pq_bits=pq_bits,
            filter_words=rest[0] if has_f else None)
        md, mi = _merge_gathered(ld, li, q, k, metric, axis_name, failed)
        if has_f:
            admitted = jax.lax.all_gather(
                jnp.sum((li >= 0).astype(jnp.int32)), axis_name)
            return md, mi, admitted
        return md, mi

    args = (index_leaves, queries) + ((filter_words,) if has_f else ())
    return run(*args)


def ground_truth_params(index, params=None) -> ivf_pq.SearchParams:
    """The ground-truth operating point for a sharded index — every
    coarse list probed (per shard for the stacked placement, globally
    for ``by_list``), exact coarse ranking, no per-probe candidate
    truncation.  The shadow-replay quality monitor
    (:mod:`raft_tpu.serving.shadow`) searches at this point through the
    SAME placement map as live traffic to estimate live recall.

    ``scan_mode`` is pinned to ``"lut"`` (not ``"auto"``): the fused
    ladder's VMEM gates can refuse at full-probe shapes, and the
    resulting ``ivf_pq.search.fused_fallback`` ticks would pollute the
    drift detector's steady-state-fallback check with the monitor's own
    traffic."""
    routed = isinstance(index, RoutedIndex)
    n_lists = int(index.n_lists if routed else index.centers.shape[1])
    base = params if params is not None else ivf_pq.SearchParams()
    return dataclasses.replace(base, n_probes=n_lists, scan_mode="lut",
                               per_probe_topk=0, exact_coarse=True)


def search(handle, params: ivf_pq.SearchParams, index, queries, k: int, *,
           failed_shards: Sequence[int] = (),
           return_status: bool = False,
           return_stats: bool = False,
           retry_policy: Optional[_retry.RetryPolicy] = None,
           deadline: Optional[_retry.Deadline] = None,
           health=None,
           shard_deadline_s: Optional[float] = None,
           hedge: bool = True,
           routing=None,
           filter=None,
           refine_ratio: int = 1):
    """Sharded search + merge; returns replicated (distances, global ids)
    of shape (q, k).  Accepts both placements: a
    :class:`DistributedIndex` (data-parallel full-shard scan) or a
    :class:`RoutedIndex` (routed-probe scan over owned lists only).

    Degraded mode: shards listed in ``failed_shards`` (or flagged by the
    active fault plan's ``fail_shards``) are masked out of the merge —
    the query still answers with the live shards' top-k, the tail padded
    with ``(inf, -1)`` when fewer than ``k`` live candidates exist.
    Under ``by_list`` a lost shard drops only its *owned* lists — recall
    degrades by roughly the failed shard's probed share instead of a
    full replica vanishing.  With ``return_status=True`` a status output
    is appended: an ``(n_shards,)`` int8 vector of
    :data:`SHARD_FAILED` / :data:`SHARD_OK` / :data:`SHARD_OK_FALLBACK`
    (live, but the requested ``scan_mode`` was lowered — see below).
    With ``return_stats=True`` a host-side dict is appended (after the
    status vector when both are requested) with the per-shard
    ``scanned_rows`` counter, the fixed candidate-exchange
    ``gather_shape``, and the effective ``scan_mode`` — the observability
    surface the placement-balance tripwire asserts on.
    Transient faults at entry (site ``distributed.ann.search``) are
    retried under ``retry_policy`` / ``deadline``.

    ``params.scan_mode`` threading (round 10): group construction is
    traceable at the static capacity
    :func:`raft_tpu.neighbors.grouped.group_capacity`, so the grouped
    and fused scans lower under ``shard_map`` for both placements —
    ``scan_mode="fused"`` (and ``"auto"`` on TPU) runs the same
    formulation ladder the single-index search picks: the fused Pallas
    kernels where the shape/VMEM gates pass, the XLA grouped twin
    elsewhere (a missed kernel gate ticks ``ivf_pq.search.fused_fallback``
    but is NOT a distributed lowering — the status stays
    :data:`SHARD_OK`).  Data-parallel ``codes``/``lut`` requests run the
    traceable LUT formulation (same quantized distance) when the stacked
    pytree carries PQ metadata.  Only the genuinely unsupported
    combinations lower to the probe-order recon scan — the code modes on
    a routed index (its shards hold no packed codes) or on a legacy
    stacked pytree — and those report :data:`SHARD_OK_FALLBACK` plus the
    ``distributed.ann.scan_mode_lowered`` counter.
    :func:`raft_tpu.neighbors.ivf_pq.plan_scan` makes the choice.

    Routed fused dispatch is sync-free: an uncalibrated index runs at
    the exact-safe worst-case capacity (zero host reads); a calibrated
    index (``group_est`` from
    :func:`raft_tpu.neighbors.ivf_pq.calibrate_group_capacity`, carried
    through :func:`shard_by_list`) dispatches at the tightened capacity
    and the per-shard true group counts ride the candidate all_gather —
    only a batch whose probe skew exceeds the calibrated bound pays the
    one host read plus an exact re-dispatch at the worst bound, counted
    by ``ivf_pq.search.group_overflow``.

    Replication (the routed path only, ``replication_factor > 1``): a
    down shard's lists fail over to their replicas *before* dispatch —
    host-side, the effective routing tables swap each affected list to
    its lowest-rank live owner, so the device program sees the same
    shapes (replica choice is data, not shape: zero recompiles) and the
    merge pulls the lost lists from shards that scan the identical rows,
    keeping full-probe results **bit-identical** to the healthy run.
    Fully-covered shards report :data:`SHARD_REPLICA_SERVED`; only
    shards with uncovered lists stay :data:`SHARD_FAILED` (the
    ``distributed.degraded_search`` event fires for those alone, and the
    residual set is the only thing passed as a static jit arg — a fully
    covered failover reuses the warmed healthy executable).

    ``health`` (a :class:`raft_tpu.distributed.health.HealthTracker`)
    contributes its FAILED shards to the down set and receives straggle
    / deadline-overrun signals.  ``shard_deadline_s`` (satellite of the
    straggler model: a float budget or a :class:`resilience.Deadline`)
    bounds the wait on any one shard — an overrun emits a
    ``distributed.shard_timeout`` flight event, notes a timeout with the
    tracker, and (with replicas available and ``hedge=True``) converts
    the unbounded wait into a **hedged read**: the straggler's probe
    subset is re-issued to a replica and the first answer taken — exact,
    because both scan identical lists.  A hedged shard's injected delay
    is not paid beyond the deadline; with no covering replica the shard
    is un-hedged and waited for in full (slow beats dropped).

    ``routing`` (a :class:`raft_tpu.distributed.routing.RoutingPolicy`)
    turns the replicas into a throughput lever on the HEALTHY path:
    every batch's effective tables come from
    :meth:`~raft_tpu.distributed.routing.RoutingPolicy.plan` — greedy
    least-loaded replica-rank selection over the per-shard load scores
    — instead of the fixed rank-0 primaries, and a hedge re-issues to
    the least-loaded covering replica rather than the lowest rank.
    Exactness is unchanged (any live assignment is bit-identical at
    full probe: the k-bounded merge argument is per list, and replica
    copies are identical rows), the tables stay data-not-shape (zero
    recompiles), and each decision lands a
    ``distributed.replica_choice`` flight event.  The routed dispatch
    also hands the policy each batch's in-graph per-list probe
    histogram (``observe_probes`` — a lazy device array, no host sync)
    for probe-frequency-aware rebalancing.

    ``filter`` (round 20): a :class:`raft_tpu.filters.SampleFilter` (or
    a ``(q, n_rows)`` bool mask) over GLOBAL row ids.  The packed
    ``(q, n_words)`` bitset is broadcast replicated alongside the
    queries — shards consume it unsliced because their ``list_indices``
    store global ids, so the admission fold commutes with both
    placements, replica failover, and hedging (replica copies scan
    identical rows).  Filtered full-probe results are bit-identical to
    a post-hoc-filtered exact scan; starved slots pad with ``(inf,
    -1)``.  The filter is data, not shape: varying filters reuse the
    warmed executable, and presence/absence is a separate trace.  Each
    shard's admitted-candidate count rides the existing gather — with
    ``return_stats=True`` the stats dict gains ``admitted_rows``, and
    the lazy per-shard vector is annotated on the ambient trace as
    ``distributed.admitted_rows``.

    ``refine_ratio=r > 1`` (a routed index that carries raw rows: built
    from a dataset, or :func:`shard_by_list` with ``dataset=``) re-ranks
    on the owning shard: every shard scans at ``k * r``, the replicated
    merge keeps the global top-``k * r`` by the scan's distance, the
    shard that scanned each kept candidate computes its exact f32
    distance against the rows it holds (the arithmetic of
    :func:`raft_tpu.neighbors.refine.refine`), and the exact top-``k``
    is returned — what ``ivf_pq.search`` at ``k * r`` followed by
    ``refine`` to ``k`` returns on one chip (:func:`_merge_refined`).
    The candidate set does not depend on which replica serves a list, so
    failover, hedging and routing tables stay bit-identical.  Each such
    search ticks ``distributed.routed.refined_rows`` by ``nq * k * r``
    while collection is on.

    Host spans (``core.tracing.annotation``, always on):
    ``raft_tpu:distributed.route`` covers failover, hedging, the routing
    policy's plan and the effective tables' upload;
    ``raft_tpu:distributed.dispatch`` the ``shard_map`` call.
    """
    with named_range("distributed::ivf_pq_search"):
        expects(handle.comms_initialized(),
                "distributed.ann.search: handle has no comms")
        comms = handle.get_comms()
        queries = ensure_array(queries, "queries")
        # lifecycle-boundary kill site: a shard killed here is seen by
        # THIS search's failed-set computation (killed during routing)
        faults.maybe_fail("distributed.route")
        failed = _degraded_set(index.n_shards, failed_shards)
        if health is not None:
            failed = tuple(sorted(
                set(failed) | set(health.failed_shards())))
        nq = int(queries.shape[0])
        k = int(k)
        fw = _fbits.query_filter_words(filter, nq, "distributed.ann.search")
        routed = isinstance(index, RoutedIndex)
        refine_ratio = int(refine_ratio)
        expects(refine_ratio >= 1,
                "distributed.ann.search: refine_ratio must be >= 1")
        refine_to = k if refine_ratio > 1 else 0
        if refine_to:
            expects(routed, "distributed.ann.search: refine_ratio > 1 "
                    "needs a routed (placement='by_list') index")
            expects(index.list_rows is not None,
                    "distributed.ann.search: refine_ratio > 1 needs the "
                    "raw rows on the shards (build by_list from a "
                    "dataset, or shard_by_list(..., dataset=))")
        # the shards scan at k * r when they re-rank
        k_scan = k * refine_ratio
        rec = _rtrace.current()
        rf = (index.placement.replication_factor
              if routed and index.placement is not None else 1)
        if isinstance(shard_deadline_s, _retry.Deadline):
            shard_deadline_s = shard_deadline_s.remaining()
        expects(shard_deadline_s is None or shard_deadline_s > 0,
                "distributed.ann.search: shard_deadline_s must be > 0")
        # per-shard straggler injection (host-side, before dispatch):
        # the SPMD merge completes when the slowest shard answers.
        # Probe the scripted schedule WITHOUT sleeping first — the
        # straggler detector — so hedging can collapse a flagged
        # shard's wait before it is paid.
        delays = faults.straggler_delays(index.n_shards)
        flagged = tuple(s for s, dly in enumerate(delays) if dly > 0.0)
        if delays:
            _flight.record_event("distributed.straggler",
                                 trace_id=rec.trace_id if rec else None,
                                 delays_s=list(delays),
                                 n_shards=index.n_shards)
        timeouts = ()
        if flagged and shard_deadline_s is not None:
            timeouts = tuple(s for s in flagged
                             if delays[s] > shard_deadline_s)
            for s in timeouts:
                _flight.record_event("distributed.shard_timeout",
                                     trace_id=rec.trace_id if rec else None,
                                     shard=s, delay_s=delays[s],
                                     deadline_s=shard_deadline_s)
                if health is not None:
                    health.note_timeout(s)
        if health is not None:
            for s in flagged:
                health.note_straggle(s)
        # -- replica failover + hedging (host-side, data not shape) ----
        with _annotation("distributed.route"):
            hedge_cand = set()
            if hedge and routed and rf > 1:
                hedge_cand = set(flagged) - set(failed)
                if health is not None:
                    hedge_cand |= set(health.suspect_shards()) - set(failed)
            hedged: Tuple[int, ...] = ()
            residual = failed
            replica_served: Tuple[int, ...] = ()
            eff = None  # (eff_owner, eff_slot) host numpy, or None
            # load-aware policy: plan() honors the same keep-primary-when-
            # uncovered contract as healthy_routing, so the residual /
            # covered bookkeeping below composes with either table source
            use_policy = routing is not None and routed and rf > 1

            def _route_tables(d):
                if use_policy:
                    return routing.plan(index.placement, down=d)
                return index.placement.healthy_routing(d)

            if routed and rf > 1 and (failed or hedge_cand or use_policy):
                down = set(failed) | hedge_cand
                eo, es = _route_tables(tuple(sorted(down)))
                still = down & set(np.unique(eo).tolist())
                # a hedge candidate whose lists have no live replica is
                # UN-hedged: the shard is alive, just slow — wait for it
                # rather than drop its lists
                unhedged = hedge_cand & still
                hedged = tuple(sorted(hedge_cand - unhedged))
                down = set(failed) | set(hedged)
                if unhedged and down:
                    eo, es = _route_tables(tuple(sorted(down)))
                if down:
                    still = down & set(np.unique(eo).tolist())
                    residual = tuple(sorted(set(failed) & still))
                    replica_served = tuple(sorted(down - still))
                    eff = (eo, es)
                elif use_policy:
                    # pure load spreading: nothing down, every list served
                    # by its least-loaded live rank
                    eff = (eo, es)
                if use_policy:
                    reason = ("failover" if failed
                              else "hedge" if hedged else "load_spread")
                    choice = routing.choice_summary()
                    _flight.record_event(
                        "distributed.replica_choice",
                        trace_id=rec.trace_id if rec else None,
                        reason=reason,
                        scores=choice.get("scores"),
                        per_rank_lists=choice.get("per_rank_lists"),
                        per_shard_lists=choice.get("per_shard_lists"))
                    from raft_tpu import observability as obs
                    if obs.enabled():
                        obs.registry().counter(
                            "distributed.replica_choice").inc()
                if failed and set(failed) - set(residual):
                    _flight.record_event(
                        "distributed.replica_failover",
                        trace_id=rec.trace_id if rec else None,
                        failed=list(failed), residual=list(residual),
                        covered=sorted(set(failed) - set(residual)))
                for s in hedged:
                    _flight.record_event(
                        "distributed.hedged_read",
                        trace_id=rec.trace_id if rec else None,
                        shard=s,
                        delay_s=delays[s] if s < len(delays) else 0.0)
                if hedged:
                    from raft_tpu import observability as obs
                    if obs.enabled():
                        obs.registry().counter(
                            "distributed.hedged_reads").inc(len(hedged))
            # effective routing tables: same shape as the healthy tables
            # (replica choice is data, not shape — no recompile)
            eff_tables = None
            if eff is not None:
                eff_tables = (_replicate(jnp.asarray(eff[0]), handle.mesh),
                              _replicate(jnp.asarray(eff[1]), handle.mesh))
        # pay the straggler wait: a hedged shard's wait collapses to the
        # deadline (the replica answered instead); everyone else is
        # waited for in full.  The sleep stays in the resilience layer.
        wait = 0.0
        hedged_set = set(hedged)
        for s, dly in enumerate(delays):
            if dly <= 0.0:
                continue
            if s in hedged_set:
                dly = min(dly, shard_deadline_s or 0.0)
            wait = max(wait, dly)
        faults.pause(wait)
        n_probes = min(params.n_probes,
                       index.n_lists if routed else index.centers.shape[1])
        r = _plan(params, index, nq, n_probes, k_scan)
        # per-request tracing: annotate the ambient recorder (pushed by
        # the serving batcher around its executor call) with the host-
        # static facts of this dispatch.  Everything attached here is
        # already on the host — NO new device->host syncs; the scanned-
        # rows counter below rides along as a lazy device array that only
        # flight.dump() materializes.
        if rec is not None:
            rec.annotate("distributed.scan_mode",
                         {"probe_recon": "recon"}.get(r.form, r.form))
            rec.annotate("distributed.n_probes", int(n_probes))
            # same host values _status_vector encodes, without the
            # device round-trip
            status = np.full(index.n_shards,
                             SHARD_OK_FALLBACK if r.lowered else SHARD_OK,
                             np.int8)
            status[list(residual)] = SHARD_FAILED
            status[list(replica_served)] = SHARD_REPLICA_SERVED
            rec.annotate("distributed.shard_status", status.tolist())
        if residual:
            # only shards with genuinely UNCOVERED lists degrade the
            # answer; a fully covered failover is telemetry, not
            # degradation
            _flight.record_event("distributed.degraded_search",
                                 trace_id=rec.trace_id if rec else None,
                                 failed=list(residual),
                                 n_shards=index.n_shards)
        scanned = None
        phist = None  # per-list probe histogram (routed; lazy device)
        admitted = None  # per-shard admitted-candidate counts (filtered)
        # lifecycle-boundary kill site: a shard killed here (mid-scan)
        # keeps this search's pre-kill routing — its in-flight answer
        # completes — and the NEXT search routes around it
        faults.maybe_fail("distributed.scan")
        if routed:
            if r.form == "probe_recon":
                sharded = (index.local_centers, index.list_recon,
                           index.list_recon_sq, index.list_indices)
                replicated = (index.coarse_centers, index.rotation,
                              index.owner, index.local_slot)
                if eff_tables is not None:
                    replicated = replicated[:2] + eff_tables
                if refine_to:
                    sharded += (index.list_rows, index.row_pos)
                out = _dispatch(
                    lambda: _dist_search_routed(
                        sharded, replicated, queries, k_scan, n_probes,
                        index.metric, comms.axis_name, handle.mesh,
                        failed=residual, filter_words=fw,
                        refine_to=refine_to),
                    retry_policy, deadline)
                if fw is not None:
                    d, i, scanned, phist, admitted = out
                else:
                    d, i, scanned, phist = out
            else:
                sharded, replicated = _routed_leaves(index, r.form)
                if eff_tables is not None:
                    replicated = (replicated[:2] + eff_tables
                                  + replicated[4:])
                if refine_to:
                    sharded += (index.list_rows, index.row_pos)

                def dispatch(ng):
                    out = _dist_search_routed_grouped(
                        sharded, replicated, queries, k_scan, r.kt,
                        n_probes, index.metric, comms.axis_name,
                        handle.mesh, ng, r.form,
                        pq_bits=int(index.pq_bits),
                        use_pallas=r.use_pallas, failed=residual,
                        filter_words=fw, refine_to=refine_to)
                    return out if fw is not None else out + (None,)

                d, i, scanned, needed, phist, admitted = _dispatch(
                    lambda: dispatch(r.n_groups), retry_policy, deadline)
                sizes = [r.n_groups]
                if not r.exact:
                    # calibrated-capacity regime: the ONE deliberate host
                    # read of the routed path, AFTER the dispatch so it
                    # overlaps the scan; almost every batch passes and
                    # pays nothing further
                    # graftlint: disable=host-sync -- overflow re-dispatch gate, not steady-state dispatch
                    if int(jnp.max(needed)) > r.n_groups:
                        from raft_tpu import observability as obs
                        if obs.enabled():
                            obs.registry().counter(
                                "ivf_pq.search.group_overflow").inc()
                        _flight.record_event(
                            "ivf_pq.group_overflow",
                            trace_id=rec.trace_id if rec else None,
                            calibrated_groups=r.n_groups,
                            worst=r.worst_groups)
                        (d, i, scanned, needed, phist,
                         admitted) = dispatch(r.worst_groups)
                        sizes.append(r.worst_groups)
                if r.form in ("fused_codes", "fused_recon"):
                    _note_routed_groups(sizes, needed, residual)
        elif r.form == "probe_recon":
            leaves = (index.centers, index.list_indices, index.rotation,
                      index.list_recon)
            out = _dispatch(
                lambda: _dist_search(leaves, queries, k, n_probes,
                                     index.metric, comms.axis_name,
                                     handle.mesh, failed=residual,
                                     filter_words=fw),
                retry_policy, deadline)
            (d, i, admitted) = out if fw is not None else out + (None,)
        elif r.form == "lut":
            leaves = (index.centers, index.codebooks, index.list_codes,
                      index.list_indices, index.rotation)
            lut_dtype = jnp.dtype(
                getattr(params, "lut_dtype", jnp.float32)).name
            out = _dispatch(
                lambda: _dist_search_lut(
                    leaves, queries, k, n_probes, index.metric,
                    index.codebook_kind, lut_dtype,
                    int(index.pq_bits), comms.axis_name, handle.mesh,
                    failed=residual, filter_words=fw),
                retry_policy, deadline)
            (d, i, admitted) = out if fw is not None else out + (None,)
        else:
            leaves = (index.centers, index.list_recon,
                      _recon_sq_stack(index), index.list_indices,
                      index.rotation)
            out = _dispatch(
                lambda: _dist_search_grouped(
                    leaves, queries, k, r.kt, n_probes, index.metric,
                    comms.axis_name, handle.mesh, r.n_groups, r.form,
                    use_pallas=r.use_pallas, failed=residual,
                    filter_words=fw),
                retry_policy, deadline)
            (d, i, admitted) = out if fw is not None else out + (None,)
        # lifecycle-boundary kill site: post-dispatch, pre-merge-return
        # — a kill here lands after the candidate gather, next search
        # sees the shard down
        faults.maybe_fail("distributed.gather")
        if rec is not None and scanned is not None:
            # lazy attachment: `scanned` is a device array; annotate()
            # stores the reference without fetching it (no host sync on
            # the dispatch path — flight.dump() materializes it later)
            rec.annotate("distributed.scanned_rows", scanned)
        if fw is not None:
            from raft_tpu import observability as obs
            if obs.enabled():
                obs.registry().counter(
                    "distributed.ann.search.filtered").inc()
            if rec is not None and admitted is not None:
                # lazy, like scanned_rows: per-shard admitted-candidate
                # counts ride the existing candidate gather
                rec.annotate("distributed.admitted_rows", admitted)
        if refine_to:
            from raft_tpu import observability as obs
            if obs.enabled():
                obs.registry().counter(
                    "distributed.routed.refined_rows").inc(nq * k_scan)
        if routing is not None and phist is not None:
            # the probe-frequency counters: the policy retains the lazy
            # device histogram; materialization happens only in its
            # maintenance-path refresh() — steady state stays sync-free
            routing.observe_probes(phist)
        out = [d, i]
        if return_status:
            out.append(_status_vector(index.n_shards, residual,
                                      r.lowered, replica_served))
        if return_stats:
            if scanned is None:
                # data-parallel: every live shard scans its whole local
                # index for every probe — n_probes lists of cap rows
                cap = index.list_recon.shape[2]
                per = np.full(index.n_shards, nq * n_probes * cap,
                              np.int64)
                per[list(residual)] = 0
            else:
                # graftlint: disable=host-sync -- opt-in stats readback (return_stats=True), not the serving dispatch
                per = np.asarray(scanned, np.int64)
            gather = (index.n_shards, nq, k_scan)
            stats = {"scanned_rows": per, "gather_shape": gather,
                     "scan_mode": {"probe_recon": "recon"}.get(
                         r.form, r.form),
                     "n_probes": int(n_probes)}
            if admitted is not None:
                # graftlint: disable=host-sync -- opt-in stats readback (return_stats=True), not the serving dispatch
                stats["admitted_rows"] = np.asarray(admitted, np.int64)
            out.append(stats)
        return tuple(out) if len(out) > 2 else (d, i)


def delete(handle, index: DistributedIndex, ids, *,
           retry_policy: Optional[_retry.RetryPolicy] = None,
           deadline: Optional[_retry.Deadline] = None) -> DistributedIndex:
    """Tombstone delete over the sharded index (ids are GLOBAL).

    One sharding-preserving elementwise rewrite of the stacked
    ``list_indices`` leaf — matching slots flip to the tombstone
    encoding (see :mod:`raft_tpu.neighbors.mutate`), which the
    shard-local recon scan already masks (it keeps ``>= 0`` slots only).
    Every other leaf is shared with the parent; the returned snapshot is
    generation-bumped.  Transient faults at entry (site
    ``distributed.ann.delete``) are retried under ``retry_policy`` /
    ``deadline``."""
    return _entry("distributed.ann.delete",
                  lambda: _delete_impl(index, ids), retry_policy, deadline)


def _delete_impl(index, ids):
    with named_range("distributed::ivf_pq_delete"):
        ids = ensure_array(ids, "ids")
        expects(ids.ndim == 1, "distributed.ann.delete: 1-D ids required")
        new_li, _ = _mutate.tombstone(index.list_indices, ids)
        if isinstance(index, RoutedIndex):
            # sharding-preserving elementwise rewrite of the stacked
            # (n_dev, L+1, cap) leaf; placement and canaries carry over
            out = dataclasses.replace(index, list_indices=new_li)
            _mutate.next_generation(index, out)
            return out
        leaves, aux = index.tree_flatten()
        leaves = list(leaves)
        leaves[3] = new_li
        out = DistributedIndex.tree_unflatten(aux, tuple(leaves))
        out.shard_canaries = index.shard_canaries
        _mutate.next_generation(index, out)
        return out


# ---------------------------------------------------------------------------
# Index-parallel sharding (placement="by_list"): routed probes + matched
# candidate gather
# ---------------------------------------------------------------------------

# v2 (round 17): trailing replication block — ``replication_factor``
# plus, when > 1, the per-rank (r, n_lists) owner/slot tables.  v1
# streams read fine and land unreplicated (r=1).
_PLACEMENT_VERSION = 2
_PLACEMENT_MIN_READ_VERSION = 1


@dataclasses.dataclass(frozen=True)
class Placement:
    """List → shard ownership map for ``placement="by_list"`` indexes.

    ``owner[g]`` is the shard owning global IVF list ``g`` (the
    *primary* — replica rank 0); ``local_slot[g]`` is that list's slot
    in the owner's stacked local leaves.  ``n_local`` is the per-shard
    slot count *excluding* the dummy slot (every shard's slot
    ``n_local`` is an always-empty list that unowned probes lower to).
    ``generation`` counts placement recomputations — it keys the
    serving tier's executable cache alongside the index mutation
    generation.

    Replication (round 17): with ``replication_factor=r > 1`` every
    list is owned by ``r`` DISTINCT shards — the primary at rank 0 plus
    ``r-1`` replicas, each rank independently LPT-balanced.  ``owners``
    / ``slots`` are the full ``(r, n_lists)`` rank tables (row 0 equals
    ``owner`` / ``local_slot``); a shard's local leaves hold the union
    of the lists it owns at ANY rank, so failover to a replica is a
    pure routing-table change — replica choice is data, not shape."""

    owner: np.ndarray       # (n_lists,) int32 — rank-0 owners
    local_slot: np.ndarray  # (n_lists,) int32 — rank-0 slots
    n_shards: int
    n_local: int
    generation: int = 0
    replication_factor: int = 1
    owners: Optional[np.ndarray] = None  # (r, n_lists) int32, r > 1 only
    slots: Optional[np.ndarray] = None   # (r, n_lists) int32, r > 1 only

    @property
    def n_lists(self) -> int:
        return int(self.owner.shape[0])

    def rank_tables(self) -> Tuple[np.ndarray, np.ndarray]:
        """The ``(r, n_lists)`` per-rank (owners, slots) tables —
        ``(1, n_lists)`` views of the primary arrays when r=1."""
        if self.owners is None:
            return self.owner[None, :], self.local_slot[None, :]
        return self.owners, self.slots

    def shard_lists(self, s: int,
                    rank: Optional[int] = None) -> np.ndarray:
        """Global list ids materialized on shard ``s``, in local-slot
        order.  Default: the union over every replica rank (the lists
        whose copies live in ``s``'s local leaves — what
        ``_place_lists`` stacks); ``rank=j`` restricts to the lists
        ``s`` owns at that rank (``rank=0`` is the primary set)."""
        owners, slots = self.rank_tables()
        if rank is not None:
            owned = np.nonzero(owners[rank] == s)[0]
            return owned[np.argsort(slots[rank][owned], kind="stable")]
        ranks, lists = np.nonzero(owners == s)
        order = np.argsort(slots[ranks, lists], kind="stable")
        return lists[order]

    def healthy_routing(self, down: Sequence[int]
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Effective (owner, slot) routing tables with every list served
        by its LOWEST-rank owner not in ``down`` — the failover /
        hedging tables.  A list all of whose owners are down keeps its
        rank-0 primary (the degraded-masking path handles it); both
        arrays are host-side numpy, shaped exactly like ``owner`` /
        ``local_slot``, so swapping them into the routed dispatch is a
        data change only (zero recompiles)."""
        owners, slots = self.rank_tables()
        eff_owner = self.owner.copy()
        eff_slot = self.local_slot.copy()
        downset = {int(s) for s in down}
        if not downset or owners.shape[0] == 1:
            return eff_owner, eff_slot
        hit = np.nonzero(np.isin(self.owner, list(downset)))[0]
        for g in hit:
            for r in range(owners.shape[0]):
                if int(owners[r, g]) not in downset:
                    eff_owner[g] = owners[r, g]
                    eff_slot[g] = slots[r, g]
                    break
        return eff_owner, eff_slot


def compute_placement(list_sizes, n_shards: int, *, generation: int = 0,
                      replication_factor: int = 1) -> Placement:
    """Balanced list partition: LPT greedy — lists sorted by (live) size
    descending, each assigned to the least-loaded shard (ties broken by
    fewest lists, then lowest shard id, so the result is deterministic
    and slot counts stay even under uniform sizes).  LPT is a 4/3
    approximation to the optimal makespan, which bounds the worst
    shard's scan work — the property the placement-balance tripwire
    (``(probed_rows / n_shards) * 1.5``) rides on.

    ``replication_factor=r > 1`` runs the SAME greedy once per replica
    rank with an anti-co-location constraint: rank ``j`` skips the
    shards already owning the list at ranks ``< j``, so a list's ``r``
    copies always land on distinct shards and any ``r-1`` shard
    failures leave every list with a healthy owner.  Each rank is
    LPT-balanced against its own load vector; local slots draw from one
    shared per-shard counter, so a shard's leaves hold the union of its
    per-rank owned sets at consecutive slots (memory cost ~``r``×)."""
    sizes = np.asarray(list_sizes, np.int64).reshape(-1)
    n_lists = sizes.shape[0]
    r = int(replication_factor)
    expects(n_shards >= 1, "compute_placement: n_shards must be >= 1")
    expects(n_lists >= n_shards,
            f"compute_placement: need n_lists ({n_lists}) >= n_shards "
            f"({n_shards}) to give every shard at least one list")
    expects(1 <= r <= n_shards,
            f"compute_placement: replication_factor ({r}) must be in "
            f"[1, n_shards={n_shards}] — replicas of a list are never "
            f"co-located, so each list needs {r} distinct shards")
    owners = np.zeros((r, n_lists), np.int32)
    slots = np.zeros((r, n_lists), np.int32)
    load = np.zeros((r, n_shards), np.int64)
    per_rank_count = np.zeros((r, n_shards), np.int64)
    count = np.zeros(n_shards, np.int64)  # shared slot counter
    # stable argsort on -sizes: equal-size lists keep ascending id order
    order = np.argsort(-sizes, kind="stable")
    for rank in range(r):
        for g in order:
            taken = owners[:rank, g]
            for s in np.lexsort((per_rank_count[rank], load[rank])):
                if s not in taken:
                    break
            s = int(s)
            owners[rank, g] = s
            slots[rank, g] = count[s]
            load[rank, s] += int(sizes[g])
            per_rank_count[rank, s] += 1
            count[s] += 1
    return Placement(owner=owners[0], local_slot=slots[0],
                     n_shards=int(n_shards), n_local=int(count.max()),
                     generation=int(generation),
                     replication_factor=r,
                     owners=owners if r > 1 else None,
                     slots=slots if r > 1 else None)


def placement_to_stream(res, stream, placement: Placement) -> None:
    """CRC32-enveloped dump of the placement map (rides inside the
    routed index envelope; also usable standalone)."""
    with ser.enveloped_writer(stream) as body:
        ser.serialize_scalar(res, body, np.int32(_PLACEMENT_VERSION))
        ser.serialize_scalar(res, body, np.int32(placement.n_shards))
        ser.serialize_scalar(res, body, np.int32(placement.n_local))
        ser.serialize_scalar(res, body, np.int64(placement.generation))
        ser.serialize_mdspan(res, body, placement.owner)
        ser.serialize_mdspan(res, body, placement.local_slot)
        # v2 replication block: factor always, rank tables only when
        # replicated (r=1 round-trips to the v1-equivalent shape)
        ser.serialize_scalar(
            res, body, np.int32(placement.replication_factor))
        if placement.replication_factor > 1:
            ser.serialize_mdspan(res, body, placement.owners)
            ser.serialize_mdspan(res, body, placement.slots)


def placement_from_stream(res, stream) -> Placement:
    body = ser.open_envelope(stream)
    version = int(ser.deserialize_scalar(res, body))
    if not (_PLACEMENT_MIN_READ_VERSION <= version
            <= _PLACEMENT_VERSION):
        raise ValueError(
            f"placement serialization version mismatch: got {version}, "
            f"expected {_PLACEMENT_MIN_READ_VERSION}.."
            f"{_PLACEMENT_VERSION}")
    n_shards = int(ser.deserialize_scalar(res, body))
    n_local = int(ser.deserialize_scalar(res, body))
    generation = int(ser.deserialize_scalar(res, body))
    owner = np.asarray(ser.deserialize_mdspan(res, body), np.int32)
    local_slot = np.asarray(ser.deserialize_mdspan(res, body), np.int32)
    replication_factor = 1
    owners = slots = None
    if version >= 2:
        replication_factor = int(ser.deserialize_scalar(res, body))
        if replication_factor > 1:
            owners = np.asarray(
                ser.deserialize_mdspan(res, body), np.int32)
            slots = np.asarray(
                ser.deserialize_mdspan(res, body), np.int32)
    return Placement(owner=owner, local_slot=local_slot,
                     n_shards=n_shards, n_local=n_local,
                     generation=generation,
                     replication_factor=replication_factor,
                     owners=owners, slots=slots)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RoutedIndex:
    """Index-parallel (``placement="by_list"``) IVF-PQ: one global
    coarse quantizer + rotation replicated on every chip, the IVF lists
    partitioned across shards.  Shard ``s``'s local leaves hold its
    owned lists at slots ``0..n_owned-1`` plus a terminal dummy slot
    (all ids ``-1``) that unowned probes lower to — the scan kernel's
    existing padded-row mask makes those probes contribute nothing, so
    routing needs zero kernel changes."""

    coarse_centers: jax.Array  # (n_lists, rot_dim) — replicated
    rotation: jax.Array        # (dim, rot_dim) — replicated
    owner: jax.Array           # (n_lists,) int32 — replicated
    local_slot: jax.Array      # (n_lists,) int32 — replicated
    local_centers: jax.Array   # (n_dev, L+1, rot_dim) — sharded
    list_recon: jax.Array      # (n_dev, L+1, cap, rot_dim) bf16 — sharded
    list_recon_sq: jax.Array   # (n_dev, L+1, cap) — sharded
    list_indices: jax.Array    # (n_dev, L+1, cap) — sharded
    list_sizes: jax.Array      # (n_dev, L+1) — sharded
    # optional lane-major code leaves (round 10): carried when the base
    # index was codes-mode eligible, so the routed fused scan streams
    # 4*ceil(W/4)+8 B/row instead of the 2*rot+8 recon rows.  None on
    # indexes sharded before round 10 (and after a v1 deserialize).
    codebooks: Optional[jax.Array] = None        # replicated
    list_code_lanes: Optional[jax.Array] = None  # (n_dev, L+1, Wi, cap)
    list_code_rsq: Optional[jax.Array] = None    # (n_dev, L+1, cap)
    # optional raw rows for the re-rank on the owning shard: each held
    # list's dataset rows in slot order (f32, placed exactly as
    # list_recon, the dummy slot zero), and each shard's map from a
    # global id to its row of the flattened (slot * cap + pos) leaf, -1
    # where the shard holds no copy.  Carried when the index was placed
    # from a dataset (build, or shard_by_list(dataset=)); None otherwise.
    list_rows: Optional[jax.Array] = None        # (n_dev, L+1, cap, dim)
    row_pos: Optional[jax.Array] = None          # (n_dev, n_ids) int32
    metric: int = DistanceType.L2Expanded
    size: int = 0
    pq_bits: int = 0
    # calibrated group-capacity estimate (see ivf_pq.group_est); static
    # aux so jit keys change when a recalibration tightens the capacity
    group_est: float = 0.0
    # host-side metadata, NOT pytree leaves (transforms drop them; the
    # host wrappers carry them explicitly, like shard_canaries above)
    placement: Optional[Placement] = None
    canaries: Optional[object] = None

    @property
    def n_shards(self) -> int:
        return self.local_centers.shape[0]

    @property
    def n_lists(self) -> int:
        return self.coarse_centers.shape[0]

    @property
    def capacity(self) -> int:
        return self.list_indices.shape[2]

    @property
    def dim(self) -> int:
        return self.rotation.shape[0]

    def tree_flatten(self):
        # the optional code leaves are pytree children too (None is an
        # empty subtree, so pre-round-10 indexes flatten identically)
        return ((self.coarse_centers, self.rotation, self.owner,
                 self.local_slot, self.local_centers, self.list_recon,
                 self.list_recon_sq, self.list_indices, self.list_sizes,
                 self.codebooks, self.list_code_lanes,
                 self.list_code_rsq, self.list_rows, self.row_pos),
                (self.metric, self.size, self.pq_bits, self.group_est))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, metric=aux[0], size=aux[1],
                   pq_bits=aux[2] if len(aux) > 2 else 0,
                   group_est=aux[3] if len(aux) > 3 else 0.0)


def _mesh_layout(handle):
    """Mesh geometry without the by_row row-divisibility constraint
    (by_list shards lists, not rows)."""
    comms = handle.get_comms()
    mesh = handle.mesh
    axis = comms.axis_name
    expects(mesh.devices.ndim == 1,
            "distributed.ann: a 1-D mesh is required (reshape 2D grids "
            "to the data axis for index sharding)")
    return comms, mesh, axis, mesh.shape[axis], mesh.devices.ravel()


def _replicate(arr, mesh):
    return jax.device_put(arr, jax.sharding.NamedSharding(
        mesh, P(*([None] * jnp.ndim(arr)))))


@functools.partial(jax.jit, static_argnames=("n_ids",))
def _row_positions(list_indices, n_ids):
    """(n_ids,) int32: the row of the flattened (slot * cap + pos) leaf
    that holds each global id in one shard's ``list_indices``, -1 for
    ids the shard holds no copy of."""
    flat = list_indices.reshape(-1)
    at = jnp.where(flat >= 0, flat, n_ids)
    pos = jnp.arange(flat.shape[0], dtype=jnp.int32)
    return jnp.full((n_ids,), -1, jnp.int32).at[at].set(pos, mode="drop")


@jax.jit
def _gather_list_rows(dataset, list_indices):
    """(n_lists, cap, dim) f32: the dataset row of every list slot, zero
    where the slot holds no live row."""
    valid = list_indices >= 0
    rows = dataset[jnp.where(valid, list_indices, 0)].astype(jnp.float32)
    return jnp.where(valid[..., None], rows, 0.0)


def _place_lists(handle, global_leaves, rotation, placement: Placement,
                 metric, size, code_leaves=None, pq_bits=0,
                 group_est=0.0, rows=None) -> RoutedIndex:
    """Assemble a :class:`RoutedIndex` from global per-list arrays
    (centers, recon, recon_sq, indices, sizes) under ``placement``.
    ``code_leaves`` optionally carries (codebooks, list_code_lanes,
    list_code_rsq) — the lane-major compact-code cache the routed fused
    scan streams; the lanes/rsq shard like the recon leaves (axis 0 is
    the global list id), the codebooks replicate.  ``rows`` optionally
    carries the (n_lists, cap, dim) raw rows, placed like the recon
    leaves, with each shard's id -> row map built beside them."""
    centers, recon, rsq, li, sizes = global_leaves
    comms, mesh, axis, n_dev, devs = _mesh_layout(handle)
    expects(placement.n_shards == n_dev,
            f"distributed.ann: placement maps {placement.n_shards} "
            f"shards but the mesh has {n_dev} devices")
    slots = placement.n_local + 1  # terminal dummy slot
    # one host read on the admin path: the id map's static width
    n_ids = int(jnp.max(li)) + 1 if rows is not None else 0

    per_shard = []
    for s in range(n_dev):
        owned = jnp.asarray(placement.shard_lists(s), jnp.int32)

        def pad(a, fill, owned=owned):
            sel = jnp.take(a, owned, axis=0)
            width = ((0, slots - sel.shape[0]),) + ((0, 0),) * (a.ndim - 1)
            return jnp.pad(sel, width, constant_values=fill)

        li_s = pad(li, -1)
        leaves_s = (pad(centers, 0), pad(recon, 0), pad(rsq, 0),
                    li_s, pad(sizes, 0))
        if code_leaves is not None:
            leaves_s += (pad(code_leaves[1], 0), pad(code_leaves[2], 0))
        if rows is not None:
            leaves_s += (pad(rows, 0), _row_positions(li_s, n_ids))
        per_shard.append(leaves_s)
    placed = _stack_leaves(per_shard, mesh, axis, devs)
    n_code = 2 if code_leaves is not None else 0
    return RoutedIndex(
        coarse_centers=_replicate(centers, mesh),
        rotation=_replicate(rotation, mesh),
        owner=_replicate(jnp.asarray(placement.owner), mesh),
        local_slot=_replicate(jnp.asarray(placement.local_slot), mesh),
        local_centers=placed[0], list_recon=placed[1],
        list_recon_sq=placed[2], list_indices=placed[3],
        list_sizes=placed[4],
        codebooks=(_replicate(code_leaves[0], mesh)
                   if code_leaves is not None else None),
        list_code_lanes=placed[5] if code_leaves is not None else None,
        list_code_rsq=placed[6] if code_leaves is not None else None,
        list_rows=placed[5 + n_code] if rows is not None else None,
        row_pos=placed[6 + n_code] if rows is not None else None,
        metric=metric, size=size, pq_bits=int(pq_bits),
        group_est=float(group_est), placement=placement)


def shard_by_list(handle, index, *,
                  placement: Optional[Placement] = None,
                  replication_factor: int = 1,
                  dataset=None) -> RoutedIndex:
    """Partition a single-chip IVF-PQ index's lists across the mesh.

    The index must carry the reconstruction cache (the shard-local scan
    is the recon formulation).  ``placement`` defaults to an LPT balance
    over *live* list sizes (tombstones excluded — dead rows cost scan
    work but a rebalance pass compacts them away, so balancing on live
    rows keeps the placement stable across compactions).

    ``replication_factor=r > 1`` materializes ``r`` copies of every
    list on distinct shards (see :func:`compute_placement`): each shard's
    stacked leaves hold the union of its per-rank owned sets, healthy
    routing serves every list from its primary, and a failed shard's
    lists fail over to replicas with results bit-identical to the
    healthy run (ignored when an explicit ``placement`` is passed — the
    placement carries its own factor).

    ``dataset`` (the rows ``index`` was built over, ids indexing it)
    places each list's raw rows with its copies, f32, on every shard
    that holds the list — what ``search(..., refine_ratio > 1)``
    re-ranks against on the owning shard.  No shard holds the whole
    dataset: a shard's rows cost ``(L+1) * cap * dim * 4`` bytes, about
    ``r / n_shards`` of the dataset's."""
    with named_range("distributed::shard_by_list"):
        expects(handle.comms_initialized(),
                "distributed.ann.shard_by_list: handle has no comms")
        expects(getattr(index, "list_recon", None) is not None,
                "distributed.ann.shard_by_list: index must carry the "
                "reconstruction cache (build with "
                "cache_reconstructions=True)")
        comms, mesh, axis, n_dev, devs = _mesh_layout(handle)
        if placement is None:
            live = _mutate.live_sizes(index.list_indices)
            placement = compute_placement(
                np.asarray(live), n_dev,
                replication_factor=replication_factor)
        rows = None
        if dataset is not None:
            dataset = ensure_array(dataset, "dataset")
            expects(dataset.ndim == 2 and dataset.shape[1] == index.dim,
                    f"distributed.ann.shard_by_list: dataset must be "
                    f"(n, {index.dim}), got {tuple(dataset.shape)}")
            expects(int(jnp.max(index.list_indices)) < dataset.shape[0],
                    "distributed.ann.shard_by_list: the index holds ids "
                    "past the dataset's rows")
            rows = _gather_list_rows(dataset, index.list_indices)
        rsq = index.list_recon_sq
        if rsq is None:
            rsq = ivf_pq._recon_sq(index.list_recon)
        size = int(jnp.sum(index.list_sizes))
        # carry the compact-code cache when the base index is eligible
        # (the routed fused scan streams the lane-major codes at
        # 4*ceil(W/4)+8 B/row instead of the 2*rot+8 recon rows)
        code_leaves = None
        pq_bits = 0
        if ivf_pq._codes_mode_eligible(index):
            if (index.list_code_lanes is None
                    or index.list_code_rsq is None):
                index = ivf_pq._with_code_lanes(index)
            code_leaves = (index.codebooks, index.list_code_lanes,
                           index.list_code_rsq)
            pq_bits = int(index.pq_bits)
        out = _place_lists(
            handle, (index.centers, index.list_recon, rsq,
                     index.list_indices, index.list_sizes),
            index.rotation, placement, index.metric, size,
            code_leaves=code_leaves, pq_bits=pq_bits,
            group_est=float(getattr(index, "group_est", 0.0)), rows=rows)
        out.canaries = getattr(index, "canaries", None)
        out.generation = _mutate.generation(index)
        # precompute the fused kernels' id-exactness verdict now (one
        # tiny host sync at shard time) so search dispatch never syncs
        grouped.ids_f32_exact(out, out.list_indices)
        return out


def _build_by_list(handle, params: ivf_pq.IndexParams, dataset,
                   replication_factor: int = 1) -> RoutedIndex:
    with named_range("distributed::ivf_pq_build_by_list"):
        expects(handle.comms_initialized(),
                "distributed.ann.build: handle has no comms (use "
                "CommsSession.worker_handle())")
        expects(params.cache_reconstructions,
                "distributed.ann: the routed search kernel runs the "
                "reconstruction path; cache_reconstructions must be True")
        dataset = ensure_array(dataset, "dataset")
        comms, mesh, axis, n_dev, devs = _mesh_layout(handle)
        expects(params.n_lists >= n_dev,
                f"distributed.ann: by_list needs n_lists "
                f"({params.n_lists}, GLOBAL in this mode) >= the "
                f"{n_dev}-device mesh")
        # ONE global quantizer/codebook train — the coarse structure is
        # tiny and replicated; only the lists are partitioned
        base = ivf_pq.build(handle, params, dataset)
        return shard_by_list(handle, base,
                             replication_factor=replication_factor,
                             dataset=dataset)


def _gather_global(index: RoutedIndex):
    """Reassemble the global per-list arrays from the stacked shards
    (admin path: rebalance / serialization — one cross-device gather of
    each leaf, never on the serving path)."""
    own = jnp.asarray(np.asarray(index.owner), jnp.int32)
    slot = jnp.asarray(np.asarray(index.local_slot), jnp.int32)
    centers = index.local_centers[own, slot]
    recon = index.list_recon[own, slot]
    rsq = index.list_recon_sq[own, slot]
    li = index.list_indices[own, slot]
    sizes = index.list_sizes[own, slot]
    code_leaves = None
    if index.list_code_lanes is not None:
        code_leaves = (index.codebooks, index.list_code_lanes[own, slot],
                       index.list_code_rsq[own, slot])
    rows = (index.list_rows[own, slot] if index.list_rows is not None
            else None)
    return centers, recon, rsq, li, sizes, code_leaves, rows


def global_list_sizes(index: RoutedIndex) -> np.ndarray:
    """Live rows of each global list, (n_lists,) host numpy, counted on
    the list's primary copy (admin path: one small cross-device gather)
    — the list sizes a routed search's work accounting reads."""
    own = jnp.asarray(np.asarray(index.owner), jnp.int32)
    slot = jnp.asarray(np.asarray(index.local_slot), jnp.int32)
    return np.asarray(jnp.sum(index.list_indices[own, slot] >= 0, axis=-1))


def route_vectors(index: RoutedIndex, vectors) -> np.ndarray:
    """The distributed WRITE path's list router (round 19): the global
    IVF list each row lands in, ranked by the SAME replicated coarse
    quantizer the probe path uses — a row's home list is its top probe
    (``n_probes=1``), so a written row is found by exactly the probes
    that would scan it after a fold.  One jitted call keyed by the
    write-batch shape; :func:`raft_tpu.core.aot.warm_write_router`
    pre-traces the serving batch shapes so the first write after a
    deploy or failover is compile-free."""
    vecs = jnp.asarray(vectors, jnp.float32)
    expects(vecs.ndim == 2 and vecs.shape[1] == index.dim,
            f"distributed.ann.route_vectors: vectors must be "
            f"(n, {index.dim}), got {tuple(vecs.shape)}")
    probes = ivf_pq._select_clusters(index.coarse_centers, index.rotation,
                                     vecs, 1, DistanceType(index.metric))
    return np.asarray(probes).reshape(-1)


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "metric",
                                             "axis_name", "mesh", "failed",
                                             "refine_to"))
def _dist_search_routed(sharded, replicated, queries, k, n_probes, metric,
                        axis_name, mesh, failed=(), filter_words=None,
                        refine_to=0):
    """Routed probe-order recon scan under ``shard_map``.  With
    ``refine_to`` > 0 the shards scan at ``k`` (= refine_to * ratio)
    and re-rank the kept candidates against the raw rows they hold (the
    last two ``sharded`` leaves): :func:`_merge_refined`."""
    sspecs = tuple(P(axis_name, *([None] * (leaf.ndim - 1)))
                   for leaf in sharded)
    rspecs = tuple(P() for _ in replicated)
    # the bitset addresses GLOBAL ids — the routed list_indices store
    # exactly those, so one replicated buffer serves every shard and the
    # replica-failover table swaps compose unchanged (replica copies are
    # identical rows, so the admission fold commutes with routing)
    has_f = filter_words is not None
    in_specs = (sspecs, rspecs, P()) + ((P(),) if has_f else ())
    out_specs = (P(),) * (5 if has_f else 4)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    def run(sl, rl, q, *rest):
        local_centers, list_recon, list_recon_sq, list_indices = sl[:4]
        coarse, rot, owner, local_slot = rl
        s = jax.lax.axis_index(axis_name)
        cap = list_recon.shape[2]
        # replicated coarse routing: every shard ranks the SAME probe
        # set deterministically, so ownership tests need no exchange
        probes = ivf_pq._select_clusters(coarse, rot, q, n_probes, metric)
        # per-list probe histogram for the routing policy's heat window:
        # built from the REPLICATED probe set (identical on every
        # shard), so it replicates for free — and it stays a lazy
        # device array until a maintenance-path refresh reads it
        hist = jnp.zeros((owner.shape[0],), jnp.int32).at[
            probes.reshape(-1)].add(1)
        owned = owner[probes] == s                       # (q, n_probes)
        dummy = local_centers.shape[1] - 1               # static slot L
        local_probes = jnp.where(owned, local_slot[probes],
                                 dummy).astype(jnp.int32)
        # unowned probes point at the dummy slot: all-(-1) ids lower to
        # the worst-distance padded-row path inside the scan — the same
        # mask tombstones ride, so this is the existing kernel untouched
        ld, li = ivf_pq._search_impl_recon(
            local_centers[0], list_recon[0], list_indices[0], rot, q,
            k, n_probes, metric, probes=local_probes,
            list_recon_sq=list_recon_sq[0],
            filter_words=rest[0] if has_f else None)
        select_min = metric != DistanceType.InnerProduct
        scanned = (jnp.sum(owned.astype(jnp.int32)) * cap).astype(
            jnp.int32)
        if failed:
            bad = jnp.any(jnp.asarray(failed, jnp.int32) == s)
            sentinel = jnp.inf if select_min else -jnp.inf
            ld = jnp.where(bad, jnp.full_like(ld, sentinel), ld)
            li = jnp.where(bad, jnp.full_like(li, -1), li)
            scanned = jnp.where(bad, 0, scanned)
        all_scanned = jax.lax.all_gather(scanned, axis_name)  # (n_dev,)
        nq = q.shape[0]
        if refine_to:
            md, mi = _merge_refined(sl[4][0], sl[5][0], q, ld, li,
                                    refine_to, metric, axis_name)
        else:
            # the k-bounded candidate exchange: exactly (q, k) pairs per
            # shard regardless of index size — the payload the
            # data-parallel path also ships, but here each pair was
            # mined from 1/n_shards of the probed rows
            all_d = jax.lax.all_gather(ld, axis_name)    # (n_dev, q, k)
            all_i = jax.lax.all_gather(li, axis_name)
            # hierarchical exactness: a global top-k candidate is in its
            # owning shard's local top-k, so the replicated merge over
            # the (n_dev * k)-wide survivors equals the single-index
            # search.  sqrt=False: the shard-local epilogue already
            # applied it for the sqrt metrics, and the merge is monotone
            md, mi = grouped.finalize_topk(
                jnp.transpose(all_d, (1, 0, 2)),
                jnp.transpose(all_i, (1, 0, 2)),
                nq, k, select_min, False, select_k)
        if has_f:
            admitted = jax.lax.all_gather(
                jnp.sum((li >= 0).astype(jnp.int32)), axis_name)
            return md, mi, all_scanned, hist, admitted
        return md, mi, all_scanned, hist

    args = (sharded, replicated, queries) + (
        (filter_words,) if has_f else ())
    return run(*args)


def _routed_leaves(index: "RoutedIndex", form: str):
    """(sharded, replicated) leaf tuples for the routed grouped dispatch.
    ``fused_codes`` threads the lane-major code cache where the recon
    forms thread the bf16 reconstructions — the kernels share positional
    structure (data, row-norms), so ONE jitted dispatcher serves both."""
    if form == "fused_codes":
        sharded = (index.local_centers, index.list_code_lanes,
                   index.list_code_rsq, index.list_indices)
        replicated = (index.coarse_centers, index.rotation, index.owner,
                      index.local_slot, index.codebooks)
    else:
        sharded = (index.local_centers, index.list_recon,
                   index.list_recon_sq, index.list_indices)
        replicated = (index.coarse_centers, index.rotation, index.owner,
                      index.local_slot)
    return sharded, replicated


@functools.partial(jax.jit, static_argnames=(
    "k", "kt", "n_probes", "metric", "axis_name", "mesh", "n_groups",
    "form", "pq_bits", "use_pallas", "failed", "refine_to"))
def _dist_search_routed_grouped(sharded, replicated, queries, k, kt,
                                n_probes, metric, axis_name, mesh,
                                n_groups, form, pq_bits=0,
                                use_pallas=False, failed=(),
                                filter_words=None, refine_to=0):
    """Routed (by_list) grouped/fused scan under ``shard_map``
    (round 10): the tentpole dispatch.  Replicated coarse routing picks
    the probe set, ownership maps it to local slots, and the shard scans
    its owned probes with the grouped formulation at the static capacity
    ``n_groups`` — the fused code scan streams 4*ceil(W/4)+8 B/row where
    the probe-order recon scan streamed 2*rot+8 (264 -> 72 at the bench
    shape).  Alongside the k-bounded candidate exchange, each shard
    all_gathers its true required group count so the HOST can check the
    calibrated capacity without a second collective; the check itself
    (and the rare exact re-dispatch) lives in :func:`search`, keeping
    this function sync-free.  ``refine_to`` re-ranks on the owning
    shard as in :func:`_dist_search_routed`."""
    sspecs = tuple(P(axis_name, *([None] * (leaf.ndim - 1)))
                   for leaf in sharded)
    rspecs = tuple(P() for _ in replicated)
    has_f = filter_words is not None
    in_specs = (sspecs, rspecs, P()) + ((P(),) if has_f else ())
    out_specs = (P(),) * (6 if has_f else 5)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    def run(sl, rl, q, *rest):
        local_centers, data, rownorm, list_indices = sl[:4]
        fw = rest[0] if has_f else None
        coarse, rot, owner, local_slot = rl[:4]
        s = jax.lax.axis_index(axis_name)
        slots = local_centers.shape[1]
        cap = list_indices.shape[2]
        probes = ivf_pq._select_clusters(coarse, rot, q, n_probes, metric)
        # replicated per-list probe histogram (identical on every shard
        # — the probe set is) for the routing policy's heat window; a
        # lazy device array until a maintenance-path refresh
        hist = jnp.zeros((owner.shape[0],), jnp.int32).at[
            probes.reshape(-1)].add(1)
        owned = owner[probes] == s                       # (q, n_probes)
        # unowned probes map to the OUT-OF-RANGE sentinel slot id
        # (== slots), NOT the dummy slot: build_groups drops sentinel
        # probes from the pair groups entirely, so they cost no group
        # slots.  Mapping them to the dummy slot (the probe-order path's
        # trick) would funnel ~(1 - 1/n_shards) of all pairs into ONE
        # list and blow any calibrated capacity.
        local_probes = jnp.where(owned, local_slot[probes],
                                 slots).astype(jnp.int32)
        if form == "fused_codes":
            ld, li = ivf_pq._search_impl_fused_codes_grouped(
                local_centers[0], rl[4], data[0], rownorm[0],
                list_indices[0], rot, q, local_probes, k, kt, metric,
                n_groups, pq_bits, filter_words=fw)
        elif form == "fused_recon":
            ld, li = ivf_pq._search_impl_fused_recon_grouped(
                local_centers[0], data[0], rownorm[0], list_indices[0],
                rot, q, local_probes, k, kt, metric, n_groups,
                filter_words=fw)
        else:
            rot_dim = data.shape[3]
            G = grouped.GROUP
            block = grouped.block_size(n_groups, G * cap * 8,
                                       cap * rot_dim * 2, G * rot_dim * 4)
            ld, li = ivf_pq._search_impl_recon_grouped(
                local_centers[0], data[0], rownorm[0], list_indices[0],
                rot, q, local_probes, k, metric, n_groups, block,
                use_pallas=use_pallas, kt=kt, filter_words=fw)
        select_min = metric != DistanceType.InnerProduct
        scanned = (jnp.sum(owned.astype(jnp.int32)) * cap).astype(
            jnp.int32)
        # the shard's TRUE group requirement — the in-graph overflow
        # count the calibrated-capacity regime is checked against
        needed = grouped.num_groups(local_probes, slots)
        if failed:
            bad = jnp.any(jnp.asarray(failed, jnp.int32) == s)
            sentinel = jnp.inf if select_min else -jnp.inf
            ld = jnp.where(bad, jnp.full_like(ld, sentinel), ld)
            li = jnp.where(bad, jnp.full_like(li, -1), li)
            scanned = jnp.where(bad, 0, scanned)
            needed = jnp.where(bad, 0, needed)
        all_scanned = jax.lax.all_gather(scanned, axis_name)  # (n_dev,)
        all_needed = jax.lax.all_gather(needed, axis_name)    # (n_dev,)
        nq = q.shape[0]
        if refine_to:
            md, mi = _merge_refined(sl[4][0], sl[5][0], q, ld, li,
                                    refine_to, metric, axis_name)
        else:
            all_d = jax.lax.all_gather(ld, axis_name)    # (n_dev, q, k)
            all_i = jax.lax.all_gather(li, axis_name)
            md, mi = grouped.finalize_topk(
                jnp.transpose(all_d, (1, 0, 2)),
                jnp.transpose(all_i, (1, 0, 2)),
                nq, k, select_min, False, select_k)
        if has_f:
            admitted = jax.lax.all_gather(
                jnp.sum((li >= 0).astype(jnp.int32)), axis_name)
            return md, mi, all_scanned, all_needed, hist, admitted
        return md, mi, all_scanned, all_needed, hist

    args = (sharded, replicated, queries) + (
        (filter_words,) if has_f else ())
    return run(*args)


def rebalance_placement(handle, index: RoutedIndex, *,
                        placement: Optional[Placement] = None
                        ) -> RoutedIndex:
    """Recompute the list partition from *live* row counts and re-shard.

    The swap is a single global generation bump — the barrier the
    serving tier needs: the new pytree is assembled completely (every
    shard's leaves) before anything is published, and
    ``Executor.swap_index`` installs it with one atomic reference swap
    after warming, so no reader ever sees shard ``a`` at placement ``g``
    and shard ``b`` at ``g+1``.  The placement generation advances with
    it, invalidating placement-keyed cache entries."""
    with named_range("distributed::rebalance_placement"):
        expects(index.placement is not None,
                "distributed.ann.rebalance_placement: index carries no "
                "placement map")
        (centers, recon, rsq, li, sizes, code_leaves,
         rows) = _gather_global(index)
        if placement is None:
            live = jnp.sum(li >= 0, axis=1).astype(jnp.int32)
            placement = compute_placement(
                np.asarray(live), index.n_shards,
                generation=index.placement.generation + 1,
                replication_factor=index.placement.replication_factor)
        out = _place_lists(handle, (centers, recon, rsq, li, sizes),
                           index.rotation, placement, index.metric,
                           index.size, code_leaves=code_leaves,
                           pq_bits=index.pq_bits,
                           group_est=index.group_est, rows=rows)
        out.canaries = index.canaries
        _mutate.next_generation(index, out)
        return out


# v2 (round 10): trailing (has_codes, pq_bits, group_est) block and,
# when has_codes, the lane-major compact-code cache (codebooks, lanes,
# row norms) — v1 streams read fine and land uncalibrated/recon-only.
# v3 (round 17): the embedded placement envelope may be placement-v2
# (replicated rank tables); the routed body layout is unchanged, the
# bump marks the back-compat read window.  v1/v2 streams still read
# (and land r=1); v2 READERS cannot open a replicated v3 stream — the
# version check fails loudly instead of mis-parsing the rank tables.
# v4: trailing raw-rows block after the canaries — ``has_rows`` and,
# when set, the global (n_lists, cap, dim) f32 rows; v1-v3 streams land
# without rows (search refuses ``refine_ratio > 1`` on them).
_ROUTED_SERIALIZATION_VERSION = 4
_ROUTED_MIN_READ_VERSION = 1


def serialize_routed(res, stream, index: RoutedIndex) -> None:
    """CRC32-enveloped dump of a routed index: the placement map rides
    in the envelope next to the global per-list arrays (reassembled from
    the shards), so a reload lands lists on the same owners.  The bf16
    recon cache is stored as uint16 views (the npy format carries no
    bfloat16 descr — same trick :mod:`raft_tpu.core.aot` uses)."""
    expects(index.placement is not None,
            "distributed.ann.serialize_routed: index carries no "
            "placement map")
    (centers, recon, rsq, li, sizes, code_leaves,
     rows) = _gather_global(index)
    with ser.enveloped_writer(stream) as body:
        ser.serialize_scalar(
            res, body, np.int32(_ROUTED_SERIALIZATION_VERSION))
        ser.serialize_scalar(res, body, np.int32(index.metric))
        ser.serialize_scalar(res, body, np.int64(index.size))
        ser.serialize_scalar(
            res, body, np.int64(_mutate.generation(index)))
        placement_to_stream(res, body, index.placement)
        ser.serialize_mdspan(res, body, centers)
        ser.serialize_mdspan(
            res, body, np.asarray(jax.device_get(recon)).view(np.uint16))
        ser.serialize_mdspan(res, body, rsq)
        ser.serialize_mdspan(res, body, li)
        ser.serialize_mdspan(res, body, sizes)
        ser.serialize_mdspan(res, body, index.rotation)
        ser.serialize_scalar(
            res, body, np.int32(1 if code_leaves is not None else 0))
        ser.serialize_scalar(res, body, np.int32(index.pq_bits))
        ser.serialize_scalar(res, body, np.float64(index.group_est))
        if code_leaves is not None:
            books, lanes, crsq = code_leaves
            ser.serialize_mdspan(res, body, books)
            ser.serialize_mdspan(res, body, lanes)
            ser.serialize_mdspan(res, body, crsq)
        from raft_tpu.integrity import canary as _canary
        _canary.to_stream(res, body, index.canaries)
        ser.serialize_scalar(res, body, np.int32(rows is not None))
        if rows is not None:
            ser.serialize_mdspan(res, body, rows)


def deserialize_routed(handle, stream) -> RoutedIndex:
    """Reload a routed index onto the handle's mesh under its stored
    placement (the mesh must match the stored shard count).  v1 streams
    (pre round 10) load recon-only and uncalibrated — always correct,
    just without the fused code scan and tightened capacity."""
    body = ser.open_envelope(stream)
    version = int(ser.deserialize_scalar(handle, body))
    if not (_ROUTED_MIN_READ_VERSION <= version
            <= _ROUTED_SERIALIZATION_VERSION):
        raise ValueError(
            f"routed serialization version mismatch: got {version}, "
            f"expected {_ROUTED_MIN_READ_VERSION}.."
            f"{_ROUTED_SERIALIZATION_VERSION}")
    metric = int(ser.deserialize_scalar(handle, body))
    size = int(ser.deserialize_scalar(handle, body))
    generation = int(ser.deserialize_scalar(handle, body))
    placement = placement_from_stream(handle, body)
    centers = jnp.asarray(ser.deserialize_mdspan(handle, body))
    recon = jnp.asarray(
        ser.deserialize_mdspan(handle, body).view(jnp.bfloat16))
    rsq = jnp.asarray(ser.deserialize_mdspan(handle, body))
    li = jnp.asarray(ser.deserialize_mdspan(handle, body))
    sizes = jnp.asarray(ser.deserialize_mdspan(handle, body))
    rotation = jnp.asarray(ser.deserialize_mdspan(handle, body))
    code_leaves = None
    pq_bits = 0
    group_est = 0.0
    if version >= 2:
        has_codes = int(ser.deserialize_scalar(handle, body))
        pq_bits = int(ser.deserialize_scalar(handle, body))
        group_est = float(ser.deserialize_scalar(handle, body))
        if has_codes:
            books = jnp.asarray(ser.deserialize_mdspan(handle, body))
            lanes = jnp.asarray(ser.deserialize_mdspan(handle, body))
            crsq = jnp.asarray(ser.deserialize_mdspan(handle, body))
            code_leaves = (books, lanes, crsq)
    from raft_tpu.integrity import canary as _canary
    canaries = _canary.from_stream(handle, body)
    rows = None
    if version >= 4 and int(ser.deserialize_scalar(handle, body)):
        rows = jnp.asarray(ser.deserialize_mdspan(handle, body))
    out = _place_lists(handle, (centers, recon, rsq, li, sizes),
                       rotation, placement, metric, size,
                       code_leaves=code_leaves, pq_bits=pq_bits,
                       group_est=group_est, rows=rows)
    out.canaries = canaries
    out.generation = generation
    return out


# ---------------------------------------------------------------------------
# IVF-Flat (same shard -> local search -> all_gather -> merge seam)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DistributedFlatIndex:
    """Leaf-stacked local IVF-Flat indexes (one shard per device)."""

    centers: jax.Array        # (n_dev, n_lists, dim)
    list_data: jax.Array      # (n_dev, n_lists, cap, dim)
    list_indices: jax.Array   # (n_dev, n_lists, cap) — GLOBAL ids
    list_sizes: jax.Array
    metric: int = DistanceType.L2Expanded
    size: int = 0
    # per-shard recall canaries — host-side, not a pytree leaf
    shard_canaries: Optional[tuple] = None

    @property
    def n_shards(self) -> int:
        return self.centers.shape[0]

    def tree_flatten(self):
        return ((self.centers, self.list_data, self.list_indices,
                 self.list_sizes), (self.metric, self.size))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, metric=aux[0], size=aux[1])


def _shard_layout(handle, dataset):
    comms = handle.get_comms()
    mesh = handle.mesh
    axis = comms.axis_name
    expects(mesh.devices.ndim == 1,
            "distributed.ann: a 1-D mesh is required (reshape 2D grids "
            "to the data axis for index sharding)")
    n = dataset.shape[0]
    n_dev = mesh.shape[axis]
    expects(n % n_dev == 0,
            f"distributed.ann: n ({n}) must divide evenly over "
            f"{n_dev} devices (pad the input)")
    return comms, mesh, axis, n, n_dev, n // n_dev, mesh.devices.ravel()


def build_flat(handle, params, dataset, *,
               retry_policy: Optional[_retry.RetryPolicy] = None,
               deadline: Optional[_retry.Deadline] = None
               ) -> DistributedFlatIndex:
    """Shard rows over the mesh and build one local IVF-Flat index per
    shard, ids globally offset (the ANN bench ``multigpu`` seam,
    docs/source/cuda_ann_benchmarks.md:163, for raft_ivf_flat)."""
    return _entry("distributed.ann.build_flat",
                  lambda: _build_flat_impl(handle, params, dataset),
                  retry_policy, deadline)


def _build_flat_impl(handle, params, dataset) -> DistributedFlatIndex:
    from raft_tpu.neighbors import ivf_flat

    with named_range("distributed::ivf_flat_build"):
        expects(handle.comms_initialized(),
                "distributed.ann.build_flat: handle has no comms")
        dataset = ensure_array(dataset, "dataset")
        comms, mesh, axis, n, n_dev, per, devs = _shard_layout(
            handle, dataset)

        locals_ = []
        for s in range(n_dev):
            idx = ivf_flat.build(handle, params, dataset[s * per:(s + 1) * per])
            idx.list_indices = jnp.where(
                idx.list_indices >= 0, idx.list_indices + s * per, -1)
            locals_.append(idx)
        cap = max(ix.capacity for ix in locals_)

        def pad_cap(a, fill):
            return jnp.pad(a, ((0, 0), (0, cap - a.shape[1]))
                           + ((0, 0),) * (a.ndim - 2),
                           constant_values=fill)

        leaves = [(ix.centers, pad_cap(ix.list_data, 0),
                   pad_cap(ix.list_indices, -1), ix.list_sizes)
                  for ix in locals_]
        placed = _stack_leaves(leaves, mesh, axis, devs)
        out = DistributedFlatIndex.tree_unflatten(
            (params.metric, n), tuple(placed))
        out.shard_canaries = _collect_canaries(locals_, per,
                                               offset_ids=True)
        return out


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "metric",
                                             "axis_name", "mesh", "failed"))
def _dist_search_flat(leaves, queries, k, n_probes, metric, axis_name,
                      mesh, failed=()):
    specs = tuple(P(axis_name, *([None] * (leaf.ndim - 1)))
                  for leaf in leaves)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(specs, P()), out_specs=(P(), P()),
                       check_vma=False)
    def run(lv, q):
        from raft_tpu.neighbors import ivf_flat
        centers, list_data, list_indices, _ = lv
        ld, li = ivf_flat._search_impl(centers[0], list_data[0],
                                       list_indices[0], q, k, n_probes,
                                       metric)
        select_min = metric != DistanceType.InnerProduct
        if failed:
            s = jax.lax.axis_index(axis_name)
            bad = jnp.any(jnp.asarray(failed, jnp.int32) == s)
            sentinel = jnp.inf if select_min else -jnp.inf
            ld = jnp.where(bad, jnp.full_like(ld, sentinel), ld)
            li = jnp.where(bad, jnp.full_like(li, -1), li)
        all_d = jax.lax.all_gather(ld, axis_name)
        all_i = jax.lax.all_gather(li, axis_name)
        nq = q.shape[0]
        return select_k(
            jnp.transpose(all_d, (1, 0, 2)).reshape(nq, -1), k,
            in_idx=jnp.transpose(all_i, (1, 0, 2)).reshape(nq, -1),
            select_min=select_min)

    return run(leaves, queries)


def search_flat(handle, params, index: DistributedFlatIndex, queries,
                k: int, *,
                failed_shards: Sequence[int] = (),
                return_status: bool = False,
                retry_policy: Optional[_retry.RetryPolicy] = None,
                deadline: Optional[_retry.Deadline] = None):
    """Sharded IVF-Flat search + merge; replicated (distances, ids).
    Same degraded-mode / retry contract as :func:`search`."""
    with named_range("distributed::ivf_flat_search"):
        expects(handle.comms_initialized(),
                "distributed.ann.search_flat: handle has no comms")
        comms = handle.get_comms()
        queries = ensure_array(queries, "queries")
        n_probes = min(params.n_probes, index.centers.shape[1])
        leaves = (index.centers, index.list_data, index.list_indices,
                  index.list_sizes)
        failed = _degraded_set(index.n_shards, failed_shards)
        # same straggler seam as search(): host-side pause, exact merge
        stragglers = faults.straggler_pause(index.n_shards)
        if stragglers:
            _flight.record_event("distributed.straggler",
                                 delays_s=list(stragglers),
                                 n_shards=index.n_shards)
        d, i = _entry(
            "distributed.ann.search_flat",
            lambda: _dist_search_flat(leaves, queries, int(k), n_probes,
                                      index.metric, comms.axis_name,
                                      handle.mesh, failed=failed),
            retry_policy, deadline)
        if not return_status:
            return d, i
        status = np.ones(index.n_shards, np.int8)
        status[list(failed)] = 0
        return d, i, jnp.asarray(status)


# ---------------------------------------------------------------------------
# CAGRA (reference's explicit multi-GPU seam: per-GPU graph chunks +
# merged search, detail/cagra/graph_core.cuh:333-369)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DistributedCagraIndex:
    """Per-shard CAGRA graphs + packed walk tables, leaf-stacked.  Ids
    inside each shard's graph/table are LOCAL (0..per-1); search maps
    them to global ids with the shard offset.  ``use_walk=False`` (walk
    fidelity calibration failed, or the per-shard table exceeds the
    byte gate — the same routes single-device ``cagra.search`` takes)
    stores (1, 1)-placeholder walk leaves and searches via the exact
    direct walk over ``graph``."""

    dataset: jax.Array        # (n_dev, per, dim)
    graph: jax.Array          # (n_dev, per, deg)
    table: jax.Array          # (n_dev, per, W) int16 packed neighborhoods
    proj: jax.Array           # (n_dev, dim, pdim)
    entry_proj: jax.Array     # (n_dev, S, pdim) bf16
    entry_sq: jax.Array       # (n_dev, S)
    entry_ids: jax.Array      # (n_dev, S) int32 LOCAL
    metric: int = DistanceType.L2Expanded
    size: int = 0
    use_walk: bool = True
    # per-shard recall canaries — host-side, not a pytree leaf; CAGRA
    # shard ids stay LOCAL, so these carry local ground-truth ids
    shard_canaries: Optional[tuple] = None

    @property
    def n_shards(self) -> int:
        return self.dataset.shape[0]

    def tree_flatten(self):
        return ((self.dataset, self.graph, self.table, self.proj,
                 self.entry_proj, self.entry_sq, self.entry_ids),
                (self.metric, self.size, self.use_walk))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, metric=aux[0], size=aux[1], use_walk=aux[2])


def build_cagra(handle, params, dataset, *,
                retry_policy: Optional[_retry.RetryPolicy] = None,
                deadline: Optional[_retry.Deadline] = None
                ) -> DistributedCagraIndex:
    """Shard rows over the mesh and build one local CAGRA graph + packed
    walk table per shard (reference: graph_core.cuh:333-369 builds the
    kNN graph in per-GPU chunks; here each shard also serves its own
    walk).  A single projection dim (calibrated on shard 0) is forced on
    every shard so the packed tables stack; when calibration fails
    (pdim 0) or the per-shard table exceeds the byte gate, the index
    falls back to the exact direct walk — the same two routes
    single-device ``cagra.search`` takes."""
    return _entry("distributed.ann.build_cagra",
                  lambda: _build_cagra_impl(handle, params, dataset),
                  retry_policy, deadline)


def _build_cagra_impl(handle, params, dataset) -> DistributedCagraIndex:
    from raft_tpu.neighbors import cagra

    with named_range("distributed::cagra_build"):
        expects(handle.comms_initialized(),
                "distributed.ann.build_cagra: handle has no comms")
        dataset = ensure_array(dataset, "dataset")
        comms, mesh, axis, n, n_dev, per, devs = _shard_layout(
            handle, dataset)

        locals_, shard_idxs, pdim, use_walk = [], [], None, True
        for s in range(n_dev):
            idx = cagra.build(handle, params, dataset[s * per:(s + 1) * per])
            shard_idxs.append(idx)
            if pdim is None:
                pdim = cagra._auto_pdim(idx)
                use_walk = (pdim > 0 and cagra._table_bytes(
                    per, idx.graph_degree, pdim, False)
                    <= cagra._WALK_TABLE_MAX_BYTES)
            if use_walk:
                cache = cagra._walk_cache(handle, idx, pdim, 4096)
                walk_leaves = (cache.table, cache.proj, cache.entry_proj,
                               cache.entry_sq, cache.entry_ids)
            else:
                walk_leaves = (jnp.zeros((1, 1), jnp.int16),
                               jnp.zeros((1, 1), jnp.float32),
                               jnp.zeros((1, 1), jnp.bfloat16),
                               jnp.zeros((1,), jnp.float32),
                               jnp.zeros((1,), jnp.int32))
            locals_.append((idx.dataset, idx.graph) + walk_leaves)
        placed = _stack_leaves(locals_, mesh, axis, devs)
        out = DistributedCagraIndex.tree_unflatten(
            (params.metric, n, use_walk), tuple(placed))
        # CAGRA shard ids are local: ground truth needs no offset
        out.shard_canaries = _collect_canaries(shard_idxs, per,
                                               offset_ids=False)
        return out


@functools.partial(jax.jit, static_argnames=(
    "k", "itopk", "search_width", "max_iterations", "metric", "rerank",
    "deg", "axis_name", "mesh", "use_walk", "n_samplings"))
def _dist_search_cagra(leaves, queries, seed_key, k, itopk, search_width,
                       max_iterations, metric, rerank, deg, axis_name,
                       mesh, use_walk, n_samplings=1):
    specs = tuple(P(axis_name, *([None] * (leaf.ndim - 1)))
                  for leaf in leaves)
    select_min = metric != DistanceType.InnerProduct

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(specs, P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    def run(lv, q, skey):
        from raft_tpu.neighbors import cagra
        ds, graph, table, proj, ep, esq, eids = lv
        per = ds.shape[1]
        s = jax.lax.axis_index(axis_name)
        if use_walk:
            d, i = cagra._search_impl_walk(
                ds[0], table[0], ep[0], esq[0], eids[0], proj[0], q, k,
                itopk, search_width, max_iterations, metric, rerank, deg)
        else:
            # same seed-count formula as single-device cagra.search
            n_seeds = max(itopk,
                          min(per, max(n_samplings * 4 * itopk, 128)))
            seed_ids = jax.random.randint(
                jax.random.fold_in(skey, s), (q.shape[0], n_seeds), 0,
                per, dtype=jnp.int32)
            d, i = cagra._search_impl(ds[0], graph[0], q, seed_ids, k,
                                      itopk, search_width,
                                      max_iterations, metric)
        i = jnp.where(i >= 0, i + s * per, -1)
        all_d = jax.lax.all_gather(d, axis_name)
        all_i = jax.lax.all_gather(i, axis_name)
        nq = q.shape[0]
        return select_k(
            jnp.transpose(all_d, (1, 0, 2)).reshape(nq, -1), k,
            in_idx=jnp.transpose(all_i, (1, 0, 2)).reshape(nq, -1),
            select_min=select_min)

    return run(leaves, queries, seed_key)


def search_cagra(handle, params, index: DistributedCagraIndex, queries,
                 k: int, *,
                 retry_policy: Optional[_retry.RetryPolicy] = None,
                 deadline: Optional[_retry.Deadline] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Sharded CAGRA walk + merge; replicated (distances, global ids).
    Transient faults at entry (site ``distributed.ann.search_cagra``)
    are retried — the seed key is drawn once, so a retried query
    answers identically."""
    with named_range("distributed::cagra_search"):
        expects(handle.comms_initialized(),
                "distributed.ann.search_cagra: handle has no comms")
        comms = handle.get_comms()
        queries = ensure_array(queries, "queries")
        itopk = max(params.itopk_size, k)
        max_iter = params.max_iterations or (
            10 + itopk // max(params.search_width, 1))
        rerank = min(itopk, params.rerank_topk or max(32, 2 * k))
        rerank = max(rerank, k)
        deg = index.graph.shape[2]
        leaves = (index.dataset, index.graph, index.table, index.proj,
                  index.entry_proj, index.entry_sq, index.entry_ids)
        seed_key = handle.next_key()
        return _entry(
            "distributed.ann.search_cagra",
            lambda: _dist_search_cagra(
                leaves, queries, seed_key, int(k), itopk,
                params.search_width, max_iter, index.metric, rerank, deg,
                comms.axis_name, handle.mesh, index.use_walk,
                n_samplings=max(params.num_random_samplings, 1)),
            retry_policy, deadline)


# ---------------------------------------------------------------------------
# per-shard recall-canary health checks (raft_tpu.integrity)
# ---------------------------------------------------------------------------

def _collect_canaries(shard_indexes, per, *, offset_ids):
    """Gather per-shard CanarySets off the local indexes.  ``offset_ids``
    globalizes the stored ground-truth ids to match the stacked leaves'
    id space (IVF shards store GLOBAL ids; CAGRA shards stay local)."""
    cans = [getattr(ix, "canaries", None) for ix in shard_indexes]
    if all(c is None for c in cans):
        return None
    out = []
    for s, cs in enumerate(cans):
        if cs is not None and offset_ids and s > 0:
            cs = dataclasses.replace(cs, gt_ids=cs.gt_ids + s * per)
        out.append(cs)
    return tuple(out)


def _local_index(index, s):
    """Reassemble shard ``s`` as a single-device index (a leaf slice —
    the stacked layout is exactly the local index layout plus a leading
    shard axis)."""
    from raft_tpu.neighbors import cagra, ivf_flat, ivf_pq
    if isinstance(index, DistributedIndex):
        out = ivf_pq.Index(
            centers=index.centers[s], codebooks=index.codebooks[s],
            list_codes=index.list_codes[s],
            list_indices=index.list_indices[s],
            list_sizes=index.list_sizes[s], rotation=index.rotation[s],
            metric=index.metric, list_recon=index.list_recon[s])
    elif isinstance(index, DistributedFlatIndex):
        out = ivf_flat.Index(
            centers=index.centers[s], list_data=index.list_data[s],
            list_indices=index.list_indices[s],
            list_sizes=index.list_sizes[s], metric=index.metric)
    elif isinstance(index, DistributedCagraIndex):
        out = cagra.Index(dataset=index.dataset[s], graph=index.graph[s],
                          metric=index.metric)
    else:
        raise TypeError(
            f"distributed.ann.health_check: unsupported index type "
            f"{type(index).__name__}")
    # the local view serves the parent's data snapshot: carry its
    # generation so generation-keyed executable caches stay distinct
    out.generation = _mutate.generation(index)
    return out


def health_check(handle, index, *, raise_on_fail: bool = True,
                 health=None):
    """Re-search every shard's stored recall canaries and compare against
    the stored floor (see :func:`raft_tpu.integrity.health_check`).

    Returns a list with one :class:`~raft_tpu.integrity.CanaryReport`
    (or ``None``) per shard, or ``None`` when the index carries no
    canaries.  With ``raise_on_fail`` (default) the first failing shard
    raises :class:`~raft_tpu.integrity.IntegrityError` — the error names
    the shard in its message.

    ``health`` (a :class:`raft_tpu.distributed.health.HealthTracker`)
    consumes the verdicts: a failing shard's canary notes a canary
    failure (ticking ``integrity.canary_failure`` with the shard id),
    a passing shard notes OK — repeated failures drive the shard
    through SUSPECT into FAILED, repeated passes clear SUSPECT back to
    HEALTHY.  On the routed path the global canary set cannot localize
    the failure; its verdict is attributed to every shard not already
    HEALTHY (the suspects are the plausible culprits), or to all shards
    when none is suspect."""
    from raft_tpu.integrity import IntegrityError
    from raft_tpu.integrity import canary as _canary

    def _note(shard, passed):
        if health is None:
            return
        if passed:
            health.note_ok(shard)
        else:
            health.note_canary_failure(shard)

    if isinstance(index, RoutedIndex):
        # routed indexes carry ONE global canary set (the quantizer is
        # global); the routed search is globally exact, so the standard
        # single-index health check applies — it dispatches the search
        # through this module (canary._search_canaries)
        if index.canaries is None:
            return None
        try:
            report = _canary.health_check(handle, index,
                                          raise_on_fail=raise_on_fail)
        except IntegrityError:
            for s in _blame_shards(index.n_shards, health):
                _note(s, False)
            raise
        passed = report is None or report.ok
        targets = (range(index.n_shards) if passed
                   else _blame_shards(index.n_shards, health))
        for s in targets:
            _note(s, passed)
        return [report]
    cans = getattr(index, "shard_canaries", None)
    if cans is None:
        return None
    reports = []
    for s, cs in enumerate(cans):
        if cs is None:
            reports.append(None)
            continue
        local = _local_index(index, s)
        local.canaries = cs
        try:
            report = _canary.health_check(
                handle, local, raise_on_fail=raise_on_fail)
        except IntegrityError as e:
            _note(s, False)
            raise IntegrityError(f"shard {s}: {e}",
                                 invariant=e.invariant,
                                 coord=(s,) + tuple(e.coord or ())) from e
        _note(s, report is None or report.ok)
        reports.append(report)
    return reports


def _blame_shards(n_shards: int, health) -> Tuple[int, ...]:
    """Shards a non-localizable (global-canary) failure is attributed
    to: the tracker's non-HEALTHY shards when any exist — the plausible
    culprits — else every shard."""
    if health is not None:
        suspects = tuple(s for s in range(n_shards)
                         if health.state(s) != "HEALTHY")
        if suspects:
            return suspects
    return tuple(range(n_shards))
