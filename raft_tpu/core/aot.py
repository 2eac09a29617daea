"""AOT export of compiled entry points — the instantiation-layer analogue.

Reference: cpp/src's 139 precompiled template instantiation units +
pylibraft's prebuilt wheels give RAFT a compile-free deployment story.
The TPU-native equivalent is **StableHLO export**: trace + lower a
jitted entry point once, serialize the portable artifact
(`jax.export`), and reload it in a process that never imports the
algorithm's Python (or pays its trace time).  Artifacts are
version-stable across jax releases per the StableHLO compatibility
guarantees and are compiled (not re-traced) on load.

Usage::

    from raft_tpu.core import aot
    blob = aot.export_fn(fn, example_args)         # bytes
    g = aot.load_fn(blob)                          # callable
    out = g(*args)                                 # same shapes/dtypes

`save_search_fn` / `load_search_fn` wrap the ANN flagship: a
searchable IVF-PQ index becomes one self-contained artifact (index
arrays + exported search program) — the deployment shape of the
reference's serialized index + prebuilt kernels.
"""

from __future__ import annotations

import io
import threading
import weakref
from typing import BinaryIO, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import export as jax_export

from raft_tpu.core.error import expects

_MAGIC = b"RAFT_TPU_AOT1"


def export_fn(fn: Callable, example_args: Sequence) -> bytes:
    """Lower + serialize ``jit(fn)`` for the example args' shapes/dtypes.

    ``fn`` must be jit-compatible; the artifact is specialized to the
    example shapes (the reference's instantiation grid is likewise
    shape-specialized — one unit per (T, IdxT, dims...) combination).
    """
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
        if not hasattr(a, "shape") else jax.ShapeDtypeStruct(a.shape, a.dtype),
        tuple(example_args))
    exp = jax_export.export(jax.jit(fn))(*shapes)
    return bytes(exp.serialize())


def load_fn(blob: bytes) -> Callable:
    """Deserialize an exported entry point into a callable."""
    exp = jax_export.deserialize(blob)

    def call(*args):
        return exp.call(*args)

    return call


def save_search_fn(stream: BinaryIO, fn: Callable, arrays: Sequence,
                   example_queries) -> None:
    """One-file deployment artifact: captured arrays + exported program.

    ``fn(arrays..., *runtime) -> (distances, indices)``; ``arrays`` are
    baked into the artifact (host numpy).  ``example_queries`` is the
    runtime input — a single queries example, or a tuple of runtime
    inputs (e.g. ``(queries, filter_words)`` for a filtered export); the
    loaded callable takes them positionally.
    """
    import jax.numpy as jnp

    runtime = (example_queries if isinstance(example_queries, tuple)
               else (example_queries,))
    blob = export_fn(fn, tuple(arrays) + runtime)
    # non-executable container on purpose: npz for the arrays + a
    # length-prefixed raw program blob (a pickle payload would execute
    # arbitrary code when loading an untrusted artifact).  bf16 has no
    # numpy representation; it rides as a uint16 view + dtype manifest.
    stream.write(_MAGIC)
    stream.write(len(blob).to_bytes(8, "little"))
    stream.write(blob)
    metas, store = [], {}
    for i, a in enumerate(arrays):
        a = jnp.asarray(a)
        if a.dtype == jnp.bfloat16:
            store[f"a{i}"] = np.asarray(
                jax.lax.bitcast_convert_type(a, jnp.uint16))
            metas.append("bfloat16")
        else:
            store[f"a{i}"] = np.asarray(a)
            metas.append("native")
    store["dtypes"] = np.asarray(metas)
    np.savez(stream, **store)


def load_search_fn(stream: BinaryIO) -> Callable:
    """Load a :func:`save_search_fn` artifact; returns ``g(queries)``."""
    magic = stream.read(len(_MAGIC))
    expects(magic == _MAGIC, "aot: not a raft_tpu AOT artifact")
    blob_len = int.from_bytes(stream.read(8), "little")
    call = load_fn(stream.read(blob_len))
    import jax.numpy as jnp

    with np.load(stream, allow_pickle=False) as payload:
        metas = [str(s) for s in payload["dtypes"]]
        arrays = []
        for i, meta in enumerate(metas):
            a = jnp.asarray(payload[f"a{i}"])
            if meta == "bfloat16":
                a = jax.lax.bitcast_convert_type(a, jnp.bfloat16)
            arrays.append(a)

    def g(*runtime):
        return call(*arrays, *runtime)

    return g


# ---------------------------------------------------------------------------
# executable cache — bucket-shaped warm executors for the serving layer
# ---------------------------------------------------------------------------

class ExecutableCache:
    """Process cache of loaded search executables, keyed per bucket shape.

    The serving layer pre-warms one executable per *bucket* — the same
    index exported at several batch sizes (1, 2, 4, ... max_batch).  The
    cache key therefore includes EVERY shape the export was specialized
    to: ``(kind, index identity, batch, k, n_probes, extra...)``.  Keying
    on the index alone (the obvious first cut) collides the buckets —
    every bucket would get the executable of whichever batch size warmed
    first, and steady-state traffic at the other sizes would re-trace.

    Index identity is ``id(index)`` *validated through a weakref*: a hit
    whose stored referent is no longer the keyed object (the id was
    recycled after a gc) is treated as a miss and re-exported, so a dead
    index can never serve another index's executables.

    Loaded callables dispatch through jax's primitive cache keyed on the
    (stable) exported-program identity and argument avals: the serving
    warmup calls each bucket's executable once, after which steady-state
    traffic at any warmed bucket shape triggers zero recompiles.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: Dict[Tuple, Tuple[weakref.ref, Callable]] = {}

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(self, kind: str, res, index, *, batch: int, k: int,
            n_probes: int = 0, scan_mode: Optional[str] = None,
            rung: int = 0, **export_kwargs) -> Callable:
        """The warmed ``g(queries) -> (distances, indices)`` for one
        bucket, exporting + loading on first use.

        ``kind`` is one of ``"ivf_pq" | "ivf_flat" | "brute_force" |
        "cagra"``; ``batch`` is the bucket's (padded) query count and is
        part of the cache key.  ``rung`` is the serving degradation-
        ladder position (brownout, PR 12): it joins the cache key — like
        ``scan_mode`` — but is NOT forwarded to the exporter, so two
        rungs that happen to share search parameters still get distinct
        warmed entries and a brownout transition can never alias a
        colder rung onto a warm one.  Extra keyword arguments are
        forwarded to the exporter (and keyed on, sorted by name).
        """
        extra = tuple(sorted(export_kwargs.items()))
        # generation rides in the key alongside the id()+weakref identity
        # check: a mutated index is a NEW object (delete/extend/compact
        # return fresh snapshots), but keying the generation explicitly
        # keeps a recycled id() from ever pairing a stale executable with
        # a newer generation, and makes swap-time invalidation exact.
        # by_list indexes additionally key their PLACEMENT generation: a
        # rebalance that moves lists between shards invalidates every
        # per-shard executable even if no row was mutated
        placement_gen = int(getattr(getattr(index, "placement", None),
                                    "generation", 0) or 0)
        key = (kind, id(index), int(getattr(index, "generation", 0) or 0),
               placement_gen, int(batch), int(k), int(n_probes),
               scan_mode, int(rung), extra)
        from raft_tpu import observability as obs
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None and hit[0]() is index:
                if obs.enabled():
                    obs.registry().counter("aot.cache.hits").inc()
                return hit[1]
        if obs.enabled():
            obs.registry().counter("aot.cache.misses").inc()
        # always-on flight event: a miss outside warmup/swap means an
        # export+compile on the serving path — exactly the "why did p99
        # spike" answer a flight dump should contain
        from raft_tpu.observability import flight as _flight
        _flight.record_event("aot.cache_miss", kind=kind, batch=int(batch),
                             k=int(k), n_probes=int(n_probes),
                             scan_mode=scan_mode)
        g = self._export_load(kind, res, index, batch=batch, k=k,
                              n_probes=n_probes, scan_mode=scan_mode,
                              **export_kwargs)
        with self._lock:
            self._entries[key] = (weakref.ref(index), g)
        return g

    def _export_load(self, kind: str, res, index, *, batch: int, k: int,
                     n_probes: int, scan_mode: Optional[str],
                     **export_kwargs) -> Callable:
        if kind == "ivf_pq":
            buf = export_ivf_pq_search(
                res, index, n_probes=n_probes, k=k, batch=batch,
                scan_mode=scan_mode or "recon", **export_kwargs)
        elif kind == "ivf_pq_routed":
            # per-shard routed program; `shard` (and, for the fused scan,
            # `group_capacity`) arrive via export_kwargs and are part of
            # the cache key like every other export specialization
            buf = export_ivf_pq_routed_search(
                res, index, n_probes=n_probes, k=k, batch=batch,
                scan_mode=scan_mode or "recon", **export_kwargs)
        elif kind == "ivf_flat":
            buf = export_ivf_flat_search(res, index, n_probes=n_probes,
                                         k=k, batch=batch, **export_kwargs)
        elif kind == "brute_force":
            buf = export_brute_force_knn(res, index, k=k, batch=batch,
                                         **export_kwargs)
        elif kind == "cagra":
            buf = export_cagra_search(res, index, k=k, batch=batch,
                                      **export_kwargs)
        else:
            expects(False, f"aot: unknown executable kind {kind!r}")
        # NOT wrapped in an outer jit: an exported call dispatches through
        # the primitive cache keyed on (exported identity, avals) — warm
        # once, then zero recompiles — while jit(g) would re-lower the
        # program with the index arrays embedded as constants (a second
        # compile AND a second copy of the index in device memory)
        return load_search_fn(buf)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


_EXECUTABLES = ExecutableCache()


def executables() -> ExecutableCache:
    """The process-global executable cache (serving warms into this)."""
    return _EXECUTABLES


def export_ivf_pq_search(res, index, n_probes: int, k: int, batch: int,
                         *, scan_mode: str = "recon",
                         group_capacity: int = 0,
                         n_filter_words: int = 0) -> io.BytesIO:
    """Export the flagship IVF-PQ search at fixed (batch, k, n_probes)
    into a self-contained artifact (reference analogue: serialized index
    + the prebuilt search instantiation).

    ``scan_mode`` picks which index representation rides in the
    artifact:

    - ``"recon"`` bakes the bf16 reconstruction cache and exports the
      recon scan (2 bytes/dim/row in the artifact — the fastest live
      formulation, also the largest file).
    - ``"fused"`` bakes the recon cache and exports the GROUPED scan at
      the static group capacity ``group_capacity`` (0 derives the
      exact-safe worst bound from (batch, n_probes, n_lists) — group
      construction is fully traceable at a static capacity since
      round 10, so the list-centric formulation exports like any other).
      The Pallas in-kernel top-k variants remain runtime dispatch paths;
      the exported XLA twin computes identical quantized distances.
      Falls back to the LUT export below when the index carries no recon
      cache.
    - ``"codes"`` / ``"lut"`` bake only the bit-packed PQ codes +
      codebooks and export the portable LUT formulation over them
      (~pq_bits/8 bytes per subspace per row — the compact deployment
      shape); it computes the same quantized distances as the codes
      kernel, so an artifact warmed under either mode answers
      identically while carrying its own distinct
      :class:`ExecutableCache` key component.

    The export makes its own representation choice: the runtime plan
    (:func:`raft_tpu.neighbors.ivf_pq.plan_scan`) picks among kernels
    an artifact cannot carry.
    """
    from raft_tpu.neighbors import grouped, ivf_pq

    expects(scan_mode in ("recon", "codes", "lut", "fused"),
            "aot: scan_mode must be 'recon', 'codes', 'lut' or 'fused'")
    metric = index.metric
    # n_filter_words > 0 adds a second runtime input: a (batch, n_words)
    # int32 packed admission bitset (raft_tpu.filters.bitset), threaded
    # through the scan's admission seam.  Filters are data, not shape —
    # one filtered artifact serves every predicate at this bucket
    # (all-ones words = unfiltered).
    nfw = int(n_filter_words)

    if scan_mode == "fused" and index.list_recon is None:
        scan_mode = "lut"
    if scan_mode in ("recon", "fused"):
        expects(index.list_recon is not None,
                "aot: index must carry the reconstruction cache")
        if index.list_recon_sq is None:
            index.list_recon_sq = ivf_pq._recon_sq(index.list_recon)

        if scan_mode == "fused":
            n_groups = int(group_capacity) or grouped.group_capacity(
                batch, n_probes, index.n_lists)[0]
            cap = int(index.capacity)
            rot = int(index.rot_dim)
            G = grouped.GROUP
            block = grouped.block_size(n_groups, G * cap * 8,
                                       cap * rot * 2, G * rot * 4)

            def fn(centers, list_recon, list_recon_sq, list_indices,
                   rotation, queries, *rt):
                probes = ivf_pq._select_clusters(centers, rotation,
                                                 queries, n_probes,
                                                 metric)
                return ivf_pq._search_impl_recon_grouped(
                    centers, list_recon, list_recon_sq, list_indices,
                    rotation, queries, probes, k, metric, n_groups,
                    block, filter_words=rt[0] if nfw else None)
        else:
            def fn(centers, list_recon, list_recon_sq, list_indices,
                   rotation, queries, *rt):
                # the precomputed norms ride in the artifact — without
                # them the exported program would recompute a full pass
                # over the recon cache per batch (they are runtime
                # inputs, not constants)
                return ivf_pq._search_impl_recon(
                    centers, list_recon, list_indices, rotation, queries,
                    k=k, n_probes=n_probes, metric=metric,
                    list_recon_sq=list_recon_sq,
                    filter_words=rt[0] if nfw else None)

        arrays = (index.centers, index.list_recon, index.list_recon_sq,
                  index.list_indices, index.rotation)
    else:
        codebook_kind = index.codebook_kind
        pq_bits = index.pq_bits

        def fn(centers, codebooks, list_codes, list_indices, rotation,
               queries, *rt):
            return ivf_pq._search_impl(
                centers, codebooks, list_codes, list_indices, rotation,
                queries, k=k, n_probes=n_probes, metric=metric,
                codebook_kind=codebook_kind, lut_dtype=jax.numpy.float32,
                pq_bits=pq_bits, filter_words=rt[0] if nfw else None)

        arrays = (index.centers, index.codebooks, index.list_codes,
                  index.list_indices, index.rotation)

    example_q = jax.ShapeDtypeStruct((batch, index.dim),
                                     index.centers.dtype)
    runtime = ((example_q, jax.ShapeDtypeStruct((batch, nfw), np.int32))
               if nfw else example_q)
    buf = io.BytesIO()
    save_search_fn(buf, fn, arrays, runtime)
    buf.seek(0)
    return buf


def export_ivf_pq_routed_search(res, index, shard: int, n_probes: int,
                                k: int, batch: int, *,
                                scan_mode: str = "recon",
                                group_capacity: int = 0,
                                replica_rank: int = 0,
                                n_filter_words: int = 0) -> io.BytesIO:
    """Export ONE shard's routed (``placement="by_list"``) search
    program at fixed (batch, k, n_probes): replicated coarse routing +
    ownership mask + the shard-local scan over the owned lists +
    shard-local top-k.  The artifact is the per-chip deployment unit of
    an index-parallel mesh — each chip loads its own shard's program,
    and the k-bounded candidate exchange/merge stays in the (tiny)
    runtime layer.  Merging every shard's exported outputs with
    ``grouped.finalize_topk`` reproduces the live
    :func:`raft_tpu.distributed.ann.search` answer exactly (the
    hierarchical-top-k argument; asserted in tests).

    ``scan_mode="recon"`` (default) bakes the probe-order recon scan.
    ``scan_mode="fused"`` bakes the grouped scan at the static group
    capacity ``group_capacity`` (0 derives the exact-safe worst bound
    from (batch, n_probes, slots) — see
    :func:`raft_tpu.neighbors.grouped.group_capacity`); group
    construction is fully traceable at a static capacity (round 10), so
    the export carries zero host syncs and the serving tier's bucket
    pre-warm covers fused routed executables like any other shape.

    ``shard_map`` itself is not exportable — this bakes the shard's
    leaves plus the replicated routing arrays (coarse centers, rotation,
    owner, local_slot) into a single-device program instead.

    ``replica_rank`` (a replicated placement only) bakes replica rank
    ``j``'s routing tables instead of the primaries': the exported
    program answers for the lists this shard owns *at that rank* — the
    artifact a deployment loads to serve a failed primary's share.  The
    shard's local leaves already hold every rank's owned lists (the slot
    layout is the union), so only the two routing arrays differ; the
    rank is part of the executable-cache key."""
    from raft_tpu.neighbors import grouped, ivf_pq

    expects(getattr(index, "placement", None) is not None,
            "aot: export_ivf_pq_routed_search needs a RoutedIndex "
            "(placement='by_list')")
    expects(0 <= shard < index.n_shards,
            f"aot: shard {shard} out of range for {index.n_shards}")
    expects(scan_mode in ("recon", "fused"),
            f"aot: export_ivf_pq_routed_search supports scan_mode "
            f"'recon' or 'fused', got {scan_mode!r}")
    expects(0 <= replica_rank < index.placement.replication_factor,
            f"aot: replica_rank {replica_rank} out of range for "
            f"replication_factor "
            f"{index.placement.replication_factor}")
    metric = index.metric
    slots = int(index.local_centers.shape[1])
    dummy = slots - 1
    # filtered routed export: the SAME (batch, n_words) bitset every
    # shard receives (filters address global row ids, so the broadcast
    # needs no per-shard slicing)
    nfw = int(n_filter_words)

    if scan_mode == "fused":
        n_groups = int(group_capacity) or grouped.group_capacity(
            batch, n_probes, slots)[0]
        cap = int(index.capacity)
        rot = int(index.rotation.shape[1])
        G = grouped.GROUP
        block = grouped.block_size(n_groups, G * cap * 8,
                                   cap * rot * 2, G * rot * 4)

        def fn(coarse, rotation, owner, local_slot, local_centers,
               list_recon, list_recon_sq, list_indices, queries, *rt):
            probes = ivf_pq._select_clusters(coarse, rotation, queries,
                                             n_probes, metric)
            owned = owner[probes] == shard
            # out-of-range sentinel (== slots): build_groups drops the
            # unowned pairs entirely (see _dist_search_routed_grouped)
            local_probes = jax.numpy.where(
                owned, local_slot[probes],
                slots).astype(jax.numpy.int32)
            return ivf_pq._search_impl_recon_grouped(
                local_centers, list_recon, list_recon_sq, list_indices,
                rotation, queries, local_probes, k, metric, n_groups,
                block, filter_words=rt[0] if nfw else None)
    else:
        def fn(coarse, rotation, owner, local_slot, local_centers,
               list_recon, list_recon_sq, list_indices, queries, *rt):
            probes = ivf_pq._select_clusters(coarse, rotation, queries,
                                             n_probes, metric)
            owned = owner[probes] == shard
            local_probes = jax.numpy.where(owned, local_slot[probes],
                                           dummy).astype(jax.numpy.int32)
            return ivf_pq._search_impl_recon(
                local_centers, list_recon, list_indices, rotation,
                queries, k=k, n_probes=n_probes, metric=metric,
                probes=local_probes, list_recon_sq=list_recon_sq,
                filter_words=rt[0] if nfw else None)

    if replica_rank > 0:
        rank_owner, rank_slot = index.placement.rank_tables()
        route = (rank_owner[replica_rank], rank_slot[replica_rank])
    else:
        route = (index.owner, index.local_slot)
    arrays = tuple(jax.device_get(a) for a in (
        index.coarse_centers, index.rotation) + route + (
        index.local_centers[shard],
        index.list_recon[shard], index.list_recon_sq[shard],
        index.list_indices[shard]))
    example_q = jax.ShapeDtypeStruct((batch, index.dim),
                                     index.coarse_centers.dtype)
    runtime = ((example_q, jax.ShapeDtypeStruct((batch, nfw), np.int32))
               if nfw else example_q)
    buf = io.BytesIO()
    save_search_fn(buf, fn, arrays, runtime)
    buf.seek(0)
    return buf


def warm_write_router(index, batches: Sequence[int]) -> int:
    """Pre-trace the distributed WRITE router (round 19) at the serving
    write-batch shapes: one jitted ``_select_clusters`` call per batch
    size with ``n_probes=1`` against the replicated coarse quantizer —
    exactly what :func:`raft_tpu.distributed.ann.route_vectors` runs per
    upsert/delete.  Called from the routed ingest tier's ``prewarm`` so
    the first write after a deploy (or the first re-routed write after a
    failover) hits a warm executable; routing tables are data, so
    placement changes never invalidate these traces.  Returns the number
    of shapes warmed."""
    from raft_tpu.distance.types import DistanceType
    from raft_tpu.neighbors import ivf_pq

    warmed = 0
    for b in sorted({int(b) for b in batches if int(b) > 0}):
        zeros = jax.numpy.zeros((b, index.dim),
                                index.coarse_centers.dtype)
        out = ivf_pq._select_clusters(index.coarse_centers,
                                      index.rotation, zeros, 1,
                                      DistanceType(index.metric))
        jax.block_until_ready(out)
        warmed += 1
    return warmed


def export_ivf_flat_search(res, index, n_probes: int, k: int,
                           batch: int, *,
                           n_filter_words: int = 0) -> io.BytesIO:
    """Export the IVF-Flat search at fixed (batch, k, n_probes): raw
    list vectors + exported scan program in one artifact (reference
    analogue: the per-(T, IdxT, veclen) interleaved-scan instantiations
    in cpp/src/neighbors/ivfflat_*).  ``n_filter_words`` > 0 adds the
    packed admission bitset as a second runtime input (see
    :func:`export_ivf_pq_search`)."""
    from raft_tpu.neighbors import ivf_flat

    metric = index.metric
    nfw = int(n_filter_words)

    def fn(centers, list_data, list_indices, queries, *rt):
        return ivf_flat._search_impl(centers, list_data, list_indices,
                                     queries, k=k, n_probes=n_probes,
                                     metric=metric,
                                     filter_words=rt[0] if nfw else None)

    example_q = jax.ShapeDtypeStruct((batch, index.dim),
                                     index.centers.dtype)
    runtime = ((example_q, jax.ShapeDtypeStruct((batch, nfw), np.int32))
               if nfw else example_q)
    buf = io.BytesIO()
    save_search_fn(buf, fn, (index.centers, index.list_data,
                             index.list_indices), runtime)
    buf.seek(0)
    return buf


def export_brute_force_knn(res, database, k: int, batch: int, *,
                           metric=None, metric_arg: float = 2.0,
                           n_filter_words: int = 0) -> io.BytesIO:
    """Export exact brute-force kNN over a fixed database at (batch, k):
    the database rides in the artifact, queries stay the runtime input
    (reference analogue: the brute_force_knn instantiation units).
    ``n_filter_words`` > 0 adds the packed admission bitset as a second
    runtime input (see :func:`export_ivf_pq_search`)."""
    from raft_tpu.distance.types import DistanceType
    from raft_tpu.neighbors import brute_force

    if metric is None:
        metric = DistanceType.L2Unexpanded
    database = jax.numpy.asarray(database)
    tile = min(brute_force._TILE_N, database.shape[0])
    nfw = int(n_filter_words)

    def fn(db, queries, *rt):
        if nfw:
            return brute_force._knn_impl(
                db, queries, k, metric, metric_arg, tile,
                filter_words=rt[0],
                id_offset=jax.numpy.int32(0))
        return brute_force._knn_impl(db, queries, k, metric, metric_arg,
                                     tile)

    example_q = jax.ShapeDtypeStruct((batch, database.shape[1]),
                                     database.dtype)
    runtime = ((example_q, jax.ShapeDtypeStruct((batch, nfw), np.int32))
               if nfw else example_q)
    buf = io.BytesIO()
    save_search_fn(buf, fn, (database,), runtime)
    buf.seek(0)
    return buf


def export_cagra_search(res, index, k: int, batch: int, *,
                        itopk: int = 64, search_width: int = 1,
                        max_iterations: int = 0,
                        walk_pdim: int = 0,
                        n_filter_words: int = 0) -> io.BytesIO:
    """Export the CAGRA packed-neighborhood walk at fixed (batch, k,
    itopk, search_width) into a self-contained artifact: walk table +
    entry set + exported walk program (reference analogue: serialized
    CAGRA index + the per-dtype prebuilt search units in
    cpp/src/neighbors/).

    The packed table and projection are calibrated/built here (the same
    lazy path the first live search takes) and baked into the artifact;
    fails when the fidelity calibration rejects every projection (the
    regime where the live search falls back to the exact direct walk —
    that path has data-dependent random seeds and is not exported).
    """
    from raft_tpu.neighbors import cagra

    itopk = max(itopk, k)
    pdim = walk_pdim or cagra._auto_pdim(index)
    expects(pdim > 0,
            "aot: walk fidelity calibration failed — no packed walk to "
            "export (the live fallback, the exact direct walk, is not "
            "exportable)")
    # same format ladder the live search uses (bf16, else the quantized
    # deep-scale format) — the exporter must cover every index the live
    # packed walk serves
    fmt = cagra._search_table_format(index, pdim)
    expects(fmt is not None,
            "aot: no packed walk table format fits the size gate")
    pdim, quant = fmt
    cache = cagra._walk_cache(res, index, pdim, max(4096, itopk),
                              quant=quant)
    max_iter = max_iterations or (10 + itopk // max(search_width, 1))
    rerank = max(min(itopk, max(32, 2 * k)), k)
    metric = index.metric
    deg = index.graph_degree
    nfw = int(n_filter_words)

    if quant:
        def fn(dataset, table, entry_proj, entry_sq, entry_ids, proj,
               scales, queries, *rt):
            return cagra._search_impl_walk(
                dataset, table, entry_proj, entry_sq, entry_ids, proj,
                queries, k, itopk, search_width, max_iter, metric,
                rerank, deg, quant=True, scales=scales,
                filter_words=rt[0] if nfw else None)

        arrays = (index.dataset, cache.table, cache.entry_proj,
                  cache.entry_sq, cache.entry_ids, cache.proj,
                  cache.scales)
    else:
        def fn(dataset, table, entry_proj, entry_sq, entry_ids, proj,
               queries, *rt):
            return cagra._search_impl_walk(
                dataset, table, entry_proj, entry_sq, entry_ids, proj,
                queries, k, itopk, search_width, max_iter, metric,
                rerank, deg, filter_words=rt[0] if nfw else None)

        arrays = (index.dataset, cache.table, cache.entry_proj,
                  cache.entry_sq, cache.entry_ids, cache.proj)

    example_q = jax.ShapeDtypeStruct((batch, index.dim),
                                     index.dataset.dtype)
    runtime = ((example_q, jax.ShapeDtypeStruct((batch, nfw), np.int32))
               if nfw else example_q)
    buf = io.BytesIO()
    save_search_fn(buf, fn, arrays, runtime)
    buf.seek(0)
    return buf
