"""Bucket-shaped search executors over the existing indexes.

One executor wraps one searchable index (IVF-PQ / IVF-Flat / CAGRA /
brute force, or a :mod:`raft_tpu.distributed.ann` sharded index) and
serves a CLOSED set of (batch, k) shapes — the buckets.  ``warmup()``
compiles every bucket once at server start; after that, a dispatch at
any bucket shape is a cache hit (zero recompiles, the steady-state
contract the serving bench asserts via the ``xla.compiles`` counter).

Two warm paths:

``"aot"`` (default for IVF-PQ / IVF-Flat / brute force)
    Executables come from :func:`raft_tpu.core.aot.executables` — the
    index is exported per bucket (StableHLO) and reloaded, the same
    artifact shape a compile-free deployment process would load.  Falls
    back to ``"jit"`` per bucket when an exporter refuses (e.g. CAGRA's
    calibration-dependent fallback walk).
``"jit"``
    The live module search functions, warmed by calling each bucket
    shape once.  The choice for distributed indexes (the cross-shard
    merge is a shard_map closure over a mesh, not exportable) —
    degraded-mode shard masking and post-load ``health_check`` compose
    unchanged because the executor calls the same public entry points.
    Since round 10 group construction under the routed path is
    shape-static (a static group capacity rides in the compiled shape
    instead of a host-synced count), so a warmed distributed bucket
    dispatches with ZERO host syncs — same steady-state contract as the
    local AOT path.  Per-shard routed programs (including the fused
    grouped scan at static capacity) ARE exportable individually via
    :class:`~raft_tpu.core.aot.ExecutableCache` kind ``"ivf_pq_routed"``
    — see :meth:`DistributedExecutor.prewarm_shard_artifacts`.

Padded rows are flagged through the integrity mask path
(:func:`~raft_tpu.integrity.boundary.mask_search_outputs`): id -1 /
worst distance, exactly like a masked non-finite row.
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import observability as obs
from raft_tpu.core.aot import executables as _aot_executables
from raft_tpu.core.error import expects
from raft_tpu.observability import flight as _flight
from raft_tpu.distance.types import DistanceType
from raft_tpu.filters import SampleFilter
from raft_tpu.filters import bitset as _fbits
from raft_tpu.integrity import boundary as _boundary
from raft_tpu.neighbors import delta as _delta
from raft_tpu.serving.buckets import bucket_sizes, pad_rows, valid_rows_mask

_KINDS = ("ivf_pq", "ivf_flat", "cagra", "brute_force")


class Executor:
    """Warmed bucket-shaped search over one local index.

    ``search_params`` is the algorithm's SearchParams (n_probes etc.) —
    fixed for the executor's lifetime, part of every bucket's compiled
    shape.  ``ks`` is the closed set of supported k values.

    ``ladder`` (brownout, PR 12) is an optional sequence of ADDITIONAL
    SearchParams variants — the degraded operating points the brownout
    controller steps through under overload.  Rung 0 is always
    ``search_params`` (full quality); rung ``i`` serves ``ladder[i-1]``.
    The rung set is closed and part of every warmed shape: ``warmup()``
    compiles every (bucket, k, rung) once, and the rung joins the AOT
    cache key (see :meth:`ExecutableCache.get`), so a brownout
    transition is a dict lookup — zero recompiles, zero host syncs.
    """

    def __init__(self, res, kind: str, index, *, ks: Sequence[int] = (10,),
                 max_batch: int = 1024, search_params=None,
                 ladder: Sequence = (), warm: str = "aot",
                 filter_rows: int = 0) -> None:
        expects(kind in _KINDS,
                f"serving: unknown executor kind {kind!r} (one of {_KINDS})")
        expects(warm in ("aot", "jit"),
                f"serving: warm mode must be 'aot' or 'jit', got {warm!r}")
        self.res = res
        self.kind = kind
        self.index = index
        self.ks = tuple(int(k) for k in ks)
        self.max_batch = int(max_batch)
        self.params = search_params
        self._rung_params: Tuple = (search_params, *ladder)
        self.warm = warm
        # filtered serving (PR 20): filter_rows > 0 declares the id
        # space admission bitsets cover; every warmed executable then
        # takes a (bucket, n_filter_words) int32 words input — data, not
        # shape, so one compiled program serves every predicate
        # (all-ones words = unfiltered).  0 keeps the one-input shapes.
        self.filter_rows = int(filter_rows)
        self.n_filter_words = (_fbits.n_words_for(self.filter_rows)
                               if self.filter_rows else 0)
        self._ones_words: Dict[int, jax.Array] = {}
        self.buckets = bucket_sizes(self.max_batch)
        self._fns: Dict[Tuple[int, int, int], Callable] = {}
        self._delta = None
        self._warmed = False

    @property
    def n_rungs(self) -> int:
        """Number of degradation-ladder operating points (>= 1)."""
        return len(self._rung_params)

    def set_ladder(self, ladder: Sequence) -> None:
        """Install the degraded-rung SearchParams variants (rungs 1..N).
        Must happen before :meth:`warmup` — the rung set is part of the
        closed warmed-shape contract, so growing it later would put a
        compile on the serving path."""
        expects(not self._warmed,
                "serving: set_ladder after warmup would break the "
                "zero-recompile contract — declare the ladder before "
                "Server.start()")
        self._rung_params = (self.params, *ladder)

    def attach_delta(self, view: Callable) -> None:
        """Attach the streaming-ingest delta tier: ``view`` is a
        zero-arg callable (``Memtable.device_view``) returning the
        shape-static ``(data, ids, tombs)`` snapshot.  Every
        :meth:`search_bucket` then merges the memtable as one more
        "shard" through the k-bounded ``finalize_topk`` epilogue, with
        tombstones masking main-index hits through the id<0 seam.  Must
        happen before :meth:`warmup` — the merge joins every warmed
        bucket shape, keeping steady state compile-free."""
        expects(not self._warmed,
                "serving: attach_delta after warmup would break the "
                "zero-recompile contract — attach the ingest tier before "
                "Server.start()")
        self._delta = view

    # ---- geometry -------------------------------------------------------

    @property
    def dim(self) -> int:
        return self._index_dim(self.index)

    def _index_dim(self, index) -> int:
        if self.kind == "brute_force":
            return int(index.shape[1])
        return int(index.dim)

    @property
    def select_min(self) -> bool:
        metric = getattr(self.index, "metric", DistanceType.L2Expanded)
        return metric != DistanceType.InnerProduct

    @property
    def query_dtype(self):
        if self.kind == "brute_force":
            return self.index.dtype
        if self.kind == "cagra":
            return self.index.dataset.dtype
        return self.index.centers.dtype

    # ---- warmup ---------------------------------------------------------

    def warmup(self) -> int:
        """Compile every (bucket, k, rung) once; returns the number of
        warmed executables.  Idempotent."""
        if self._warmed:
            return len(self._fns)
        for b in self.buckets:
            for k in self.ks:
                for r in range(self.n_rungs):
                    zeros = jnp.zeros((b, self.dim), self.query_dtype)
                    # b-1 valid rows also warms the padded-row mask ops at
                    # this bucket shape (mask shape is n_valid-independent)
                    d, i = self.search_bucket(zeros, max(1, b - 1), k,
                                              rung=r)
                    jax.block_until_ready((d, i))
                    if obs.enabled():
                        obs.registry().counter(
                            "serving.warmed_executables").inc()
        self._warmed = True
        return len(self._fns)

    def _obtain(self, bucket: int, k: int, rung: int = 0) -> Callable:
        key = (bucket, k, rung)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        fn = self._build_fn(self.index, bucket, k, rung)
        self._fns[key] = fn
        return fn

    def _build_fn(self, index, bucket: int, k: int, rung: int = 0
                  ) -> Callable:
        """One bucket executable against an EXPLICIT index — the builder
        :meth:`swap_index` uses to assemble a replacement table without
        touching the published one."""
        params = self._rung_params[rung]
        fn = None
        if self.warm == "aot":
            try:
                fn = self._aot_fn(index, bucket, k, params, rung)
            except Exception as e:  # noqa: BLE001 - exporter refusal
                warnings.warn(
                    f"serving: AOT export failed for {self.kind} bucket "
                    f"({bucket}, {k}, rung {rung}) — falling back to live "
                    f"search: {e}",
                    stacklevel=2)
        if fn is None:
            fn = self._live_fn(index, k, params)
        return fn

    def _aot_fn(self, index, bucket: int, k: int, params, rung: int
                ) -> Callable:
        cache = _aot_executables()
        # the filter-buffer width joins the export kwargs (and so the
        # cache key) — its shape depends only on the declared id bound,
        # never on filter contents, so the key stays bucket-shaped
        fkw = ({"n_filter_words": self.n_filter_words}
               if self.n_filter_words else {})
        if self.kind == "ivf_pq":
            n_probes = min(params.n_probes, index.n_lists)
            mode = getattr(params, "scan_mode", "auto")
            if mode not in ("recon", "codes", "lut", "fused"):
                mode = ("recon" if index.list_recon is not None
                        else "lut")
            return cache.get("ivf_pq", self.res, index, batch=bucket,
                             k=k, n_probes=n_probes, scan_mode=mode,
                             rung=rung, **fkw)
        if self.kind == "ivf_flat":
            n_probes = min(params.n_probes, index.n_lists)
            return cache.get("ivf_flat", self.res, index, batch=bucket,
                             k=k, n_probes=n_probes, rung=rung, **fkw)
        if self.kind == "brute_force":
            return cache.get("brute_force", self.res, index,
                             batch=bucket, k=k, rung=rung, **fkw)
        # cagra: export when the packed walk calibrates, else live
        itopk = max(getattr(params, "itopk_size", 64), k)
        width = getattr(params, "search_width", 1)
        return cache.get("cagra", self.res, index, batch=bucket, k=k,
                         rung=rung, itopk=itopk, search_width=width,
                         **fkw)

    def _live_fn(self, index, k: int, params) -> Callable:
        # live module entry points under validation policy "off": the
        # server already boundary-checked each request at submit, and
        # padded zero rows must not be re-flagged.  The closure captures
        # the index ARGUMENT (not self.index) so a built fn table stays
        # pinned to the generation it was built against.
        from raft_tpu import config

        if self.kind == "ivf_pq":
            from raft_tpu.neighbors import ivf_pq as mod
        elif self.kind == "ivf_flat":
            from raft_tpu.neighbors import ivf_flat as mod
        elif self.kind == "cagra":
            from raft_tpu.neighbors import cagra as mod
        else:
            from raft_tpu.neighbors import brute_force

            if self.n_filter_words:
                n_rows = self.filter_rows

                def bf_f(queries, fw):
                    with config.validation_policy("off"):
                        return brute_force.knn(
                            self.res, index, queries, k,
                            filter=SampleFilter.from_words(fw, n_rows))
                return bf_f

            def bf(queries):
                with config.validation_policy("off"):
                    return brute_force.knn(self.res, index, queries, k)
            return bf

        if self.n_filter_words:
            n_rows = self.filter_rows

            def live_f(queries, fw):
                with config.validation_policy("off"):
                    return mod.search(
                        self.res, params, index, queries, k,
                        filter=SampleFilter.from_words(fw, n_rows))
            return live_f

        def live(queries):
            with config.validation_policy("off"):
                return mod.search(self.res, params, index,
                                  queries, k)
        return live

    # ---- generation swap ------------------------------------------------

    def swap_index(self, new_index) -> int:
        """Swap in a new index generation without a serving gap.

        Builds a COMPLETE replacement executable table against
        ``new_index`` and (when the executor was warmed) warms every
        (bucket, k) with a zero batch before anything is published; the
        swap itself is one tuple assignment of ``(index, _fns)``, atomic
        under the GIL.  In-flight :meth:`search_bucket` calls captured
        the old table on entry and finish on the generation they started
        on; calls arriving after the swap see only the new one — no
        reader ever observes a mixed table, and steady-state traffic
        after the swap recompiles nothing.  Returns the number of bucket
        executables built."""
        expects(new_index is not None, "serving: swap_index needs an index")
        dim = self._index_dim(new_index)
        expects(dim == self.dim,
                f"serving: swap_index dim mismatch ({dim} != {self.dim})")
        fns: Dict[Tuple[int, int, int], Callable] = {}
        for b in self.buckets:
            for k in self.ks:
                for r in range(self.n_rungs):
                    fn = self._build_fn(new_index, b, k, r)
                    if self._warmed:
                        zeros = jnp.zeros((b, dim), self.query_dtype)
                        if self.n_filter_words:
                            jax.block_until_ready(
                                fn(zeros, self._all_ones_words(b)))
                        else:
                            jax.block_until_ready(fn(zeros))
                    fns[(b, k, r)] = fn
        self.index, self._fns = new_index, fns
        if obs.enabled():
            obs.registry().counter("serving.generation_swaps").inc()
        # always-on flight event: a generation swap is exactly the kind of
        # state change a post-mortem needs to see next to shed/error events
        _flight.record_event("serving.generation_swap",
                             generation=getattr(new_index, "generation",
                                                None),
                             executables=len(fns))
        return len(fns)

    # ---- the hot path ---------------------------------------------------

    def _all_ones_words(self, bucket: int) -> jax.Array:
        """The cached admit-everything words buffer for ``bucket`` —
        what an unfiltered dispatch feeds a filter-configured executor
        so every dispatch shares ONE compiled shape."""
        w = self._ones_words.get(bucket)
        if w is None:
            w = jnp.full((bucket, self.n_filter_words), -1, jnp.int32)
            self._ones_words[bucket] = w
        return w

    def search_bucket(self, queries, n_valid: int, k: int, rung: int = 0,
                      filter_words=None) -> Tuple[jax.Array, jax.Array]:
        """Search a padded bucket batch; rows past ``n_valid`` come back
        masked (id -1 / worst distance) through the integrity mask path.
        ``rung`` selects the degradation-ladder operating point (0 =
        full quality); every rung is warmed, so the selection is a dict
        lookup, never a compile.

        ``filter_words`` is the batch's packed admission bitset
        ``(bucket, n_filter_words)`` int32 — only meaningful on an
        executor constructed with ``filter_rows > 0`` (None there means
        admit everything via the cached all-ones buffer; filters are
        data, so either way it is the same warmed executable)."""
        bucket = queries.shape[0]
        expects(0 <= rung < self.n_rungs,
                f"serving: rung {rung} outside the declared ladder "
                f"(n_rungs={self.n_rungs})")
        expects(filter_words is None or self.n_filter_words > 0,
                "serving: executor not configured for filters — "
                "construct with filter_rows=<id bound>")
        # one capture of the published table: a concurrent swap_index
        # replaces self._fns wholesale, so everything below dispatches
        # against a single consistent generation
        fns = self._fns
        fn = fns.get((bucket, k, rung))
        expects(fn is not None or not self._warmed,
                f"serving: shape ({bucket}, {k}, rung {rung}) is not a "
                f"warmed bucket")
        if fn is None:
            fn = self._obtain(bucket, k, rung)
        fw = None
        if self.n_filter_words:
            fw = (filter_words if filter_words is not None
                  else self._all_ones_words(bucket))
            expects(fw.shape == (bucket, self.n_filter_words),
                    f"serving: filter words shape {fw.shape} != "
                    f"({bucket}, {self.n_filter_words})")
            d, i = fn(queries, fw)
        else:
            d, i = fn(queries)
        delta = self._delta
        if delta is not None:
            data, ids, tombs = delta()
            d, i = _delta.merge_with_main(
                d, i, queries, data, ids, tombs, k=k,
                metric=getattr(self.index, "metric",
                               DistanceType.L2Expanded),
                filter_words=fw)
        if n_valid < bucket:
            d, i = _boundary.mask_search_outputs(
                d, i, valid_rows_mask(n_valid, bucket),
                select_min=self.select_min)
        return d, i

    def pad(self, queries, bucket: int):
        return pad_rows(queries, bucket)

    # ---- introspection --------------------------------------------------

    def operating_knobs(self, rung: int = 0) -> Dict[str, object]:
        """The closed-shape coordinates this executor serves ``rung``
        at — the knob half of an operating-point record (see
        :class:`raft_tpu.observability.quality.OpPoint`).  Keys absent
        from the rung's SearchParams come back None (e.g. brute force
        has no probes)."""
        expects(0 <= rung < self.n_rungs,
                f"serving: rung {rung} outside the declared ladder "
                f"(n_rungs={self.n_rungs})")
        params = self._rung_params[rung]
        # a None rung inherits the previous rung's params (the shed-only
        # ladder idiom) — walk back to the operative point
        r = rung
        while params is None and r > 0:
            r -= 1
            params = self._rung_params[r]
        mw = getattr(params, "merge_window", None)
        return {
            "kind": self.kind,
            "bucket": self.max_batch,
            "rung": int(rung),
            "n_probes": getattr(params, "n_probes", None),
            "scan_mode": getattr(params, "scan_mode", None),
            "kt": getattr(params, "per_probe_topk", None),
            "merge_window": mw if isinstance(mw, (int, str,
                                                  type(None))) else str(mw),
            "filtered": bool(self.n_filter_words),
        }


class DistributedExecutor(Executor):
    """Executor over a :mod:`raft_tpu.distributed.ann` sharded index —
    both placements: the data-parallel :class:`DistributedIndex` and the
    routed-probe :class:`RoutedIndex` (``placement="by_list"``), whose
    search routes each query's probes to owning shards via the
    replicated placement map.

    Always ``warm="jit"`` (the cross-shard merge is a shard_map closure,
    not exportable).  The resilience surface passes through untouched:
    ``failed_shards`` / fault-plan masking and per-shard status behave
    exactly as in direct :func:`raft_tpu.distributed.ann.search` calls,
    and post-load :func:`raft_tpu.distributed.ann.health_check` works on
    the wrapped index because the executor never copies or re-wraps it.
    Under ``by_list`` a ``swap_index`` to a rebalanced snapshot is the
    global generation barrier: the warmed fn table is rebuilt completely
    against the new placement before the single atomic swap, so no
    request ever mixes placements.

    Zero-sync steady state (round 10): ``scan_mode="fused"`` lowers
    under shard_map at a static group capacity, so a warmed bucket
    dispatch reads nothing back to the host.  The one exception is an
    index calibrated with a tightened capacity
    (:func:`raft_tpu.neighbors.ivf_pq.calibrate_group_capacity`): its
    dispatch carries an in-graph overflow flag whose single host read
    gates the exact re-dispatch — uncalibrated indexes run at the exact
    worst bound and never read it.

    ``routing`` (a :class:`raft_tpu.distributed.routing.RoutingPolicy`)
    adds load-aware replica selection with **per-bucket replica
    groups**: when the executor builds its warmed fn table it consults
    ``routing.spread_bucket(bucket)`` per ``(bucket, k)`` — hot
    small-batch buckets close over the policy (every dispatch plans
    least-loaded replica tables; data-parallel across the ranks) while
    memory-bound large-batch buckets close over ``None`` (pinned at
    the rank-0 primary).  The choice is baked into the fn-table
    closure, NOT the executable cache key: routing tables are runtime
    data, so both groups share the same warmed shapes and the AOT /
    executable cache key is unchanged.
    """

    def __init__(self, handle, index, *, ks: Sequence[int] = (10,),
                 max_batch: int = 1024, search_params=None,
                 failed_shards: Sequence[int] = (),
                 routing=None, filter_rows: int = 0) -> None:
        self.handle = handle
        self.failed_shards = tuple(failed_shards)
        self.routing = routing
        super().__init__(handle, "ivf_pq", index, ks=ks,
                         max_batch=max_batch, search_params=search_params,
                         warm="jit", filter_rows=filter_rows)
        self._feed_routing_rows(index)

    def _index_dim(self, index) -> int:
        # rotation is (n_dev, dim, rot_dim) stacked (by_row) or
        # (dim, rot_dim) replicated (by_list) — [-2] is dim in both
        return int(index.rotation.shape[-2])

    @property
    def query_dtype(self):
        centers = getattr(self.index, "coarse_centers", None)
        if centers is None:
            centers = self.index.centers
        return centers.dtype

    def _aot_fn(self, index, bucket: int, k: int, params, rung: int
                ) -> Callable:
        raise NotImplementedError("distributed indexes are jit-warmed")

    # ---- per-bucket replica groups --------------------------------------

    def _bucket_routing(self, bucket: int):
        """The bucket→replica-group map: the routing policy for hot
        buckets (spread across replica ranks), None for memory-bound
        ones (pinned at the primary)."""
        r = self.routing
        if r is None:
            return None
        return r if r.spread_bucket(bucket) else None

    def _build_fn(self, index, bucket: int, k: int, rung: int = 0
                  ) -> Callable:
        # the replica-group choice is made HERE, per (bucket, k, rung),
        # and baked into the fn-table closure — the warmed shapes and
        # the executable cache key never see it (routing tables are
        # runtime data, not shape)
        params = self._rung_params[rung]
        return self._routed_fn(index, k, params,
                               self._bucket_routing(bucket))

    def _feed_routing_rows(self, index) -> None:
        # per-list probe cost for the policy's expected-work weights —
        # read once per build/swap (never on the dispatch path).  The
        # routed scans run over PADDED list slabs (every probe touches
        # the full (cap,) slot row regardless of live rows), so the
        # honest per-probe cost is the slab capacity — uniform across
        # lists, which makes the plan weight pure measured heat
        r = self.routing
        placement = getattr(index, "placement", None)
        li = getattr(index, "list_indices", None)
        if r is None or placement is None or li is None:
            return
        n_lists = int(np.asarray(placement.owner).shape[0])
        r.note_list_rows(np.full(n_lists, float(li.shape[-1])))

    def swap_index(self, new_index) -> int:
        n = super().swap_index(new_index)
        self._feed_routing_rows(new_index)
        return n

    def prewarm_shard_artifacts(self, scan_mode: str = "fused") -> int:
        """Load one PER-SHARD routed executable per (bucket, k, shard)
        into the process :class:`~raft_tpu.core.aot.ExecutableCache`
        (kind ``"ivf_pq_routed"``) so a single-shard deployment process
        answers its first request compile-free.

        Only meaningful for ``by_list`` (:class:`RoutedIndex`) indexes —
        data-parallel placements return 0.  For ``scan_mode="fused"``
        each artifact bakes the grouped scan at the STATIC group
        capacity for its bucket shape; that capacity rides in the cache
        key via the export kwargs, so re-warming after a bucket change
        never aliases a stale group count.  A replicated placement
        (``replication_factor > 1``) warms one executable per REPLICA
        RANK as well — a failover that promotes a shard's rank-j tables
        must hit a warmed program, not a first-request compile (the
        rank joins the cache key via the export kwargs).  Returns the
        number of cached shard executables."""
        index = self.index
        if getattr(index, "local_centers", None) is None:
            return 0
        from raft_tpu.neighbors import grouped

        cache = _aot_executables()
        n_probes = min(self.params.n_probes, index.n_lists)
        slots = int(index.local_centers.shape[1])
        rf = (index.placement.replication_factor
              if getattr(index, "placement", None) is not None else 1)
        n = 0
        for b in self.buckets:
            cap = grouped.group_capacity(b, n_probes, slots)[0]
            for k in self.ks:
                for s in range(index.n_shards):
                    for rank in range(rf):
                        kwargs = {"shard": s}
                        if scan_mode == "fused":
                            kwargs["group_capacity"] = cap
                        if rank > 0:
                            kwargs["replica_rank"] = rank
                        cache.get("ivf_pq_routed", self.handle, index,
                                  batch=b, k=k, n_probes=n_probes,
                                  scan_mode=scan_mode, **kwargs)
                        n += 1
        return n

    def _live_fn(self, index, k: int, params) -> Callable:
        return self._routed_fn(index, k, params, self.routing)

    def _routed_fn(self, index, k: int, params, routing) -> Callable:
        from raft_tpu import config
        from raft_tpu.distributed import ann

        if self.n_filter_words:
            n_rows = self.filter_rows

            def live_f(queries, fw):
                with config.validation_policy("off"):
                    return ann.search(
                        self.handle, params, index, queries, k,
                        failed_shards=self.failed_shards,
                        routing=routing,
                        filter=SampleFilter.from_words(fw, n_rows))
            return live_f

        def live(queries):
            with config.validation_policy("off"):
                return ann.search(self.handle, params, index,
                                  queries, k,
                                  failed_shards=self.failed_shards,
                                  routing=routing)
        return live
