"""IVF-Flat: inverted-file index over a balanced-k-means coarse quantizer.

Reference: raft/neighbors/ivf_flat.cuh:65 ``build``, :201 ``extend``, :389
``search``; types ivf_flat_types.hpp:44 (index_params), :76 (search_params),
:126 (index).  Build internals: detail/ivf_flat_build.cuh (kmeans_balanced fit
:336-339, predict + calc_centers_and_sizes :180-204); search:
detail/ivf_flat_search.cuh:670 ``interleaved_scan_kernel`` + select_k.

TPU design — the central impedance mismatch is the reference's *ragged*
inverted lists vs XLA's static shapes (SURVEY.md §7 "hard parts"):

- lists are stored **padded to one shared capacity** (rounded to a multiple of
  32, like the reference rounds list allocations — ivf_flat_types.hpp /
  ivf_list.hpp); slot validity comes from ``list_indices >= 0``;
- balanced k-means keeps the padding overhead bounded (that is *why* the
  reference uses a balanced quantizer: list occupancy = search cost);
- search scans the ``n_probes`` probed lists with a ``lax.scan``, each step
  one gathered (q, capacity, d) block → a batched-matmul distance + masked
  top-k merge.  The gather+matmul per probe is the TPU analogue of the
  interleaved-scan kernel: MXU does the FLOPs, the mask replaces list length.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import BinaryIO, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_types import KMeansBalancedParams
from raft_tpu.core import platform as _platform
from raft_tpu.core import serialize as ser
from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import ensure_array
from raft_tpu.core.tracing import range as named_range
from raft_tpu import observability as obs
from raft_tpu.integrity import boundary as _boundary
from raft_tpu.integrity import canary as _canary
from raft_tpu.neighbors import mutate as _mutate
from raft_tpu.distance.types import DistanceType
from raft_tpu.filters import bitset as _fbits
from raft_tpu.matrix.select_k import select_k
from raft_tpu.utils.precision import get_matmul_precision
from raft_tpu.core.outputs import auto_convert_output

_LIST_ALIGN = 32  # reference: list sizes rounded to warp multiples (ivf_list.hpp)


@dataclasses.dataclass
class IndexParams:
    """Reference: ivf_flat_types.hpp:44 ``index_params``."""

    n_lists: int = 1024
    metric: int = DistanceType.L2Expanded
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    adaptive_centers: bool = False
    add_data_on_build: bool = True
    # recall canaries (raft_tpu.integrity): > 0 samples that many sentinel
    # queries at build, stores their exact neighbors in the index, and
    # health-checks recall against the floor after load()/extend()
    canary_queries: int = 0
    canary_k: int = 10
    canary_floor: float = 0.5


@dataclasses.dataclass
class SearchParams:
    """Reference: ivf_flat_types.hpp:76 ``search_params``.

    ``coarse_recall_target`` / ``exact_coarse`` control the approx probe
    ranking (``approx_max_k``) of :func:`_select_clusters`: the recall
    target trades coarse ranking fidelity for speed, and ``exact_coarse``
    forces ``lax.top_k``.  Probe selection also falls back to the exact
    select on its own when ``n_probes`` is close to ``n_lists`` (the
    approximation saves nothing when nearly every list is probed anyway).
    Inherited by :class:`raft_tpu.neighbors.ivf_pq.SearchParams`.
    """

    n_probes: int = 20
    coarse_recall_target: float = 0.95
    exact_coarse: bool = False


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Index:
    """Reference: ivf_flat_types.hpp:126 ``index`` (centers + per-list data
    + per-list source ids + sizes).  ``list_data`` is (n_lists, capacity, dim)
    with invalid slots zero; ``list_indices`` is (n_lists, capacity) int32
    with -1 marking empty slots."""

    centers: jax.Array          # (n_lists, dim) f32
    list_data: jax.Array        # (n_lists, capacity, dim)
    list_indices: jax.Array     # (n_lists, capacity) int32
    list_sizes: jax.Array       # (n_lists,) int32
    metric: int = DistanceType.L2Expanded
    adaptive_centers: bool = False
    # Derived search-time cache: per-row squared norms (n_lists, capacity)
    # fp32, loop-invariant across searches (recomputing it per call costs
    # a full pass over the raw vectors).  Lazily attached by search().
    list_data_sq: Optional[jax.Array] = None
    # Recall-canary sentinel set (integrity.CanarySet) — host-side
    # metadata, deliberately NOT a pytree leaf (aux must stay hashable),
    # so jax transforms drop it; build/extend/serialize carry it.
    canaries: Optional[object] = None
    # Mutation generation counter (see neighbors/mutate): host-side like
    # canaries — a leaf would be wrong and aux would force a retrace per
    # mutation.  extend/delete/compact stamp parent+1 on the new index.
    generation: int = 0

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def capacity(self) -> int:
        return self.list_data.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_sizes))

    def tree_flatten(self):
        leaves = (self.centers, self.list_data, self.list_indices,
                  self.list_sizes, self.list_data_sq)
        return leaves, (self.metric, self.adaptive_centers)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves[:4], metric=aux[0], adaptive_centers=aux[1],
                   list_data_sq=leaves[4])


def _round_up(x: int, align: int) -> int:
    return -(-x // align) * align


def _pack_lists(dataset: jax.Array, labels: jax.Array, source_ids: jax.Array,
                n_lists: int, capacity: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter rows into padded per-list storage.

    The TPU analogue of the reference's list layout + fill kernels
    (detail/ivf_flat_build.cuh; codepacking in ivf_pq does the same dance):
    sort by label, compute each row's rank within its list, one scatter.
    """
    n = dataset.shape[0]
    order = jnp.argsort(labels)
    sorted_labels = labels[order]
    sizes = jax.ops.segment_sum(jnp.ones(n, jnp.int32), labels,
                                num_segments=n_lists)
    starts = jnp.cumsum(sizes) - sizes
    rank = jnp.arange(n) - starts[sorted_labels]
    list_data = jnp.zeros((n_lists, capacity, dataset.shape[1]),
                          dataset.dtype)
    list_idx = jnp.full((n_lists, capacity), -1, jnp.int32)
    list_data = list_data.at[sorted_labels, rank].set(dataset[order])
    list_idx = list_idx.at[sorted_labels, rank].set(
        source_ids[order].astype(jnp.int32))
    return list_data, list_idx, sizes


@jax.jit
def _append_lists_multi(bufs, rows, list_idx: jax.Array,
                        list_sizes: jax.Array, new_labels: jax.Array,
                        new_ids: jax.Array, lane_bufs=(), lane_rows=()):
    """Scatter-append rows into existing padded lists — the O(n_new)
    extend fast path (callers must have verified no list overflows the
    current capacity).  The reference's extend likewise appends in place
    when lists have headroom and only reallocates grown lists
    (ivf_list.hpp resize semantics).

    ``bufs``/``rows`` are matching tuples of per-list storages and their
    new rows (IVF-PQ appends codes + recon cache + recon norms in one
    pass); the slot layout is computed once and shared.  ``lane_bufs`` /
    ``lane_rows`` are lane-major (n_lists, X, capacity) storages (the
    packed-code-lane cache) whose new (n_new, X) rows scatter at
    ``[label, :, slot]``."""
    n_lists = list_sizes.shape[0]
    n_new = new_ids.shape[0]
    order = jnp.argsort(new_labels)
    sl = new_labels[order]
    new_counts = jax.ops.segment_sum(jnp.ones(n_new, jnp.int32), new_labels,
                                     num_segments=n_lists)
    starts = jnp.cumsum(new_counts) - new_counts
    slot = list_sizes[sl] + (jnp.arange(n_new) - starts[sl])
    bufs = tuple(b.at[sl, slot].set(r[order].astype(b.dtype))
                 for b, r in zip(bufs, rows))
    lane_bufs = tuple(
        b.at[sl[:, None], jnp.arange(b.shape[1])[None, :],
             slot[:, None]].set(r[order].astype(b.dtype))
        for b, r in zip(lane_bufs, lane_rows))
    list_idx = list_idx.at[sl, slot].set(new_ids[order].astype(jnp.int32))
    return bufs, lane_bufs, list_idx, list_sizes + new_counts


def _append_lists(list_data: jax.Array, list_idx: jax.Array,
                  list_sizes: jax.Array, new_rows: jax.Array,
                  new_labels: jax.Array, new_ids: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-payload convenience wrapper over _append_lists_multi."""
    (list_data,), _, list_idx, sizes = _append_lists_multi(
        (list_data,), (new_rows,), list_idx, list_sizes, new_labels,
        new_ids)
    return list_data, list_idx, sizes


def build(res, params: IndexParams, dataset) -> Index:
    """Build an IVF-Flat index (reference: ivf_flat.cuh:65).

    Trains the balanced coarse quantizer on a subsample
    (``kmeans_trainset_fraction``, as detail/ivf_flat_build.cuh:336), then
    assigns and packs all rows.
    """
    with named_range("ivf_flat::build"), \
            obs.build_scope("ivf_flat.build") as rep:
        dataset = ensure_array(dataset, "dataset")
        expects(dataset.ndim == 2, "ivf_flat.build: 2-D dataset required")
        dataset, _ = _boundary.check_matrix(dataset, "dataset",
                                            site="ivf_flat.build",
                                            allow_empty=False)
        n, dim = dataset.shape
        expects(params.n_lists <= n, "ivf_flat.build: n_lists > n_rows")

        with obs.stage("ivf_flat.build.kmeans") as st:
            n_train = max(params.n_lists,
                          int(n * params.kmeans_trainset_fraction))
            if n_train < n:
                key = res.next_key()
                sel = jax.random.choice(key, n, (n_train,), replace=False)
                trainset = dataset[sel]
            else:
                trainset = dataset
            bal = KMeansBalancedParams(n_iters=params.kmeans_n_iters,
                                       metric=params.metric
                                       if params.metric == DistanceType.InnerProduct
                                       else DistanceType.L2Expanded)
            centers = kmeans_balanced.fit(res, bal, trainset, params.n_lists)
            # order lists along the centers' first principal component:
            # spatially adjacent lists get adjacent ids, so a query's probes
            # cluster into few super-tiles (the small-cap scan regime —
            # see search()'s super-tile dedupe)
            cf = centers.astype(jnp.float32)
            # mean-center before the gram: off-origin data (e.g. all-positive
            # SIFT features) would otherwise put the mean direction in the
            # top eigenvector and make the projections ~constant
            cc = cf - jnp.mean(cf, axis=0, keepdims=True)
            _, cvecs = jnp.linalg.eigh(
                jax.lax.dot_general(cc, cc, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32))
            centers = centers[jnp.argsort(cc @ cvecs[:, -1])]
            st.fence(centers)

        index = Index(centers=centers,
                      list_data=jnp.zeros((params.n_lists, _LIST_ALIGN, dim),
                                          dataset.dtype),
                      list_indices=jnp.full((params.n_lists, _LIST_ALIGN), -1,
                                            jnp.int32),
                      list_sizes=jnp.zeros(params.n_lists, jnp.int32),
                      metric=params.metric,
                      adaptive_centers=params.adaptive_centers)
        if params.add_data_on_build:
            index = extend(res, index, dataset,
                           jnp.arange(n, dtype=jnp.int32))
            if params.canary_queries > 0:
                cs = _canary.make(res, dataset, metric=params.metric,
                                  n_queries=params.canary_queries,
                                  k=params.canary_k,
                                  floor=params.canary_floor)
                index.canaries = cs
                cs.build_recall = _canary.measure(res, index, cs)
        return rep.attach(index)


def extend(res, index: Index, new_vectors, new_indices=None) -> Index:
    """Add vectors to an index (reference: ivf_flat.cuh:201 ``extend``).

    Fast path (no list outgrows the current capacity): one O(n_new)
    scatter-append into the existing padded storage.  Slow path (some list
    overflows): flatten + repack at a larger capacity — the reference
    likewise reallocates lists that outgrow their capacity (ivf_list.hpp).
    The coarse centers optionally drift when ``adaptive_centers`` is set
    (ivf_flat_types.hpp adaptive_centers semantics).
    """
    with named_range("ivf_flat::extend"):
        new_vectors = ensure_array(new_vectors, "new_vectors")
        expects(new_vectors.ndim == 2 and new_vectors.shape[1] == index.dim,
                "ivf_flat.extend: dim mismatch")
        new_vectors, _ = _boundary.check_matrix(
            new_vectors, "new_vectors", site="ivf_flat.extend",
            dim=index.dim)
        n_new = new_vectors.shape[0]
        if new_indices is None:
            new_indices = index.size + jnp.arange(n_new, dtype=jnp.int32)
        else:
            new_indices = ensure_array(new_indices, "new_indices")

        with obs.stage("ivf_flat.extend.assign") as st:
            bal = KMeansBalancedParams(metric=index.metric
                                       if index.metric == DistanceType.InnerProduct
                                       else DistanceType.L2Expanded)
            new_labels = kmeans_balanced.predict(res, bal, new_vectors,
                                                 index.centers)
            new_counts = jax.ops.segment_sum(
                jnp.ones(n_new, jnp.int32), new_labels,
                num_segments=index.n_lists)
            needed = index.list_sizes + new_counts
            st.fence(new_labels)

        # one host sync over an (n_lists,) reduction decides the path — the
        # only data-dependent choice (capacity is a static shape)
        if int(jnp.max(needed)) <= index.capacity:
            with obs.stage("ivf_flat.extend.pack") as st:
                bufs, rows = [index.list_data], [new_vectors]
                if index.list_data_sq is not None:
                    bufs.append(index.list_data_sq)
                    rows.append(jnp.sum(
                        new_vectors.astype(jnp.float32) ** 2, axis=-1))
                new_bufs, _, list_idx, sizes = _append_lists_multi(
                    tuple(bufs), tuple(rows), index.list_indices,
                    index.list_sizes, new_labels, new_indices)
                st.fence(new_bufs)
            list_data = new_bufs[0]
            data_sq = new_bufs[1] if len(new_bufs) > 1 else None
            centers = index.centers
            if index.adaptive_centers:
                # incremental drift: centers approximate list means, so the
                # updated mean is the size-weighted blend with the new rows
                # (reference: ivf_flat_build extend center update)
                new_sums = jax.ops.segment_sum(
                    new_vectors.astype(jnp.float32), new_labels,
                    num_segments=index.n_lists)
                blend = (centers * index.list_sizes[:, None] + new_sums
                         ) / jnp.maximum(needed, 1)[:, None]
                centers = jnp.where((new_counts > 0)[:, None], blend, centers)
                if index.metric == DistanceType.InnerProduct:
                    # spherical quantizer: keep the unit-norm invariant the
                    # build-time balanced k-means enforces
                    centers = centers / jnp.maximum(
                        jnp.linalg.norm(centers, axis=1, keepdims=True),
                        1e-12)
            out = Index(centers=centers, list_data=list_data,
                        list_indices=list_idx, list_sizes=sizes,
                        metric=index.metric,
                        adaptive_centers=index.adaptive_centers,
                        list_data_sq=data_sq)
            _mutate.next_generation(index, out)
            if index.canaries is not None:
                out.canaries = index.canaries
                _canary.auto_check(res, out, site="extend")
            return out

        # slow path: existing rows, flattened back out of the padded storage
        old_valid = index.list_indices >= 0
        old_labels = jnp.repeat(jnp.arange(index.n_lists, dtype=jnp.int32),
                                index.capacity)[old_valid.ravel()]
        old_vecs = index.list_data.reshape(-1, index.dim)[old_valid.ravel()]
        old_ids = index.list_indices.ravel()[old_valid.ravel()]

        all_vecs = jnp.concatenate([old_vecs, new_vectors.astype(
            index.list_data.dtype)], axis=0)
        all_ids = jnp.concatenate([old_ids, new_indices.astype(jnp.int32)])
        all_labels = jnp.concatenate([old_labels, new_labels])

        # +1 before rounding: a repack must never leave the fullest list
        # brim-full (max exactly on an alignment boundary), or the very
        # next one-row extend is forced back onto this O(n) path
        capacity = _round_up(max(int(jnp.max(needed)) + 1, _LIST_ALIGN),
                             _LIST_ALIGN)
        with obs.stage("ivf_flat.extend.pack") as st:
            list_data, list_idx, sizes = _pack_lists(
                all_vecs, all_labels, all_ids, index.n_lists, capacity)
            st.fence(list_data)

        centers = index.centers
        if index.adaptive_centers:
            # drift centers toward the new per-list means (reference:
            # ivf_flat_build extend updates centers when adaptive)
            sums = jax.ops.segment_sum(all_vecs.astype(jnp.float32),
                                       all_labels,
                                       num_segments=index.n_lists)
            means = sums / jnp.maximum(sizes, 1)[:, None]
            centers = jnp.where((sizes > 0)[:, None], means, centers)
            if index.metric == DistanceType.InnerProduct:
                centers = centers / jnp.maximum(
                    jnp.linalg.norm(centers, axis=1, keepdims=True), 1e-12)

        out = Index(centers=centers, list_data=list_data,
                    list_indices=list_idx, list_sizes=sizes,
                    metric=index.metric,
                    adaptive_centers=index.adaptive_centers)
        _mutate.next_generation(index, out)
        if index.canaries is not None:
            out.canaries = index.canaries
            _canary.auto_check(res, out, site="extend")
        return out


def delete(res, index: Index, ids) -> Index:
    """Tombstone-delete rows by source id (the online mutation layer —
    see :mod:`raft_tpu.neighbors.mutate` for the encoding).

    Every slot whose id is in ``ids`` is rewritten in ``list_indices``
    to a tombstone; all scan paths (XLA and Pallas, fused included)
    already mask negative ids to the worst-distance sentinel, so
    deleted rows disappear from search results immediately at zero
    per-search cost.  Storage is reclaimed by :func:`compact`, not
    here.  Ids not present in the index match nothing.

    Returns a NEW index — the next generation — sharing every array
    except ``list_indices`` with its parent; readers pinned on the
    parent are unaffected.
    """
    with named_range("ivf_flat::delete"):
        ids = ensure_array(ids, "ids")
        expects(ids.ndim == 1, "ivf_flat.delete: 1-D ids required")
        new_li, _ = _mutate.tombstone(index.list_indices, ids)
        out = Index(centers=index.centers, list_data=index.list_data,
                    list_indices=new_li, list_sizes=index.list_sizes,
                    metric=index.metric,
                    adaptive_centers=index.adaptive_centers,
                    list_data_sq=index.list_data_sq)
        out.canaries = index.canaries
        _mutate.next_generation(index, out)
        if index.canaries is not None:
            _canary.auto_check(res, out, site="delete")
        return out


def upsert(res, index: Index, ids, vectors) -> Index:
    """Replace-or-insert rows under explicit source ids: tombstone any
    existing rows with these ids, then append ``vectors`` under the same
    ids — one logical mutation, ONE generation bump (a churn loop of
    upserts advances the counter like a single ``extend`` per batch, so
    generation-keyed caches see one swap, not two).  Ids not present
    simply insert; duplicate live ids are all tombstoned first, so each
    id resolves to exactly one live row."""
    with named_range("ivf_flat::upsert"):
        ids = ensure_array(ids, "ids")
        vectors = ensure_array(vectors, "vectors")
        expects(ids.ndim == 1 and ids.shape[0] == vectors.shape[0],
                "ivf_flat.upsert: ids must be 1-D, one per vector")
        parent_gen = _mutate.generation(index)
        out = extend(res, delete(res, index, ids), vectors,
                     new_indices=ids)
        out.generation = parent_gen + 1
        if obs.enabled():
            obs.registry().counter("ivf_flat.upserts").inc()
        return out


def compact(res, index: Index) -> Index:
    """Reclaim tombstoned slots: stable-partition each list's live rows
    to the front, drop every tombstone, and shrink the shared capacity
    to fit the fullest surviving list (aligned, with the same one-row
    headroom the extend repack keeps).  O(n_lists * capacity) — the
    rebalancer calls this past its dead-fraction threshold rather than
    on every delete.  Returns a new generation sharing ``centers`` with
    its parent."""
    with named_range("ivf_flat::compact"):
        order, sizes = _mutate.compaction_order(index.list_indices)
        max_size = int(jnp.max(sizes)) if index.n_lists else 0
        capacity = _round_up(max(max_size + 1, _LIST_ALIGN), _LIST_ALIGN)
        capacity = min(capacity, max(index.capacity, _LIST_ALIGN))

        li = jnp.take_along_axis(index.list_indices, order, axis=1)
        data = jnp.take_along_axis(index.list_data, order[:, :, None],
                                   axis=1)
        li, data = li[:, :capacity], data[:, :capacity]
        live = (jnp.arange(capacity, dtype=jnp.int32)[None, :]
                < sizes[:, None])
        li = jnp.where(live, li, -1)
        data = jnp.where(live[:, :, None], data, 0)
        data_sq = None
        if index.list_data_sq is not None:
            data_sq = jnp.take_along_axis(index.list_data_sq, order,
                                          axis=1)[:, :capacity]
            data_sq = jnp.where(live, data_sq, 0)

        out = Index(centers=index.centers, list_data=data,
                    list_indices=li, list_sizes=sizes,
                    metric=index.metric,
                    adaptive_centers=index.adaptive_centers,
                    list_data_sq=data_sq)
        out.canaries = index.canaries
        _mutate.next_generation(index, out)
        if index.canaries is not None:
            _canary.auto_check(res, out, site="compact")
        return out


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "metric",
                                             "recall_target", "exact"))
def _search_impl(centers, list_data, list_indices, queries, k, n_probes,
                 metric, recall_target=0.95, exact=False,
                 filter_words=None):
    nq = queries.shape[0]
    qf = queries.astype(jnp.float32)
    cf = centers.astype(jnp.float32)
    ip_metric = metric == DistanceType.InnerProduct

    # ---- coarse: pick n_probes lists per query (select_clusters analogue) --
    probes = _select_clusters(centers, queries, n_probes, metric,
                              recall_target=recall_target, exact=exact)

    # ---- fine: scan probed lists, hierarchical select --------------------
    # per-probe local top-k inside the scan + ONE final select over the
    # n_probes*k survivors (exact — probe lists are disjoint; same
    # restructure as ivf_pq._search_impl_recon, where the trace showed the
    # per-probe merge chain / single wide sort dominating)
    worst = -jnp.inf if ip_metric else jnp.inf
    q_sq = jnp.sum(qf * qf, axis=1)
    cap = list_data.shape[1]
    kt = min(k, cap)

    def probe_step(carry, p):
        alld, alli = carry
        lists = probes[:, p]                        # (q,)
        data = list_data[lists].astype(jnp.float32)  # (q, cap, d)
        ids = list_indices[lists]                   # (q, cap)
        ip = jnp.einsum("qd,qcd->qc", qf, data,
                        precision=get_matmul_precision())
        if ip_metric:
            d = jnp.where(ids >= 0, ip, worst)
        else:
            d_sq = jnp.sum(data * data, axis=-1)
            d = jnp.maximum(q_sq[:, None] + d_sq - 2.0 * ip, 0.0)
            d = jnp.where(ids >= 0, d, worst)
        if filter_words is not None:
            # admission fold through the tombstone seam: rejected rows
            # are worst before the per-probe top-kt
            adm = _fbits.query_bits(filter_words, jnp.arange(nq), ids)
            d = jnp.where(adm > 0, d, worst)
        td, ti = select_k(d, kt, in_idx=ids, select_min=not ip_metric)
        alld = jax.lax.dynamic_update_slice(alld, td, (0, p * kt))
        alli = jax.lax.dynamic_update_slice(alli, ti, (0, p * kt))
        return (alld, alli), None

    init = (jnp.full((nq, n_probes * kt), worst, jnp.float32),
            jnp.full((nq, n_probes * kt), -1, jnp.int32))
    (alld, alli), _ = jax.lax.scan(probe_step, init,
                                   jnp.arange(n_probes))
    from raft_tpu.neighbors import grouped
    return grouped.finalize_topk(
        alld, alli, nq, k, not ip_metric,
        metric in (DistanceType.L2SqrtExpanded,
                   DistanceType.L2SqrtUnexpanded), select_k)


def super_tile_factor(cap: int, n_lists: int, n_probes: int
                      ) -> Tuple[int, int]:
    """(F, n_lists_eff) for the small-cap super-tile scan: how many
    adjacent lists one tile reads.  The ONE owner of the gate —
    ``search()`` and the exactness test both derive tiling from here,
    so a threshold change cannot desynchronize them."""
    F = 1
    while (cap * F < 512 and F < 8
           and n_lists % 2 == 0 and n_lists > n_probes):
        F *= 2
        n_lists //= 2
    return F, n_lists


@functools.partial(jax.jit, static_argnames=("n_probes", "metric",
                                             "recall_target", "exact"))
def _select_clusters(centers, queries, n_probes, metric,
                     recall_target=0.95, exact=False):
    """Coarse top-``n_probes`` ranking (the select_clusters analogue).

    ``approx_max_k`` instead of ``top_k``: probe selection needs a good
    candidate SET, not an exact ranking — the TPU-native partial
    reduction measured 1.8x faster at (5000, 16384) with a 99.3%
    probe-set overlap (the ~0.7% swapped probes are the marginal ones,
    far below the recall noise floor).  On CPU it lowers to the exact
    select, so test assertions are unaffected.

    ``recall_target`` / ``exact`` come from ``SearchParams``
    (coarse_recall_target / exact_coarse).  When ``n_probes`` is within
    1/8 of ``n_lists`` the approx reduction is bypassed for ``lax.top_k``:
    its oversampled partial reduction degenerates to a full select there,
    so approx would cost the overlap loss for no speedup."""
    qf = queries.astype(jnp.float32)
    cf = centers.astype(jnp.float32)
    q_dot_c = jax.lax.dot_general(qf, cf, (((1,), (1,)), ((), ())),
                                  precision=get_matmul_precision(),
                                  preferred_element_type=jnp.float32)
    if metric == DistanceType.InnerProduct:
        score = q_dot_c
    else:
        c_sq = jnp.sum(cf * cf, axis=1)
        score = 2.0 * q_dot_c - c_sq[None, :]
    n_lists = centers.shape[0]
    if exact or n_probes >= n_lists - (n_lists // 8):
        _, probes = jax.lax.top_k(score, n_probes)
    else:
        _, probes = jax.lax.approx_max_k(score, n_probes,
                                         recall_target=recall_target)
    return probes


@functools.partial(jax.jit, static_argnames=("k", "metric", "n_groups",
                                             "block", "use_pallas",
                                             "pallas_interpret"))
def _search_impl_grouped(centers, list_data, list_indices, queries, probes,
                         k, metric, n_groups, block, list_data_sq=None,
                         use_pallas=False, pallas_interpret=False,
                         filter_words=None):
    """List-centric scan over fixed-size pair groups: each group is GROUP
    (query, probe) pairs of one list, so list vectors are read ~once and
    the distance block is a full batched MXU GEMM.  See
    :mod:`raft_tpu.neighbors.grouped` for the design; distances here are
    exact fp32 (same restructure as ivf_pq._search_impl_recon_grouped).
    On TPU the scan runs as the fused Pallas kernel
    (:mod:`raft_tpu.ops.pq_group_scan_pallas`, flat variant).
    """
    from raft_tpu.neighbors import grouped

    nq, n_probes = probes.shape
    P = nq * n_probes
    n_lists = centers.shape[0]
    cap = list_data.shape[1]
    dim = list_data.shape[2]
    ip_metric = metric == DistanceType.InnerProduct
    worst = -jnp.inf if ip_metric else jnp.inf

    qf = queries.astype(jnp.float32)
    q_sq = jnp.sum(qf * qf, axis=1)

    group_list, slot_pairs = grouped.build_groups(probes, n_lists, n_groups)
    # per-(slot, candidate) admission words in list-slot order — the
    # layout the kernel streams through VMEM; note list_indices here may
    # be the SUPER-TILED view (F*cap wide), which is exactly the layout
    # the kernel iterates, so the packing follows it for free
    adm_words = None
    if filter_words is not None:
        adm_words = _fbits.group_admission_words(
            filter_words, group_list, slot_pairs, list_indices, n_probes, P)

    kt = min(k, cap)
    if use_pallas:
        from raft_tpu.ops import pq_group_scan_pallas as pqp

        if pqp.supported(not ip_metric, cap, dim, kt, nq,
                         data_elem_bytes=4):
            d_sq = (list_data_sq if list_data_sq is not None
                    else jnp.sum(list_data.astype(jnp.float32) ** 2,
                                 axis=-1))
            vals, ti = pqp.grouped_flat_l2_scan(
                group_list, slot_pairs, qf, list_data, d_sq,
                list_indices, kt, n_probes, interpret=pallas_interpret,
                adm_words=adm_words)
            outd, outi = grouped.scatter_packed(vals, ti, slot_pairs, P,
                                                not ip_metric)
            return grouped.finalize_topk(
                outd, outi, nq, k, not ip_metric,
                metric in (DistanceType.L2SqrtExpanded,
                           DistanceType.L2SqrtUnexpanded), select_k)

    def distance_block(gl, slot):
        qid = jnp.where(slot < P, slot // n_probes, 0)
        qv = qf[qid]                                     # (B, G, d)
        data = list_data[gl].astype(jnp.float32)         # (B, cap, d)
        ids = list_indices[gl]
        ip = jnp.einsum("bqd,bcd->bqc", qv, data,
                        precision=get_matmul_precision())
        if ip_metric:
            d = ip
        else:
            d_sq = jnp.sum(data * data, axis=-1)         # (B, cap)
            d = jnp.maximum(q_sq[qid][:, :, None]
                            + d_sq[:, None, :] - 2.0 * ip, 0.0)
        d = jnp.where(ids[:, None, :] >= 0, d, worst)
        if filter_words is not None:
            adm = _fbits.query_bits(
                filter_words, qid, jnp.broadcast_to(ids[:, None, :],
                                                    d.shape))
            d = jnp.where(adm > 0, d, worst)
        return d, ids

    outd, outi = grouped.scan_and_scatter(
        group_list, slot_pairs, P, cap, k, not ip_metric, block,
        select_k, distance_block)
    return grouped.finalize_topk(
        outd, outi, nq, k, not ip_metric,
        metric in (DistanceType.L2SqrtExpanded,
                   DistanceType.L2SqrtUnexpanded), select_k)


@auto_convert_output
def search(res, params: SearchParams, index: Index, queries, k: int, *,
           filter=None) -> Tuple[jax.Array, jax.Array]:
    """Search the index (reference: ivf_flat.cuh:389).

    Returns ``(distances (q, k), indices (q, k) int32)``; unfilled slots
    (fewer than k valid candidates in the probed lists) carry id -1 and
    +inf / -inf distance, matching the reference's sentinel behavior.

    ``filter`` (a :class:`~raft_tpu.filters.SampleFilter` or an
    (nq, n_rows) bool mask) restricts each query's candidate set by
    source id; rejected rows fold to the worst-distance sentinel before
    every top-k (see docs/api.md, "Filtered search & tenancy").

    .. note:: the first TPU search mutates ``index`` in place, lazily
       attaching derived caches (``list_data_sq`` row norms, the group
       count and id-exactness caches).  ``list_data_sq`` is a pytree
       leaf, so the index's registered pytree structure changes from a
       ``None`` leaf to an array leaf — code that captured the index in
       a jitted closure before the first search will retrace once, and
       tree-structure comparisons across that boundary will differ.

    Queries pass through the boundary validator (see
    :mod:`raft_tpu.integrity.boundary`): under policy ``mask``,
    non-finite query rows return id -1 / worst distance instead of
    poisoning the batch.
    """
    queries = ensure_array(queries, "queries")
    queries, ok_rows = _boundary.check_matrix(
        queries, "queries", site="ivf_flat.search", dim=index.dim)
    # legacy shape guard: still fires when the validator policy is "off"
    expects(queries.ndim == 2 and queries.shape[1] == index.dim,
            "ivf_flat.search: query dim mismatch")
    dist, ids = _search_checked(res, params, index, queries, k,
                                filter=filter)
    if ok_rows is not None:
        dist, ids = _boundary.mask_search_outputs(
            dist, ids, ok_rows,
            select_min=index.metric != DistanceType.InnerProduct)
    return dist, ids


def _search_checked(res, params: SearchParams, index: Index, queries,
                    k: int, filter=None) -> Tuple[jax.Array, jax.Array]:
    with named_range("ivf_flat::search"):
        from raft_tpu.neighbors import grouped

        fw = _fbits.query_filter_words(filter, queries.shape[0],
                                       "ivf_flat.search")
        if fw is not None and obs.enabled():
            obs.registry().counter("ivf_flat.search.filtered").inc()
        n_probes = min(params.n_probes, index.n_lists)
        coarse_rt = getattr(params, "coarse_recall_target", 0.95)
        exact_coarse = getattr(params, "exact_coarse", False)
        if (isinstance(queries, jax.core.Tracer)
                or isinstance(index.centers, jax.core.Tracer)):
            # queries or the Index pytree traced by an outer jit/vmap:
            # use the fully traceable probe-order scan
            return _search_impl(index.centers, index.list_data,
                                index.list_indices, queries, k, n_probes,
                                index.metric, recall_target=coarse_rt,
                                exact=exact_coarse, filter_words=fw)
        with obs.stage("ivf_flat.search.coarse") as st:
            probes = _select_clusters(index.centers, queries, n_probes,
                                      index.metric, recall_target=coarse_rt,
                                      exact=exact_coarse)
            st.fence(probes)
        # the fused kernel's one-hot id contraction is f32 — require
        # every actual candidate id (incl. user-supplied extend ids)
        # to be f32-exact, not just the row count
        use_pallas = (_platform.on_tpu()
                      and grouped.ids_f32_exact(index, index.list_indices))
        if use_pallas and index.list_data_sq is None:
            # lazily attach the row-norm cache (stays on the index);
            # the XLA fallback recomputes row norms in its own fused
            # block, so attaching here would only force a retrace
            index.list_data_sq = jnp.sum(
                index.list_data.astype(jnp.float32) ** 2, axis=-1)

        # super-tiles: the fused scan's per-group cost is flat in cap
        # (~22 us measured at cap 160 AND 416, round 5), so small lists
        # — the nlist=16384 regime — fragment pairs into pure overhead.
        # Scan F adjacent lists per tile and dedupe per-query probes
        # that land in the same tile.
        cap = index.capacity
        F, n_lists_eff = super_tile_factor(cap, index.n_lists, n_probes)
        dsq = index.list_data_sq
        if F > 1:
            probes_eff = grouped.dedup_super_probes(probes, F,
                                                    n_lists_eff)
            data_eff = index.list_data.reshape(n_lists_eff, F * cap,
                                               index.dim)
            ids_eff = index.list_indices.reshape(n_lists_eff, F * cap)
            dsq_eff = (dsq.reshape(n_lists_eff, F * cap)
                       if dsq is not None else None)
            centers_eff = index.centers[::F]
        else:
            probes_eff, data_eff, ids_eff = probes, index.list_data, \
                index.list_indices
            dsq_eff, centers_eff = dsq, index.centers

        # static group capacity (round 10): the worst-case bound
        # ceil(P/G) + n_touched is exact-safe — no pair can drop at it —
        # so dispatch needs no host-synced group count, the shape is a
        # pure function of (nq, n_probes, n_lists_eff), and one warmed
        # executable serves every batch at the shape (the old
        # cached_groups ratchet recompiled on probe-distribution shift)
        n_groups, _ = grouped.group_capacity(
            queries.shape[0], n_probes, n_lists_eff)
        G = grouped.GROUP
        block = grouped.block_size(
            n_groups,
            G * F * cap * 8,            # fp32 distances + broadcast ids
            (F * cap + G) * index.dim * 4)  # data slice + query gather

        with obs.stage("ivf_flat.search.scan") as st:
            out = _search_impl_grouped(centers_eff, data_eff,
                                       ids_eff, queries, probes_eff,
                                       k, index.metric, n_groups, block,
                                       list_data_sq=dsq_eff,
                                       use_pallas=use_pallas,
                                       filter_words=fw)
            st.fence(out)
        return out


# ---------------------------------------------------------------------------
# serialization (reference: ivf_flat_serialize.cuh; version hard-checked)
# ---------------------------------------------------------------------------

# v2: trailing recall-canary block (nested envelope, may be absent)
_SERIALIZATION_VERSION = 2
_MIN_READ_VERSION = 1


def serialize(res, stream: BinaryIO, index: Index) -> None:
    """Versioned index dump (reference: detail/ivf_flat_serialize.cuh),
    wrapped in the CRC32 integrity envelope (core/serialize)."""
    with ser.enveloped_writer(stream) as body:
        ser.serialize_scalar(res, body, np.int32(_SERIALIZATION_VERSION))
        ser.serialize_scalar(res, body, np.int32(index.metric))
        ser.serialize_scalar(res, body, np.int32(index.adaptive_centers))
        for arr in (index.centers, index.list_data, index.list_indices,
                    index.list_sizes):
            ser.serialize_mdspan(res, body, arr)
        _canary.to_stream(res, body, index.canaries)


def deserialize(res, stream: BinaryIO) -> Index:
    """Truncated / bit-flipped streams raise
    :class:`~raft_tpu.core.serialize.CorruptIndexError` (CRC-checked
    envelope), never load as garbage arrays."""
    body = ser.open_envelope(stream)
    version = int(ser.deserialize_scalar(res, body))
    if not _MIN_READ_VERSION <= version <= _SERIALIZATION_VERSION:
        raise ValueError(
            f"ivf_flat serialization version mismatch: got {version}, "
            f"expected {_MIN_READ_VERSION}..{_SERIALIZATION_VERSION}")
    metric = int(ser.deserialize_scalar(res, body))
    adaptive = bool(ser.deserialize_scalar(res, body))
    arrays = [jnp.asarray(ser.deserialize_mdspan(res, body))
              for _ in range(4)]
    index = Index(*arrays, metric=metric, adaptive_centers=adaptive)
    if version >= 2:
        index.canaries = _canary.from_stream(res, body)
    return index


def save(res, filename: str, index: Index, *, retry_policy=None,
         deadline=None) -> None:
    """Atomic file dump (tmp + fsync + rename) with transient-IO retry —
    the filename overload of the reference's serialize, hardened."""
    from raft_tpu.resilience import _save_index
    _save_index("ivf_flat.save", lambda b: serialize(res, b, index),
                filename, retry_policy, deadline)


def load(res, filename: str, *, retry_policy=None, deadline=None) -> Index:
    """File-load overload; transient IO errors retry, corruption raises
    :class:`~raft_tpu.core.serialize.CorruptIndexError` immediately.

    Indexes carrying recall canaries are health-checked before being
    returned (see :func:`raft_tpu.integrity.health_check`)."""
    from raft_tpu.resilience import _load_index
    index = _load_index("ivf_flat.load", lambda b: deserialize(res, b),
                        filename, retry_policy, deadline)
    _canary.auto_check(res, index, site="load")
    return index
