"""List-centric grouped scan machinery shared by the IVF searches.

The probe-order scan (one step per probe rank) re-reads every probed list's
data once per probing query — at SIFT-1M bench shapes that is ~55 GB of
HBM gather traffic per 5000-query batch, and the per-query einsum is a
batched mat-vec the MXU cannot tile.  The measured trace
(`profiles/ab_trace`, round 3) shows the scan's gather+einsum fusion
bandwidth-bound at ~320 GB/s.

The grouped scan inverts the loop the way the reference's
``compute_similarity_kernel`` assigns one CTA per (list, query-group)
(ivf_pq_search.cuh:611): (query, probe) pairs are bucketed BY LIST, so each
list's data is read once.  A first cut bucketed pairs into one
``qcap``-wide bucket per list; probe-popularity skew made ``qcap`` ~3.3x
the mean occupancy and the padding inflated both the GEMM and the select
by the same factor (measured slower than probe-order).  This module
implements the fix: **fixed-size pair groups** — each list's pair count is
padded to a multiple of ``G`` (128, a full MXU tile of queries), so hot
lists get several groups instead of widening every bucket.  Padding
overhead is bounded by ``n_lists·G/2`` slots total (~16% at bench shapes),
independent of skew.

The number of groups a batch *needs* is data-dependent, but dispatch no
longer syncs it (round 10): :func:`group_capacity` gives a static,
shape-only bound — ``ceil(P/G) + n_touched_lists`` — at which
:func:`build_groups` provably cannot drop a pair, so the grouped scans
are fully traceable (they lower under ``jit`` and ``shard_map``) and a
warmed executable serves every batch at that shape.  A calibrated
per-index estimate tightens the touched-lists term; only then is an
in-graph overflow count armed, read *after* the scan is enqueued, and
the rare overflowing batch re-dispatches at the exact-safe bound.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from raft_tpu.matrix import ops as matrix_ops

GROUP = 128          # pair-group size: one full MXU tile of queries
_GROUP_ROUND = 256   # n_groups rounding quantum (compile-cache stability)


def _sort_pairs(probes: jax.Array, n_lists: int
                ) -> Tuple[jax.Array, jax.Array]:
    """The batch's pairs sorted by list, ascending pair index within a
    list: ``(pl_s, order)``, each (P,) int32.  Sentinel probes
    (``>= n_lists``) share the key ``n_lists`` and sort last."""
    key = jnp.minimum(probes.reshape(-1).astype(jnp.int32), n_lists)
    return jax.lax.sort((key, jax.lax.iota(jnp.int32, key.shape[0])),
                        num_keys=1, is_stable=True)


def _list_starts(pl_s: jax.Array, n_lists: int) -> jax.Array:
    """(n_lists + 1,) int32: the first sorted position of each list; the
    last entry is the number of in-range pairs.

    A two-level search over the sorted keys in ``GROUP``-wide blocks:
    every block whose last key lies below ``l`` lies wholly below it,
    and the one block after them holds the boundary.  Both levels are
    dense compares; the only data move is one block row per list —
    ``jnp.searchsorted`` would lower to serial per-key gathers."""
    P = pl_s.shape[0]
    nb = -(-P // GROUP)
    blocks = jnp.pad(pl_s, (0, nb * GROUP - P),
                     constant_values=n_lists).reshape(nb, GROUP)
    v = jax.lax.iota(jnp.int32, n_lists + 1)[:, None]
    last = blocks[:, GROUP - 1:].reshape(1, nb)
    below = jnp.sum(last < v, axis=1, dtype=jnp.int32)
    row = blocks[jnp.minimum(below, nb - 1)]
    within = jnp.sum(row < v, axis=1, dtype=jnp.int32)
    return below * GROUP + jnp.where(below < nb, within, 0)


def _group_bounds(lstart: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(group_start, group_end)``, each (n_lists,) int32: the first
    group of each list and one past its last, ``ceil(count/G)`` groups
    a list."""
    per_list = -(-(lstart[1:] - lstart[:-1]) // GROUP)
    gend = jnp.cumsum(per_list, dtype=jnp.int32)
    return gend - per_list, gend


def num_groups(probes: jax.Array, n_lists: int) -> jax.Array:
    """Total fixed-size groups this batch needs: sum over lists of
    ceil(count/G), counted from the same sort :func:`build_groups`
    lays its groups out from, so sentinel probes (``>= n_lists``) are
    excluded exactly as there.  The dispatch path no longer syncs this
    (see :func:`group_capacity`); it remains the calibrated regime's
    overflow count and the measurement
    :func:`raft_tpu.neighbors.ivf_pq.calibrate_group_capacity` reads."""
    if probes.size == 0:
        return jnp.int32(0)
    pl_s, _ = _sort_pairs(probes, n_lists)
    return _group_bounds(_list_starts(pl_s, n_lists))[1][-1]


num_groups = jax.jit(num_groups, static_argnames=("n_lists",))


@functools.partial(jax.jit, static_argnames=("n_lists",))
def touched_lists(probes: jax.Array, n_lists: int) -> jax.Array:
    """Distinct in-range lists the batch probes — the quantity
    :func:`group_capacity`'s calibrated estimate models."""
    counts = jax.ops.segment_sum(
        jnp.ones(probes.size, jnp.int32), probes.reshape(-1),
        num_segments=n_lists)
    return jnp.sum((counts > 0).astype(jnp.int32))


def round_groups(n: int) -> int:
    """Round a group count up to the compile-cache quantum."""
    return -(-max(n, 1) // _GROUP_ROUND) * _GROUP_ROUND


# estimate safety margin: a calibrated capacity covers probe
# distributions that touch up to 25% more lists than measured before the
# overflow re-dispatch path triggers
_EST_MARGIN = 1.25


def group_capacity(nq: int, n_probes: int, n_lists: int,
                   est: float = 0.0) -> Tuple[int, bool]:
    """Static group capacity for dispatching :func:`build_groups` at a
    traceable shape.  Returns ``(capacity, exact)``.

    Worst case: with ``P = nq * n_probes`` pairs, each touched list
    wastes at most one partial group, so
    ``sum_l ceil(c_l/G) <= ceil(P/G) + n_touched`` and
    ``n_touched <= min(n_lists, P)``.  Dispatching at that bound can
    NEVER drop a pair — ``exact=True`` means no overflow machinery (and
    no host sync of any kind) is needed.

    ``est`` (the calibrated fraction of ``min(n_lists, P)`` a real batch
    touches, measured by ``ivf_pq.calibrate_group_capacity`` and carried
    in the index envelope) tightens the touched-lists term under a 25%
    safety margin.  The tightened capacity is rounded
    (:func:`round_groups`) so nearby estimates share executables and
    clamped to the worst bound; when it lands below the bound,
    ``exact=False`` tells the caller to arm the in-graph overflow count
    and re-dispatch at the worst bound if exceeded.
    """
    P = nq * n_probes
    if P <= 0:
        return 1, True
    touched_worst = min(n_lists, P)
    worst = -(-P // GROUP) + touched_worst
    if est <= 0.0:
        return worst, True
    touched = min(int(est * _EST_MARGIN * touched_worst) + 1, touched_worst)
    capacity = min(round_groups(-(-P // GROUP) + touched), worst)
    return capacity, capacity >= worst


def ids_f32_exact(index_obj, list_indices: jax.Array) -> bool:
    """True when every candidate id in ``list_indices`` is exactly
    representable in float32 (|id| < 2^24) — the precondition for the
    Pallas kernel's one-hot f32 id contraction.

    ``extend(new_indices=...)`` accepts arbitrary user int32 ids, so a
    row-count proxy (n_lists * capacity) is not a safe bound.  The check
    reads the true max |id| once (one tiny host sync) and caches the
    verdict on the index object; extend() returns a fresh Index, so the
    cache never goes stale.
    """
    cached = getattr(index_obj, "_ids_f32_exact", None)
    if cached is None:
        max_abs = int(jnp.max(jnp.abs(list_indices)))
        cached = max_abs < (1 << 24)
        object.__setattr__(index_obj, "_ids_f32_exact", cached)
    return cached


def build_groups(probes: jax.Array, n_lists: int, n_groups: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """Bucket (query, probe) pairs into fixed-size per-list groups.

    Returns ``(group_list, slot_pairs)``:

    - ``group_list`` (n_groups,) int32 — the list each group scans, the
      live groups first in list order (tail groups beyond the real count
      alias the last list; their slots are empty);
    - ``slot_pairs`` (n_groups, GROUP) int32 — flattened pair index
      (q * n_probes + probe_rank) per slot, ascending within a list,
      with ``P = probes.size`` as the empty-slot sentinel (scatters
      through it are dropped).  A group's real slots come first.

    Pair → (group, slot): sort pairs by list; pair with in-list rank r of
    list l lands in group ``group_start[l] + r // G``, slot ``r % G``.
    Sentinel probes (``>= n_lists``) occupy no slot.  Group ``g`` is
    therefore the window of ``min(G, list end - start)`` sorted pairs
    from ``start = list_start[l] + G * (g - group_start[l])``: past the
    one sort, every step works on per-list or per-group tables, and the
    only full-size data move is that window per group.  No gather or
    scatter is indexed per pair: over the batch cell's 360,000 pairs
    each such op costs ≈ 2.5 ms on a TPU v5e, against 0.46 ms for the
    sort and 0.67 ms for this whole layout (``profiles/group_build.py``).
    """
    P = probes.size
    if P == 0:
        return (jnp.full((n_groups,), n_lists - 1, jnp.int32),
                jnp.zeros((n_groups, GROUP), jnp.int32))
    pl_s, order = _sort_pairs(probes, n_lists)
    lstart = _list_starts(pl_s, n_lists)
    gstart, gend = _group_bounds(lstart)
    g = jax.lax.iota(jnp.int32, n_groups)
    # the list of each group: how many lists end at or before it
    # (n_lists past the live groups, which clamps to the tail's alias)
    gl = jnp.sum(gend[None, :] <= g[:, None], axis=1, dtype=jnp.int32)
    group_list = jnp.minimum(gl, n_lists - 1)
    offset = lstart[:-1] - GROUP * gstart
    start = jnp.where(gl < n_lists, offset[group_list] + GROUP * g, P)
    length = jnp.minimum(lstart[1:][group_list] - start, GROUP)
    slot_pairs = jnp.where(
        jax.lax.iota(jnp.int32, GROUP)[None, :] < length[:, None],
        _windows(order, start), P)
    return group_list, slot_pairs


def _windows(order: jax.Array, start: jax.Array) -> jax.Array:
    """(n, GROUP) int32: ``order[s : s + GROUP]`` for each ``s`` of
    ``start`` (``0 <= s <= P``; ``P`` past the end).

    Each window is cut from the two aligned ``GROUP``-wide rows of
    ``order`` that hold it — two row gathers — and shifted into place
    by a seven-stage barrel shift.  A gather of unaligned 128-wide
    windows lowers to a loop of one dynamic slice per window on the
    TPU: with it the layout took 6.0 ms at the batch cell's 6,909
    groups, with this 0.67 ms (``profiles/group_build.py``)."""
    P = order.shape[0]
    rows = -(-P // GROUP) + 1
    src = jnp.pad(order, (0, rows * GROUP - P),
                  constant_values=P).reshape(rows, GROUP)
    r = start // GROUP
    win = jnp.concatenate([src[r], src[jnp.minimum(r + 1, rows - 1)]],
                          axis=1)
    shift = start % GROUP
    for b in range(GROUP.bit_length() - 1):
        step = 1 << b
        rolled = jnp.concatenate([win[:, step:], win[:, :step]], axis=1)
        win = jnp.where(((shift & step) != 0)[:, None], rolled, win)
    return win[:, :GROUP]


@functools.partial(jax.jit, static_argnames=("n_lists",))
def probe_overlap_order(probes: jax.Array, n_lists: int) -> jax.Array:
    """Probe-overlap query grouping: a permutation of the batch's queries
    that clusters queries probing the SAME lists.

    Queries sort by their (rank-0, rank-1) probe pair — nearest coarse
    centers, the strongest overlap signal the probe table carries.
    Combined with :func:`build_groups`'s (list, pair-index) sort this
    makes a hot list's pair groups hold runs of CONSECUTIVE queries:

    - adjacent groups of one list keep the same BlockSpec index, so the
      Pallas pipeline skips the re-DMA and each hot list's data streams
      from HBM once per BATCH, not once per probing query;
    - the fused kernels' per-step accumulator row copies touch a
      narrow band of query rows per group.

    Returns ``qorder`` (nq,) int32; callers permute queries/probes by it
    before grouping and un-permute results with ``argsort(qorder)``.
    The permutation changes only iteration order — distances and ids
    are untouched.
    """
    nq, n_probes = probes.shape
    if n_probes == 0:
        # degenerate batch (no probes — e.g. every list emptied by
        # delete/compaction upstream): identity order, nothing to cluster
        return jnp.arange(nq, dtype=jnp.int32)
    r0 = jnp.minimum(probes[:, 0].astype(jnp.int32), n_lists)
    r1 = jnp.minimum(probes[:, min(1, n_probes - 1)].astype(jnp.int32),
                     n_lists)
    # sentinels (>= n_lists, from super-tile dedupe) clamp into range so
    # the ordering stays monotone
    if n_lists + 1 <= 46340:
        # (n_lists+1)^2 fits int32: one fused sort key
        key = r0 * (n_lists + 1) + r1
        return jnp.argsort(key).astype(jnp.int32)
    # above ~46k lists the packed key wraps int32 (and x64 is disabled
    # by default, so an int64 key would silently downcast): lexsort via
    # two STABLE passes — secondary key first, primary second
    o1 = jnp.argsort(r1, stable=True)
    o0 = jnp.argsort(r0[o1], stable=True)
    return o1[o0].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("factor", "n_super"))
def dedup_super_probes(probes: jax.Array, factor: int, n_super: int
                       ) -> jax.Array:
    """Map per-query probes onto super-tiles of ``factor`` adjacent
    lists and mask per-row duplicates with the ``n_super`` sentinel.

    Small lists fragment pairs into many groups whose per-group cost is
    flat (~22 us measured at any cap, round 5); scanning ``factor``
    lists per tile cuts the group count, and a query probing several
    lists of one tile pays for the tile ONCE — the duplicate pairs are
    sentineled out here and dropped by :func:`build_groups`."""
    sp = probes // factor
    dup = matrix_ops.row_duplicate_mask(sp)
    return jnp.where(dup, n_super, sp)


def finalize_topk(outd: jax.Array, outi: jax.Array, nq: int, k: int,
                  select_min: bool, sqrt: bool, select_k_fn
                  ) -> Tuple[jax.Array, jax.Array]:
    """Final hierarchical select over the per-pair top-kt survivors.

    ``outd``/``outi`` are (P, kt) — or already (nq, n_probes*kt) — laid
    out so reshaping to (nq, n_probes*kt) groups each query's candidates
    (pair id is q * n_probes + probe_rank).  Shared epilogue of every
    probe-order and grouped scan: one narrow select, sentinel padding to
    k, optional sqrt for the sqrt-L2 metrics.
    """
    worst = jnp.inf if select_min else -jnp.inf
    alld = outd.reshape(nq, -1)
    alli = outi.reshape(nq, -1)
    kf = min(k, alld.shape[1])
    best_d, best_i = select_k_fn(alld, kf, in_idx=alli,
                                 select_min=select_min)
    if kf < k:
        best_d = jnp.pad(best_d, ((0, 0), (0, k - kf)),
                         constant_values=worst)
        best_i = jnp.pad(best_i, ((0, 0), (0, k - kf)),
                         constant_values=-1)
    # tombstoned slots (neighbors/mutate: id <= -2) carry worst-sentinel
    # distances through every scan, but when k exceeds the valid
    # candidate count their ENCODED ids can survive the select — clamp
    # every negative id to the public -1 sentinel here, the one epilogue
    # all probe-order and grouped scans share.  Filter-rejected rows
    # (filters.SampleFilter) fold to the worst distance with their REAL
    # id still attached; map any worst-distance survivor to -1 so every
    # scan path shares the fused epilogue's (worst, -1) contract.
    best_i = jnp.where(best_d == worst, -1, jnp.maximum(best_i, -1))
    if sqrt:
        best_d = jnp.sqrt(jnp.maximum(best_d, 0.0))
    return best_d, best_i


def scatter_packed(vals, ids, slot_pairs, P, select_min):
    """Scatter per-pair kernel results into (P, kt) buffers in ONE pass.

    Two separate (values, ids) row scatters measured ~36 ms each at bench
    shapes; bitcast-packing halves the per-row scatter bookkeeping.
    Rows with +inf values (exhausted: fewer than kt finite candidates)
    get the -1 id sentinel, matching the XLA scan path.
    """
    kt = vals.shape[-1]
    worst = jnp.inf if select_min else -jnp.inf
    ids = jnp.where(jnp.isinf(vals), -1, ids)
    flat = slot_pairs.reshape(-1)
    packed = jnp.concatenate(
        [jax.lax.bitcast_convert_type(vals, jnp.int32).reshape(-1, kt),
         ids.reshape(-1, kt)], axis=1)                 # (rows, 2*kt)
    init = jnp.concatenate(
        [jnp.broadcast_to(
            jax.lax.bitcast_convert_type(jnp.float32(worst), jnp.int32),
            (P, kt)),
         jnp.full((P, kt), -1, jnp.int32)], axis=1)
    outp = init.at[flat].set(packed, mode="drop")
    outd = jax.lax.bitcast_convert_type(outp[:, :kt], jnp.float32)
    outi = outp[:, kt:]
    return outd, outi


def scan_traffic(rot: int, pq_dim: int = 0, pq_bits: int = 0) -> dict:
    """Per-candidate-row HBM bytes each grouped scan mode streams.

    Every mode reads the (int32) candidate-id row and an (f32) cached row
    norm per candidate; what differs is the data payload — bf16
    reconstructions (2 B/dim) or lane-major packed codes (int32 words
    covering pq_dim*pq_bits bits).
    Query/center/codebook traffic is per GROUP (128 pairs), not per row,
    and amortizes out at scan scale; this model is what the round-6
    decomposition profile and the docs' memory-traffic table report."""
    base = 4 + 4                      # id row (int32) + row norm (f32)
    out = {"recon": 2 * rot + base}
    if pq_dim and pq_bits:
        w_bytes = -(-pq_dim * pq_bits // 8)
        out["codes"] = 4 * -(-w_bytes // 4) + base
    # fused mode streams the same candidate rows as its backing source
    # (codes when eligible, else recon) — its win is on the OUTPUT side:
    # the per-pair (vals, ids) round-trip plus scatter and final select
    # disappear (see pair_output_traffic)
    out["fused"] = out.get("codes", out["recon"])
    return out


def pair_output_traffic(kt: int) -> int:
    """Per-(query, probe) HBM bytes of the NON-fused epilogue that the
    fused kernels eliminate: the kernel's (kt f32, kt int32) output
    write, the scatter's read + packed write, and the final select's
    read of the (P, 2*kt) buffers.  This is the round-7 column of the
    decomposition profile."""
    row = 2 * 4 * kt                  # one (vals, ids) pair row
    return row * 4                    # write + scatter r/w + select read


def block_size(n_groups: int, *per_group_bytes: int,
               budget: int = 96 << 20, quantum: int = 16) -> int:
    """Groups per scan step such that the listed per-group transients stay
    under ``budget`` bytes."""
    per = max(sum(per_group_bytes), 1)
    b = budget // per
    b = max(quantum, b - b % quantum)
    # floor at 1: n_groups == 0 (every probed list empty after
    # delete/compaction) must not produce a zero block size — the scan
    # driver guards the empty case itself
    return min(b, max(n_groups, 1))


def scan_and_scatter(group_list, slot_pairs, P, cap, k, select_min, block,
                     select_k_fn, distance_block, kt=0):
    """Shared scan driver: for each block of groups, compute distances via
    ``distance_block(gl, slot) -> ((B, GROUP, cap) masked distances,
    (B, cap) candidate ids)`` and take each pair-row's local top-kt.

    Per-block results are emitted as scan *outputs* and scattered into the
    (P, kt) buffers ONCE after the loop — a (P, kt) scan carry would be
    copied every iteration by the in-loop scatter (measured ~150 MB/block
    at bench shapes).  Candidate ids are resolved by gathering the block's
    (B, cap) id rows at the selected positions, which broadcasting
    ``take_along_axis`` does without materializing a (B, GROUP, cap) id
    tensor.  Sentinel slots scatter out of bounds and are dropped; the
    clamped tail block emits duplicate pairs with identical values, so the
    final scatter stays idempotent."""
    n_groups = group_list.shape[0]
    worst = jnp.inf if select_min else -jnp.inf
    # kt (SearchParams.per_probe_topk) narrows the per-pair keep-set below
    # k; 0 keeps the exact-merge default
    kt = min(kt or k, cap) if cap else (kt or k)

    if n_groups == 0 or block <= 0 or cap == 0:
        # nothing to scan (all probed lists empty — possible after
        # delete/compaction empties the index): every pair is exhausted
        return (jnp.full((P, kt), worst, jnp.float32),
                jnp.full((P, kt), -1, jnp.int32))
    block = min(block, n_groups)
    n_blocks = -(-n_groups // block)
    block_starts = jnp.minimum(jnp.arange(n_blocks) * block,
                               n_groups - block)

    def step(_, start):
        gl = jax.lax.dynamic_slice(group_list, (start,), (block,))
        slot = jax.lax.dynamic_slice(slot_pairs, (start, 0), (block, GROUP))
        d, ids = distance_block(gl, slot)            # (B, G, cap), (B, cap)
        td, pos = select_k_fn(d.reshape(block * GROUP, cap), kt,
                              select_min=select_min)
        ti = jnp.take_along_axis(ids[:, None, :],
                                 pos.reshape(block, GROUP, kt), axis=2)
        return None, (td, ti.reshape(block * GROUP, kt), slot.reshape(-1))

    outd = jnp.full((P, kt), worst, jnp.float32)
    outi = jnp.full((P, kt), -1, jnp.int32)

    _, (tds, tis, flats) = jax.lax.scan(step, None, block_starts)
    flat = flats.reshape(-1)
    outd = outd.at[flat].set(tds.reshape(-1, kt), mode="drop")
    outi = outi.at[flat].set(tis.reshape(-1, kt), mode="drop")
    return outd, outi
