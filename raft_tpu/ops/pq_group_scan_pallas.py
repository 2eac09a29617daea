"""Pallas TPU kernels: grouped PQ-reconstruction scans + top-k in VMEM.

The ``compute_similarity_kernel`` analogue (reference:
neighbors/detail/ivf_pq_search.cuh:611) for the grouped search layout
(:mod:`raft_tpu.neighbors.grouped`): one program per pair-group computes
the group's (GROUP, cap) quantized L2 distances on the MXU and extracts
each row's top-kt **in VMEM**, so the distance matrix never reaches HBM.

Structure per program ``g``:

- the scalar-prefetched ``group_list`` drives the BlockSpec index maps —
  the list's bf16 reconstructions, squared norms, and candidate ids are
  DMA'd directly by list id (the TPU equivalent of the reference
  assigning one CTA per (list, query-group));
- the group's rotated queries come from a query table resident in VMEM
  for the whole grid.  The FUSED kernels (:func:`grouped_l2_scan_fused`
  and the codes twin) copy each slot's row by index, the query ids
  streamed per group through SMEM: O(GROUP) row copies per step,
  whatever the batch.  The non-fused kernels gather by a one-hot MXU
  contraction against the exact three-part bf16 split of the table
  (:func:`query_table`), whose cost grows with the batch;
- residuals against the list center, the distance GEMM
  ``d = ||sub||^2 + ||recon||^2 - 2 sub.recon``, and kt passes of
  max / where-iota argmin / mask extract the top-kt per row — all in
  VMEM;
- selected positions map to **global candidate ids** by a masked reduce
  over the list's id row (ids < 2^24 are exact in f32), so the XLA side
  needs no post-hoc id gather.

The non-fused kernels output per-pair (values, global ids); callers
scatter them into the (P, kt) buffers by pair slot.  Rows with fewer
than kt finite candidates emit +inf values; callers map those to the -1
id sentinel (valid L2 distances are finite).  The fused kernels merge
into a per-query accumulator instead and output each query's final
top-k (see the section comment above :func:`slot_query_rows`).

On one v5e at the SIFT-1M IVF-PQ cell's shape (4,096 lists of 416 rows,
k = kt = 20, 6,909 pair groups) the fused recon kernel's one-hot
addressing of a 5,000-query batch cost 11.1 us per grid step against
7.5 us at 640 queries; row addressing reads 7.6 us at either width with
every slot live, and 6.8 us for a 64-query batch, whose groups hold
about one live slot each (profiles/fused_scan_addressing.py).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.neighbors.grouped import GROUP
from raft_tpu.ops import vmem_budget as vb

# extraction switches from unrolled static-lane passes to a fori_loop
# with transposed scratch above this kt (see _extract_topk)
_KT_UNROLL = 64
_KT_MAX = 128

# Finite "worst distance" sentinel of the fused accumulator: exhausted
# ranks hold a large FINITE value, so no inf ever meets a product or a
# comparison chain in the merge, and the epilogue maps values past
# _ACC_WORST/2 to the public +inf / id -1 contract.
_ACC_WORST = 3.0e38

# The non-fused kernels' one-hot query gather must move f32 rows
# bit-exact, and Mosaic's default contraction precision rounds f32
# operands to bf16.  So the table is split into three bf16 parts that
# together hold its 24-bit significand: a bf16 one-hot times a bf16 part
# is exact and every output takes at most one non-zero product, so three
# single bf16 passes sum back exactly — half the passes of a
# ``Precision.HIGHEST`` contraction.


def _split3(x):
    """Three bf16 parts of f32 ``x`` whose f32 sum is ``x`` exactly.

    Each part keeps the top 8 significant bits of what is left (the low
    16 bits masked off), so no part ever rounds and the split holds
    under any compiler that folds an f32 -> bf16 -> f32 round trip."""
    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.int32)
        return jax.lax.bitcast_convert_type(bits & jnp.int32(-65536),
                                            jnp.float32)
    hi = top(x)
    rest = x - hi
    mid = top(rest)
    lo = rest - mid
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, lo))


def query_table(q, nq_pad: int, width: int):
    """The non-fused scan kernels' VMEM-resident query table: ``q``
    zero-padded to (nq_pad, width) f32 (:func:`row_table`) and stored as
    its (3, nq_pad, width) bf16 :func:`_split3` parts, split once per
    search instead of once per grid step.  Padded rows are the zero row
    empty slots gather."""
    return jnp.stack(_split3(row_table(q, nq_pad, width)))


def _gather_rows(onehot, q_ref):
    """(G, nq_pad) one-hot x the (3, nq_pad, d) split table -> exact
    (G, d) f32 rows, one bf16 pass per part."""
    ohb = onehot.astype(jnp.bfloat16)
    out = None
    for i in range(q_ref.shape[0]):
        p = jax.lax.dot_general(ohb, q_ref[i], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        out = p if out is None else out + p
    return out


def _scratch_shapes(kt):
    if kt <= _KT_UNROLL:
        shape = (GROUP, kt)
    else:
        shape = (-(-kt // 8) * 8, GROUP)
    return [pltpu.VMEM(shape, jnp.float32), pltpu.VMEM(shape, jnp.int32)]


def _gather_queries(slot_ref, q_ref, n_probes, P):
    """One-hot MXU row gather of the group's queries from the
    VMEM-resident :func:`query_table`, exact in f32 (one product per
    output) — a plain bf16 table would round |q| before any center
    subtraction, which can exceed the residual magnitude on
    well-clustered data.  Sentinel slots gather the zero row."""
    nq_pad = q_ref.shape[1]
    slot = slot_ref[0, 0]                              # (G,) int32 pair ids
    qid = jnp.where(slot < P, slot // n_probes, nq_pad - 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (GROUP, nq_pad), 1)
    onehot = (cols == qid[:, None]).astype(jnp.bfloat16)
    return _gather_rows(onehot, q_ref)                 # (G, d)


def _unpack_admission(adm_ref, cap):
    """In-kernel unpack of the packed per-(slot, candidate) admission
    words — (1, GROUP, Wc) int32, bit b of word w admitting candidate
    ``32*w + b`` (the layout :func:`raft_tpu.filters.bitset.pack_mask`
    writes, built per group by ``group_admission_words``) — to a
    (GROUP, cap) 0/1 block.  One shift/mask per word: admission costs
    ~1 bit of VMEM streaming per candidate."""
    aw = adm_ref[0]                                    # (GROUP, Wc) int32
    shifts = jax.lax.broadcasted_iota(jnp.int32, aw.shape + (32,), 2)
    bits = (aw[:, :, None] >> shifts) & 1
    return bits.reshape(aw.shape[0], -1)[:, :cap]


def _kernel(gl_ref, slot_ref, qrot_ref, cf_ref, data_ref, rsq_ref, ids_ref,
            *rest, kt, n_probes, P, has_adm=False):
    adm_ref, rest = (rest[0], rest[1:]) if has_adm else (None, rest)
    vals_ref, ids_out_ref, vscratch, pscratch = rest
    qv = _gather_queries(slot_ref, qrot_ref, n_probes, P)
    sub = qv - cf_ref[0, 0][None, :]                   # (G, rot) f32
    sub_sq = jnp.sum(sub * sub, axis=1)                # (G,)
    data = data_ref[0]                                 # (cap, rot) bf16
    ip = jax.lax.dot_general(sub.astype(jnp.bfloat16), data,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    d = sub_sq[:, None] + rsq_ref[0, 0][None, :] - 2.0 * ip
    d = jnp.maximum(d, 0.0)
    ids_row = ids_ref[0, 0]                            # (cap,) int32
    adm = _unpack_admission(adm_ref, d.shape[1]) if has_adm else None
    _extract_topk(d, ids_row, vals_ref, ids_out_ref, vscratch, pscratch,
                  kt, adm=adm)


def _extract_topk(d, ids_row, vals_ref, ids_out_ref, vscratch, pscratch,
                  kt, adm=None):
    """Shared in-VMEM top-kt extraction + position -> global-id mapping.

    kt passes of max / where-iota argmin / mask over the (G, cap) block;
    the id map is a masked reduce against the list's id row per pass
    (a single (G*kt, cap) one-hot matmul would cost ~5 MB of VMEM).

    kt <= _KT_UNROLL: unrolled passes writing static scratch lanes (the
    proven hot path).  Larger kt (radix-select regime, k to 128+ —
    reference select_radix.cuh): a ``fori_loop`` with dynamic SUBLANE
    stores into (kt, G)-transposed scratch — dynamic stores on the lane
    dim are Mosaic-hostile, on the sublane dim they are cheap — then one
    in-VMEM transpose on the way out."""
    invalid = (ids_row < 0)[None, :]
    if adm is not None:
        # per-(slot, candidate) admission bit: a rejected candidate
        # folds exactly like a tombstone — excluded before any
        # selection pass, through the same finite-sentinel seam
        invalid = invalid | (adm == 0)
    neg = jnp.where(invalid, -jnp.inf, -d)             # select-min as max

    cap = neg.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, neg.shape, 1)
    ids_f = ids_row.astype(jnp.float32)                # exact below 2^24

    def step(neg):
        m = jnp.max(neg, axis=1)                       # (G,)
        # where-iota argmax (ties -> lowest column, stable like sort)
        p = jnp.min(jnp.where(neg == m[:, None], col, cap), axis=1)
        p = jnp.minimum(p, cap - 1)                    # all -inf row guard
        sel = col == p[:, None]
        gid = jnp.max(jnp.where(sel, ids_f[None, :], -jnp.inf), axis=1)
        return m, sel, gid

    if kt <= _KT_UNROLL:
        for j in range(kt):
            m, sel, gid = step(neg)
            vscratch[:, j] = -m
            pscratch[:, j] = gid.astype(jnp.int32)
            neg = jnp.where(sel, -jnp.inf, neg)
        vals_ref[0] = vscratch[:, :]
        ids_out_ref[0] = pscratch[:, :]
    else:
        def body(j, neg):
            m, sel, gid = step(neg)
            vscratch[pl.ds(j, 1), :] = (-m)[None, :]
            pscratch[pl.ds(j, 1), :] = gid.astype(jnp.int32)[None, :]
            return jnp.where(sel, -jnp.inf, neg)

        jax.lax.fori_loop(0, kt, body, neg, unroll=False)
        vals_ref[0] = vscratch[:kt, :].T
        ids_out_ref[0] = pscratch[:kt, :].T


# ---------------------------------------------------------------------------
# fused in-kernel top-k: candidates never touch HBM
# ---------------------------------------------------------------------------
#
# The non-fused kernels emit (n_groups, GROUP, kt) per-pair winners that
# the XLA side scatters into (P, kt) buffers and reduces with a final
# select.  The fused variants exploit the TPU grid's SEQUENTIAL
# execution: a query-major (nq_pad, acc_lanes(k)) per-query accumulator
# lives in VMEM scratch across ALL grid steps, each group's local top-kt
# is merged into its queries' rows in-kernel, and only the final answers
# are copied to HBM on the last step.  No scatter, no final select.
#
# Both the query table and the accumulator are addressed by ROW.  Each
# slot's query row (``slot // n_probes``; the zero padding row
# ``nq_pad - 1`` for an empty slot) and the group's live-slot count are
# computed once in XLA (:func:`slot_query_rows`) and streamed one group
# per step into SMEM; the step copies its live slots' query rows and
# accumulator rows out by dynamic single-row reads, merges in the
# sublane-stacked (rows, GROUP) layout, and writes the accumulator rows
# back.  A group's real slots come first (``grouped.build_groups`` fills
# slots in rank order), so the copies stop at the live count, rounded up
# to the copy loop's unroll, and the lanes past the count merge stale
# rows that are never written back.  The live groups come first too, so
# the steps past the batch's live group count (:func:`live_groups`) do
# no work at all and fetch nothing: the static grid's all-empty tail
# costs a scalar compare per step.  Every real slot of a group holds a
# DISTINCT query (a group is one list; a query probes each list at most
# once), so the write-back touches each real row once; empty slots
# inside the live range read and write the padding row, which is never
# returned.  Row copies are exact, so neither the query table nor the
# accumulator needs the three-part split the one-hot contractions of the
# non-fused query gather take, and no per-step array has an nq_pad
# dimension.


def slot_query_rows(slot_pairs, n_probes: int, P: int, nq_pad: int):
    """(n_groups, GROUP) pair slots -> (n_groups, 2, GROUP) int32: row 0
    holds each slot's query-table row (``slot // n_probes`` for a real
    slot, the padding row ``nq_pad - 1`` for the empty-slot sentinel
    ``P``), row 1 the group's live-slot count (one past its last real
    slot) in every lane."""
    real = slot_pairs < P
    rows = jnp.where(real, slot_pairs // n_probes, nq_pad - 1)
    live = jnp.max(jnp.where(real, jnp.arange(1, GROUP + 1), 0), axis=1)
    return jnp.stack([rows, jnp.broadcast_to(live[:, None], rows.shape)],
                     axis=1).astype(jnp.int32)


def row_table(q, nq_pad: int, width: int):
    """The fused kernels' query table: ``q`` zero-padded to
    (nq_pad, width) f32, one copy (rows are copied, never contracted)."""
    nq, d = q.shape
    return jnp.zeros((nq_pad, width), jnp.float32).at[:nq, :d].set(
        q.astype(jnp.float32))


def _topk_rows(d, ids_row, kt, adm=None):
    """Local top-kt of a (G, cap) distance block as sublane-stacked
    (kt, G) value/id rows — the fused twin of :func:`_extract_topk`
    (same max / where-iota argmin / masked-id-reduce passes), except
    results stay in registers for the in-kernel merge and exhausted
    slots carry the finite ``_ACC_WORST`` instead of +inf.  ``adm``
    folds per-(slot, candidate) admission bits through the same seam
    BEFORE any value reaches the accumulator."""
    invalid = (ids_row < 0)[None, :]
    if adm is not None:
        invalid = invalid | (adm == 0)
    neg = jnp.where(invalid, -jnp.inf, -d)
    cap = neg.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, neg.shape, 1)
    ids_f = ids_row.astype(jnp.float32)                # exact below 2^24
    vs, gs = [], []
    for _ in range(kt):
        m = jnp.max(neg, axis=1)                       # (G,)
        p = jnp.min(jnp.where(neg == m[:, None], col, cap), axis=1)
        p = jnp.minimum(p, cap - 1)                    # all -inf row guard
        sel = col == p[:, None]
        gid = jnp.max(jnp.where(sel, ids_f[None, :], -jnp.inf), axis=1)
        v = jnp.where(jnp.isinf(m), _ACC_WORST, -m)
        vs.append(v[None, :])
        gs.append(gid[None, :])
        neg = jnp.where(sel, -jnp.inf, neg)
    return jnp.concatenate(vs, 0), jnp.concatenate(gs, 0)   # (kt, G)


def _merge_topk(cat_v, cat_i, k, mer_v, mer_i):
    """k selection passes over sublane-stacked (rows, G) candidates —
    the accumulator's sorted k rows, then a group's local kt rows —
    into rows ``[:k]`` of the (acc_lanes(k), G) ``mer_v`` / ``mer_i``
    scratch.  Cross-SUBLANE reduces; the lane axis stays the 128 pair
    slots.  Each pass takes the minimum, breaks ties to the lowest row
    (accumulator before new, stable like sort), reads the winner's id
    by a masked reduce and re-masks the winner to ``_ACC_WORST``.

    k past the unrolled regime runs as a ``fori_loop`` with dynamic
    sublane stores into the scratch (the candidates are materialized
    before the loop, so the stores never feed back into the carry)."""
    rows_n = cat_v.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, cat_v.shape, 0)

    def step(cat_v):
        m = jnp.min(cat_v, axis=0)                     # (G,)
        p = jnp.min(jnp.where(cat_v == m[None, :], rows, rows_n), axis=0)
        p = jnp.minimum(p, rows_n - 1)
        sel = rows == p[None, :]
        gi = jnp.max(jnp.where(sel, cat_i, -jnp.inf), axis=0)
        return m, sel, gi

    if k <= _KT_UNROLL:
        out_v, out_i = [], []
        for _ in range(k):
            m, sel, gi = step(cat_v)
            out_v.append(m[None, :])
            out_i.append(gi[None, :])
            cat_v = jnp.where(sel, _ACC_WORST, cat_v)
        mer_v[:k, :] = jnp.concatenate(out_v, 0)
        mer_i[:k, :] = jnp.concatenate(out_i, 0)
    else:
        def body(j, cat_v):
            m, sel, gi = step(cat_v)
            mer_v[pl.ds(j, 1), :] = m[None, :]
            mer_i[pl.ds(j, 1), :] = gi[None, :]
            return jnp.where(sel, _ACC_WORST, cat_v)

        jax.lax.fori_loop(0, k, body, cat_v, unroll=False)


# rows copied per unrolled iteration of the per-slot row loops
_ROW_UNROLL = 8


def _copy_slot_rows(qid_ref, pairs, *, gather):
    """Per-slot row copies between the resident tables and the step's
    (GROUP, width) row blocks, for every ``(table, block)`` in
    ``pairs``: ``block[r] = table[qid[r]]`` (``gather``) or
    ``table[qid[r]] = block[r]``, over the group's live slots (the count
    :func:`slot_query_rows` streams) rounded up to ``_ROW_UNROLL``.
    Slots run in order, so the empty slots' writes to the shared padding
    row land last-wins."""
    def body(i, carry):
        # Mosaic lowers a fori_loop only fully unrolled or not at all:
        # unroll by hand, _ROW_UNROLL slots per iteration
        for j in range(_ROW_UNROLL):
            r = i * _ROW_UNROLL + j
            q = qid_ref[0, 0, r]
            for table, block in pairs:
                if gather:
                    block[pl.ds(r, 1), :] = table[pl.ds(q, 1), :]
                else:
                    table[pl.ds(q, 1), :] = block[pl.ds(r, 1), :]
        return carry

    n_iter = (qid_ref[0, 1, 0] + _ROW_UNROLL - 1) // _ROW_UNROLL
    jax.lax.fori_loop(0, n_iter, body, 0)


def _fused_init(q_hbm, qtab, acc_v, acc_i, qrows, rows_v, rows_i, mer_v,
                mer_i):
    """Step 0: copy the query table into VMEM once and fill the
    accumulator, the row blocks (whose lanes past a group's live slots
    are never copied into) and the merged rows' pad lanes with zeros or
    the finite sentinel pair."""
    pltpu.sync_copy(q_hbm, qtab)
    qrows[...] = jnp.zeros(qrows.shape, jnp.float32)
    for ref, fill in ((acc_v, _ACC_WORST), (acc_i, -1.0),
                      (rows_v, _ACC_WORST), (rows_i, -1.0),
                      (mer_v, _ACC_WORST), (mer_i, -1.0)):
        ref[...] = jnp.full(ref.shape, fill, jnp.float32)


def _fused_merge(qid_ref, d, ids_row, kt, k, acc_v, acc_i, rows_v, rows_i,
                 mer_v, mer_i, adm=None):
    """Merge one group's (G, cap) distances into its queries'
    accumulator rows: local top-kt, read the slots' rows, transpose to
    the sublane-stacked layout, merge sorted k + kt candidates per slot,
    transpose back, write the rows."""
    new_v, new_i = _topk_rows(d, ids_row, kt, adm=adm)  # (kt, G)
    _copy_slot_rows(qid_ref, ((acc_v, rows_v), (acc_i, rows_i)),
                    gather=True)
    old_v = rows_v[...].T[:k]                          # (k, G)
    old_i = rows_i[...].T[:k]
    _merge_topk(jnp.concatenate([old_v, new_v], 0),
                jnp.concatenate([old_i, new_i], 0), k, mer_v, mer_i)
    rows_v[...] = mer_v[...].T
    rows_i[...] = mer_i[...].T
    _copy_slot_rows(qid_ref, ((acc_v, rows_v), (acc_i, rows_i)),
                    gather=False)


def _kernel_fused(gl_ref, nlive_ref, qid_ref, q_hbm, cf_ref, data_ref,
                  rsq_ref, ids_ref, *rest, kt, k, n_groups, has_adm=False):
    """Fused recon scan: the non-fused ``_kernel`` distance block plus
    the in-kernel per-query merge; the final query-major answers are
    copied to HBM once, on the last grid step.  Steps past the batch's
    live groups (``nlive_ref``) scan nothing."""
    adm_ref, rest = (rest[0], rest[1:]) if has_adm else (None, rest)
    (vals_hbm, ids_hbm, qtab, acc_v, acc_i, qrows, rows_v, rows_i, mer_v,
     mer_i) = rest
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        _fused_init(q_hbm, qtab, acc_v, acc_i, qrows, rows_v, rows_i,
                    mer_v, mer_i)

    @pl.when(g < nlive_ref[0])
    def _scan():
        _copy_slot_rows(qid_ref, ((qtab, qrows),), gather=True)
        sub = qrows[...] - cf_ref[0, 0][None, :]       # (G, rot) f32
        sub_sq = jnp.sum(sub * sub, axis=1)            # (G,)
        data = data_ref[0]                             # (cap, rot) bf16
        ip = jax.lax.dot_general(sub.astype(jnp.bfloat16), data,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        d = sub_sq[:, None] + rsq_ref[0, 0][None, :] - 2.0 * ip
        d = jnp.maximum(d, 0.0)
        adm = _unpack_admission(adm_ref, d.shape[1]) if has_adm else None
        _fused_merge(qid_ref, d, ids_ref[0, 0], kt, k, acc_v, acc_i,
                     rows_v, rows_i, mer_v, mer_i, adm=adm)

    @pl.when(g == n_groups - 1)
    def _flush():
        pltpu.sync_copy(acc_v, vals_hbm)
        pltpu.sync_copy(acc_i, ids_hbm)


def live_groups(qid_rows):
    """(1,) int32: one past the last group of :func:`slot_query_rows`'s
    output that holds a real slot.  ``grouped.build_groups`` lays the
    live groups first, so this is the number of live groups, and every
    group from it on is an all-empty tail group."""
    live = qid_rows[:, 1, 0] > 0
    n = jnp.arange(1, live.shape[0] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(live, n, 0), keepdims=True)


def _at_live(index_map):
    """An index map of (step, group_list) taken at the last live step for
    every tail step: a tail step's blocks are the ones already in VMEM,
    so it fetches nothing."""
    return lambda g, gl, n_live: index_map(
        jnp.minimum(g, jnp.maximum(n_live[0] - 1, 0)), gl)


def fused_scan_call(kernel, *, group_list, slot_pairs, n_probes, P,
                    q_table, stream_specs, stream_inputs, adm_words, k,
                    total_bytes, interpret):
    """One ``pallas_call`` of a fused scan kernel over the pair groups:
    the per-slot query rows and live-slot counts
    (:func:`slot_query_rows`) stream into SMEM, the query table and both
    outputs stay in HBM (the kernel copies them itself), and
    ``stream_specs`` / ``stream_inputs`` are the kernel's per-list
    blocks, their index maps taking ``(step, group_list)``.  The live
    group count (:func:`live_groups`) is the second scalar-prefetch
    operand: the kernel skips the steps past it, and every block index
    stays at the last live step's, so the all-empty tail of the static
    grid neither computes nor fetches.  Returns the final ``(vals
    (nq_pad, k) f32, ids (nq_pad, k) int32)``.  Call from the jitted
    scan function: the Pallas call's instruction is named after it."""
    n_groups = group_list.shape[0]
    nq_pad, width = q_table.shape
    kl = vb.acc_lanes(k)
    qid_rows = slot_query_rows(slot_pairs, n_probes, P, nq_pad)
    in_specs = [pl.BlockSpec((1, 2, GROUP), lambda g, gl: (g, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY), *stream_specs]
    inputs = [group_list, live_groups(qid_rows), qid_rows, q_table,
              *stream_inputs]
    if adm_words is not None:
        in_specs.append(pl.BlockSpec((1, GROUP, adm_words.shape[2]),
                                     lambda g, gl: (g, 0, 0)))
        inputs.append(adm_words)
    in_specs = [s if s.index_map is None
                else dataclasses.replace(s, index_map=_at_live(s.index_map))
                for s in in_specs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_groups,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=vb.fused_scan_scratch(k, nq_pad, width, GROUP),
    )
    vals, ids = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((nq_pad, kl), jnp.float32),
                   jax.ShapeDtypeStruct((nq_pad, kl), jnp.float32)],
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vb.fused_scan_vmem_limit(total_bytes)),
        interpret=interpret,
    )(*inputs)
    return vals[:, :k], ids[:, :k].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("kt", "k", "n_probes",
                                             "interpret"))
def grouped_l2_scan_fused(group_list, slot_pairs, qrot, centers_f32,
                          list_recon, rec_sq, list_indices, kt, k, n_probes,
                          interpret=False, adm_words=None):
    """Fused grouped recon scan with IN-KERNEL per-query top-k.

    Inputs as :func:`grouped_l2_scan`; instead of per-pair winners the
    kernel returns the batch's FINAL per-query answers —
    ``(vals (nq_pad, k) f32, ids (nq_pad, k) int32)`` sorted ascending
    per row, query q in row q.  Exhausted ranks carry values at the
    finite ``_ACC_WORST`` sentinel (callers map values past
    ``_ACC_WORST/2`` to +inf / id -1).  ``kt`` bounds the per-(query,
    probe) keep-set exactly like the non-fused path: each group
    contributes at most its local top-kt per pair before the merge, so
    results match the scatter+select reference at matched kt.

    ``adm_words`` (n_groups, GROUP, ceil(cap/32)) int32 streams packed
    per-(slot, candidate) admission bits (filtered search): rejected
    candidates fold to the finite sentinel before the merge.
    """
    nq, rot = qrot.shape
    _, cap, _ = list_recon.shape
    stream_specs = [
        pl.BlockSpec((1, 1, rot), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, cap, rot), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
    ]
    stream_inputs = [centers_f32[:, None, :], list_recon,
                     rec_sq[:, None, :], list_indices[:, None, :]]
    kernel = functools.partial(_kernel_fused, kt=kt, k=k,
                               n_groups=group_list.shape[0],
                               has_adm=adm_words is not None)
    return fused_scan_call(
        kernel, group_list=group_list, slot_pairs=slot_pairs,
        n_probes=n_probes, P=nq * n_probes,
        q_table=row_table(qrot, vb.nq_padded(nq), rot),
        stream_specs=stream_specs, stream_inputs=stream_inputs,
        adm_words=adm_words, k=k,
        total_bytes=_fused_bytes(cap, rot, kt, k, nq,
                                 list_recon.dtype.itemsize),
        interpret=interpret)


def fused_stream_bytes(cap: int, data_bytes: int) -> int:
    """A fused scan's streamed VMEM besides the query table and the
    accumulator: the double-buffered per-list ``data_bytes`` block, its
    norm / id rows and admission words (sublane- and lane-padded), and
    the (GROUP, cap) distance block with its extraction temporaries."""
    return (2 * data_bytes
            + 2 * 2 * 8 * cap * 4
            + 2 * GROUP * vb.round_up(-(-cap // 32), 128) * 4
            + 2 * GROUP * cap * 4)


def _fused_bytes(cap: int, rot: int, kt: int, k: int, nq: int,
                 data_elem_bytes: int) -> int:
    return vb.fused_scan_bytes(
        k, kt, vb.nq_padded(nq), rot, GROUP,
        fused_stream_bytes(cap, cap * rot * data_elem_bytes))


def _fused_static_ok(metric_is_l2: bool, cap: int, rot: int, kt: int,
                     k: int, nq: int) -> bool:
    return (metric_is_l2 and rot % 128 == 0 and cap % 16 == 0
            and GROUP % 16 == 0 and 0 < kt <= _KT_UNROLL
            and 0 < k <= vb.FUSED_K_MAX and nq <= 6144)


def supported_fused(metric_is_l2: bool, cap: int, rot: int, kt: int,
                    k: int, nq: int, data_elem_bytes: int = 2) -> bool:
    """Shapes the fused recon kernel handles.  Beyond :func:`supported`:
    the resident query table and (nq_pad, acc_lanes(k)) accumulator pair
    join the VMEM model (:mod:`raft_tpu.ops.vmem_budget`); kt stays in
    the unrolled regime while k extends to ``FUSED_K_MAX`` through the
    looped per-step merge."""
    return (_fused_static_ok(metric_is_l2, cap, rot, kt, k, nq)
            and vb.fused_scan_fits(
                _fused_bytes(cap, rot, kt, k, nq, data_elem_bytes)))


def fused_reject_reason(metric_is_l2: bool, cap: int, rot: int, kt: int,
                        k: int, nq: int, data_elem_bytes: int = 2) -> str:
    """Reason code for a fused-recon gate miss ('' when supported):
    'dtype' (metric), 'k-too-large' (k/kt bounds), 'bucket-too-wide'
    (batch, layout, or VMEM).  Drives the ``fused_fallback`` counter
    attrs and flight events."""
    if not metric_is_l2:
        return "dtype"
    if not (0 < kt <= _KT_UNROLL and 0 < k <= vb.FUSED_K_MAX):
        return "k-too-large"
    if not (rot % 128 == 0 and cap % 16 == 0 and GROUP % 16 == 0
            and nq <= 6144):
        return "bucket-too-wide"
    if not vb.fused_scan_fits(
            _fused_bytes(cap, rot, kt, k, nq, data_elem_bytes)):
        return "bucket-too-wide"
    return ""


def _kernel_flat(gl_ref, slot_ref, q_ref, data_ref, dsq_ref, ids_ref,
                 *rest, kt, n_probes, P, has_adm=False):
    """IVF-Flat variant: exact fp32 distances over raw list vectors
    (d = ||q||^2 + ||x||^2 - 2 q.x), same gather/extraction structure."""
    adm_ref, rest = (rest[0], rest[1:]) if has_adm else (None, rest)
    vals_ref, ids_out_ref, vscratch, pscratch = rest
    qv = _gather_queries(slot_ref, q_ref, n_probes, P)
    q_sq = jnp.sum(qv * qv, axis=1)                    # (G,)
    data = data_ref[0]                                 # (cap, d) f32
    ip = jax.lax.dot_general(qv, data, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    d = jnp.maximum(q_sq[:, None] + dsq_ref[0, 0][None, :] - 2.0 * ip, 0.0)
    ids_row = ids_ref[0, 0]                            # (cap,) int32
    adm = _unpack_admission(adm_ref, d.shape[1]) if has_adm else None
    _extract_topk(d, ids_row, vals_ref, ids_out_ref, vscratch, pscratch,
                  kt, adm=adm)


@functools.partial(jax.jit, static_argnames=("kt", "n_probes", "interpret"))
def grouped_l2_scan(group_list, slot_pairs, qrot, centers_f32, list_recon,
                    rec_sq, list_indices, kt, n_probes, interpret=False,
                    adm_words=None):
    """Fused query-gather + distance + local top-kt over all pair groups.

    ``group_list`` (n_groups,) int32; ``slot_pairs`` (n_groups, GROUP)
    int32 pair ids with P = nq * n_probes as the empty sentinel;
    ``qrot`` (nq, rot) f32 rotated queries; ``centers_f32`` (n_lists, rot)
    f32; ``list_recon`` (n_lists, cap, rot) bf16; ``rec_sq`` (n_lists,
    cap) f32; ``list_indices`` (n_lists, cap) int32.  Returns
    ``(vals (n_groups, GROUP, kt) f32, ids ... int32)`` sorted ascending
    (L2); exhausted rows carry +inf values (callers map them to -1 ids).

    ``adm_words`` (n_groups, GROUP, ceil(cap/32)) int32, optional:
    packed per-(slot, candidate) admission bits in list-slot order
    (:func:`raft_tpu.filters.bitset.group_admission_words`); rejected
    candidates fold like tombstones before extraction.
    """
    n_groups = group_list.shape[0]
    nq, rot = qrot.shape
    _, cap, _ = list_recon.shape
    P = nq * n_probes

    # pad the query table to a lane-friendly height; the sentinel row
    # (all zeros, index nq_pad-1) is what empty slots gather
    nq_pad = vb.nq_padded(nq)
    qrot_pad = query_table(qrot, nq_pad, rot)

    has_adm = adm_words is not None
    in_specs = [
        pl.BlockSpec((1, 1, GROUP), lambda g, gl: (g, 0, 0)),
        pl.BlockSpec((3, nq_pad, rot), lambda g, gl: (0, 0, 0)),
        pl.BlockSpec((1, 1, rot), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, cap, rot), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
    ]
    inputs = [group_list, slot_pairs[:, None, :], qrot_pad,
              centers_f32[:, None, :], list_recon, rec_sq[:, None, :],
              list_indices[:, None, :]]
    if has_adm:
        wc = adm_words.shape[2]
        in_specs.append(pl.BlockSpec((1, GROUP, wc),
                                     lambda g, gl: (g, 0, 0)))
        inputs.append(adm_words)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_groups,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, GROUP, kt), lambda g, gl: (g, 0, 0)),
            pl.BlockSpec((1, GROUP, kt), lambda g, gl: (g, 0, 0)),
        ],
        scratch_shapes=_scratch_shapes(kt),
    )
    vals, gids = pl.pallas_call(
        functools.partial(_kernel, kt=kt, n_probes=n_probes, P=P,
                          has_adm=has_adm),
        out_shape=[
            jax.ShapeDtypeStruct((n_groups, GROUP, kt), jnp.float32),
            jax.ShapeDtypeStruct((n_groups, GROUP, kt), jnp.int32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
    )(*inputs)
    return vals, gids


@functools.partial(jax.jit, static_argnames=("kt", "n_probes", "interpret"))
def grouped_flat_l2_scan(group_list, slot_pairs, queries_f32, list_data,
                         d_sq, list_indices, kt, n_probes, interpret=False,
                         adm_words=None):
    """IVF-Flat fused scan: exact fp32 distances over raw list vectors.
    Same contract as :func:`grouped_l2_scan` with ``queries_f32``
    (nq, dim) raw queries, ``list_data`` (n_lists, cap, dim) fp32 and
    ``d_sq`` (n_lists, cap) fp32 row norms."""
    n_groups = group_list.shape[0]
    nq, dim = queries_f32.shape
    _, cap, _ = list_data.shape
    P = nq * n_probes

    nq_pad = vb.nq_padded(nq)
    q_pad = query_table(queries_f32, nq_pad, dim)

    has_adm = adm_words is not None
    in_specs = [
        pl.BlockSpec((1, 1, GROUP), lambda g, gl: (g, 0, 0)),
        pl.BlockSpec((3, nq_pad, dim), lambda g, gl: (0, 0, 0)),
        pl.BlockSpec((1, cap, dim), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
    ]
    inputs = [group_list, slot_pairs[:, None, :], q_pad,
              list_data.astype(jnp.float32), d_sq[:, None, :],
              list_indices[:, None, :]]
    if has_adm:
        wc = adm_words.shape[2]
        in_specs.append(pl.BlockSpec((1, GROUP, wc),
                                     lambda g, gl: (g, 0, 0)))
        inputs.append(adm_words)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_groups,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, GROUP, kt), lambda g, gl: (g, 0, 0)),
            pl.BlockSpec((1, GROUP, kt), lambda g, gl: (g, 0, 0)),
        ],
        scratch_shapes=_scratch_shapes(kt),
    )
    vals, gids = pl.pallas_call(
        functools.partial(_kernel_flat, kt=kt, n_probes=n_probes, P=P,
                          has_adm=has_adm),
        out_shape=[
            jax.ShapeDtypeStruct((n_groups, GROUP, kt), jnp.float32),
            jax.ShapeDtypeStruct((n_groups, GROUP, kt), jnp.int32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
    )(*inputs)
    return vals, gids


def supported(metric_is_l2: bool, cap: int, rot: int, kt: int,
              nq: int, data_elem_bytes: int = 2) -> bool:
    """Shapes the kernel handles; callers fall back to the XLA scan
    otherwise.  Lane dims must be 128-aligned (rot) or tile-aligned
    (cap); kt is bounded to keep the extraction loop sane; the
    query table, its per-program one-hot, the per-list data block, and
    the (GROUP, cap) distance block all live in VMEM, so their summed
    footprint is bounded (the one-hot gather cost also grows with nq —
    larger batches should be split by the caller anyway).

    Candidate-id f32-exactness (|id| < 2^24, required by the f32 id
    lanes of the extraction and the fused merge) is data-dependent and checked by the caller on the
    index's actual ids (:func:`raft_tpu.neighbors.grouped.ids_f32_exact`)
    — user-supplied ids from ``extend(new_indices=...)`` can exceed any
    row-count proxy."""
    nq_pad = -(-(nq + 1) // 128) * 128
    vmem = (2 * nq_pad * rot * 4              # query table + one-hot
            + cap * rot * data_elem_bytes     # per-list data block
            + 2 * GROUP * cap * 4)            # distances + extraction temps
    return (metric_is_l2 and rot % 128 == 0 and cap % 16 == 0
            and GROUP % 16 == 0 and 0 < kt <= _KT_MAX
            and nq <= 6144 and vmem <= (10 << 20))
