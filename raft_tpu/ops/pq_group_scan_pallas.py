"""Pallas TPU kernel: fused grouped PQ-reconstruction scan + local top-k.

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
- the group's rotated queries are gathered from the VMEM-resident
  ``qrot`` table (it is only nq x rot ~ a few MB) by a **one-hot MXU
  matmul** — Mosaic has no native row-gather, and the XLA-side gather
  this replaces measured ~120 ms/batch at bench shapes versus a few ms
  of MXU time for the one-hot contraction;
- residuals against the list center, the distance GEMM
  ``d = ||sub||^2 + ||recon||^2 - 2 sub.recon``, and kt passes of
  max / where-iota argmin / mask extract the top-kt per row — all in
  VMEM;
- selected positions map to **global candidate ids** by a second one-hot
  contraction against the list's id row (ids < 2^24 are exact in f32),
  so the XLA side needs no post-hoc id gather.

Outputs are per-pair (values, global ids); callers scatter them into the
(P, kt) buffers by pair slot.  Rows with fewer than kt finite candidates
emit +inf values; callers map those to the -1 id sentinel (valid L2
distances are finite).
"""

from __future__ import annotations

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

# Finite "worst distance" sentinel of the fused accumulator.  The
# accumulator is read and written through one-hot f32 contractions, and
# IEEE 0 * inf = nan would leak a +inf sentinel into every gathered row
# — so the fused kernels keep exhausted slots at a large FINITE value
# and the epilogue maps values past _ACC_WORST/2 to the public
# +inf / id -1 contract.
_ACC_WORST = 3.0e38

# The one-hot contractions move f32 values and f32-encoded ids that must
# arrive bit-exact, and Mosaic's default contraction precision rounds f32
# operands to bf16 (ids past 256 come back as other ids).  So every f32
# operand of a one-hot contraction is split into three bf16 parts that
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


def _parts_dot(parts, oh, dims, onehot_lhs=False):
    """Exact f32 ``dot_general(x, oh)`` (``dot_general(oh, x)`` with
    ``onehot_lhs``) from the :func:`_split3` parts of ``x`` against a
    0/1 one-hot ``oh``."""
    ohb = oh.astype(jnp.bfloat16)
    out = None
    for part in parts:
        lhs, rhs = (ohb, part) if onehot_lhs else (part, ohb)
        p = jax.lax.dot_general(lhs, rhs, dims,
                                preferred_element_type=jnp.float32)
        out = p if out is None else out + p
    return out


def _onehot_dot(x, oh, dims):
    """Exact f32 ``dot_general(x, oh)`` against a 0/1 one-hot ``oh``."""
    return _parts_dot(_split3(x), oh, dims)


def query_table(q, nq_pad: int, width: int):
    """The scan kernels' VMEM-resident query table: ``q`` zero-padded to
    (nq_pad, width) f32 and stored as its (3, nq_pad, width) bf16
    :func:`_split3` parts, split once per search instead of once per
    grid step.  Padded rows are the zero row empty slots gather."""
    nq, d = q.shape
    qp = jnp.zeros((nq_pad, width), jnp.float32)
    qp = qp.at[:nq, :d].set(q.astype(jnp.float32))
    return jnp.stack(_split3(qp))


def _gather_rows(onehot, q_ref):
    """(G, nq_pad) one-hot x the (3, nq_pad, d) split table -> exact
    (G, d) f32 rows."""
    return _parts_dot([q_ref[i] for i in range(q_ref.shape[0])], onehot,
                      (((1,), (0,)), ((), ())), onehot_lhs=True)


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
# select — at bench shapes that round-trip plus the select is the
# dominant remaining cost (PERFORMANCE.md round 6: ~3.3 us per kept
# candidate).  The fused variants exploit the TPU grid's SEQUENTIAL
# execution: a (k, nq_pad) per-query accumulator lives in VMEM scratch
# across ALL grid steps, each group's local top-kt is merged into its
# queries' rows in-kernel, and only the final (k, nq_pad) answer is
# written to HBM on the last step.  No scatter, no final select — the
# extraction stage disappears from the profile.
#
# The accumulator is addressed by query id through the SAME one-hot
# matrix the query gather builds (rows are gathered by
# ``onehot @ acc`` and written back as ``acc*(1-cover) + onehotT @
# merged``).  Every slot of a group holds a DISTINCT query (a group is
# one list; a query probes each list at most once), so the write-back
# touches each row through exactly one one-hot lane — the update is
# EXACT in f32, and candidate ids ride along as exact-below-2^24 f32
# lanes just like the id mapping of the non-fused extraction.


def _gather_queries_masked(slot_ref, q_ref, n_probes, P):
    """Query gather that also returns the validity-masked one-hot used
    to address the fused accumulator.  Sentinel slots have an all-zero
    one-hot row: they gather the zero query row AND are excluded from
    the accumulator write-back (their merged columns are discarded)."""
    nq_pad = q_ref.shape[1]
    slot = slot_ref[0, 0]                              # (G,) int32 pair ids
    # sentinel slots map to column -1, which no iota column matches —
    # validity rides the int32 id, because Mosaic cannot lay out the
    # (G,) -> (G, 1) reshape of a bool vector
    qid = jnp.where(slot < P, slot // n_probes, -1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (GROUP, nq_pad), 1)
    oh = (cols == qid[:, None]).astype(jnp.float32)
    return _gather_rows(oh, q_ref), oh


def _topk_rows(d, ids_row, kt, adm=None):
    """Local top-kt of a (G, cap) distance block as sublane-stacked
    (kt, G) value/id rows — the fused twin of :func:`_extract_topk`
    (same max / where-iota argmin / masked-id-reduce passes), except
    results stay in registers for the in-kernel merge and exhausted
    slots carry the finite ``_ACC_WORST`` instead of +inf.  ``adm``
    folds per-(slot, candidate) admission bits through the same seam
    BEFORE any value reaches the staging ring or the accumulator's
    one-hot products (only finite sentinels ever meet a product)."""
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


def _merge_topk(cat_v, cat_i, k):
    """k selection passes over sublane-stacked (rows, G) candidates:
    merge of the accumulator's sorted k rows with a group's local kt
    rows.  Cross-SUBLANE reduces (rows <= k + kt, tiny) — the lane axis
    stays the 128 pair slots."""
    rows_n = cat_v.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, cat_v.shape, 0)
    out_v, out_i = [], []
    for _ in range(k):
        m = jnp.min(cat_v, axis=0)                     # (G,)
        p = jnp.min(jnp.where(cat_v == m[None, :], rows, rows_n), axis=0)
        p = jnp.minimum(p, rows_n - 1)
        sel = rows == p[None, :]
        gi = jnp.max(jnp.where(sel, cat_i, -jnp.inf), axis=0)
        out_v.append(m[None, :])
        out_i.append(gi[None, :])
        cat_v = jnp.where(sel, _ACC_WORST, cat_v)
    return jnp.concatenate(out_v, 0), jnp.concatenate(out_i, 0)  # (k, G)


def _fused_accumulate(oh, d, ids_row, acc_v, acc_i, kt, adm=None):
    """Merge one group's (G, cap) distances into the per-query
    accumulator: local top-kt, gather the slots' accumulator rows via
    the one-hot, merge sorted k+kt candidates per slot, write back.
    The one-hot write-back is exact (each real row is covered by at
    most one slot; sentinel slots have all-zero one-hot rows)."""
    k = acc_v.shape[0]
    new_v, new_i = _topk_rows(d, ids_row, kt, adm=adm)  # (kt, G)
    old_v = _onehot_dot(acc_v[:], oh, (((1,), (1,)), ((), ())))
    old_i = _onehot_dot(acc_i[:], oh, (((1,), (1,)), ((), ())))
    mer_v, mer_i = _merge_topk(jnp.concatenate([old_v, new_v], 0),
                               jnp.concatenate([old_i, new_i], 0), k)
    cover = jnp.sum(oh, axis=0)                        # (nq_pad,) 0/1
    keep = (1.0 - cover)[None, :]
    acc_v[:] = acc_v[:] * keep + _onehot_dot(
        mer_v, oh, (((1,), (0,)), ((), ())))
    acc_i[:] = acc_i[:] * keep + _onehot_dot(
        mer_i, oh, (((1,), (0,)), ((), ())))


def _merge_cols(acc_v, acc_i, stg_v, stg_i, k):
    """Windowed merge: fold the staged (kt*W, nq_pad) ring into the
    sorted (k, nq_pad) accumulator at FULL column width — no one-hot
    gather or write-back, every query column merges in place.  Same
    selection rule as :func:`_merge_topk` (min, lowest-row tie-break,
    masked-id reduce, winner re-masked to the finite sentinel), with
    rows ordered [accumulator | ring in arrival order] so tie retention
    matches the per-step merge bit-for-bit.  Columns whose staged rows
    are all sentinels reproduce the accumulator exactly (it is sorted
    and its rows precede the ring's), so partially-filled windows and
    all-sentinel tails are free.

    k past the unrolled regime runs as a ``fori_loop`` with dynamic
    SUBLANE stores into the accumulator — the concatenated working set
    is materialized before the loop, so the in-place row writes never
    feed back into the selection carry."""
    cat_v = jnp.concatenate([acc_v[:], stg_v[:]], axis=0)
    cat_i = jnp.concatenate([acc_i[:], stg_i[:]], axis=0)
    rows_n = cat_v.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, cat_v.shape, 0)

    def step(cat_v):
        m = jnp.min(cat_v, axis=0)                     # (nq_pad,)
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
        acc_v[:] = jnp.concatenate(out_v, 0)
        acc_i[:] = jnp.concatenate(out_i, 0)
    else:
        def body(j, cat_v):
            m, sel, gi = step(cat_v)
            acc_v[pl.ds(j, 1), :] = m[None, :]
            acc_i[pl.ds(j, 1), :] = gi[None, :]
            return jnp.where(sel, _ACC_WORST, cat_v)

        jax.lax.fori_loop(0, k, body, cat_v, unroll=False)


def _fused_step(g, oh, d, ids_row, acc_v, acc_i, stg, *, kt,
                merge_window, n_groups, adm=None):
    """One grid step of the fused accumulator, windowed.

    W <= 1 is the original per-step path (:func:`_fused_accumulate` —
    gather + merge + write-back every step).  W > 1 stages instead:
    the step's local top-kt lands in the ring slot ``g % W`` by ONE
    one-hot scatter per operand — uncovered columns take the
    ``_ACC_WORST`` / id -1 sentinel fill (``dot + _ACC_WORST*(1-cover)``
    is exact: covered columns add 0, uncovered columns add to 0) — and
    only every W-th step (and the flush step) pays
    :func:`_merge_cols`.  The ring resets to sentinels after each
    merge so stale slots of a partial final window merge as no-ops.
    """
    if merge_window <= 1:
        _fused_accumulate(oh, d, ids_row, acc_v, acc_i, kt, adm=adm)
        return
    stg_v, stg_i = stg
    new_v, new_i = _topk_rows(d, ids_row, kt, adm=adm)  # (kt, G), finite
    cover = jnp.sum(oh, axis=0)                        # (nq_pad,) 0/1
    fill = (1.0 - cover)[None, :]
    row0 = (g % merge_window) * vb.stage_stride(kt)
    stg_v[pl.ds(row0, kt), :] = _onehot_dot(
        new_v, oh, (((1,), (0,)), ((), ()))) + _ACC_WORST * fill
    stg_i[pl.ds(row0, kt), :] = _onehot_dot(
        new_i, oh, (((1,), (0,)), ((), ()))) - fill

    @pl.when(((g + 1) % merge_window == 0) | (g == n_groups - 1))
    def _merge():
        _merge_cols(acc_v, acc_i, stg_v, stg_i, acc_v.shape[0])
        stg_v[:] = jnp.full(stg_v.shape, _ACC_WORST, jnp.float32)
        stg_i[:] = jnp.full(stg_i.shape, -1.0, jnp.float32)


def _kernel_fused(gl_ref, slot_ref, qrot_ref, cf_ref, data_ref, rsq_ref,
                  ids_ref, *rest, kt, k, n_probes, P, n_groups,
                  merge_window, has_adm=False):
    """Fused recon scan: the non-fused ``_kernel`` distance block plus
    the in-kernel accumulator merge (windowed through the staging ring
    when merge_window > 1); outputs are the FINAL per-query (k, nq_pad)
    answers, flushed once on the last grid step."""
    adm_ref, rest = (rest[0], rest[1:]) if has_adm else (None, rest)
    vals_ref, ids_out_ref, acc_v, acc_i, *stg = rest
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        acc_v[:] = jnp.full(acc_v.shape, _ACC_WORST, jnp.float32)
        acc_i[:] = jnp.full(acc_i.shape, -1.0, jnp.float32)
        if merge_window > 1:
            stg[0][:] = jnp.full(stg[0].shape, _ACC_WORST, jnp.float32)
            stg[1][:] = jnp.full(stg[1].shape, -1.0, jnp.float32)

    qv, oh = _gather_queries_masked(slot_ref, qrot_ref, n_probes, P)
    sub = qv - cf_ref[0, 0][None, :]                   # (G, rot) f32
    sub_sq = jnp.sum(sub * sub, axis=1)                # (G,)
    data = data_ref[0]                                 # (cap, rot) bf16
    ip = jax.lax.dot_general(sub.astype(jnp.bfloat16), data,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    d = sub_sq[:, None] + rsq_ref[0, 0][None, :] - 2.0 * ip
    d = jnp.maximum(d, 0.0)
    adm = _unpack_admission(adm_ref, d.shape[1]) if has_adm else None
    _fused_step(g, oh, d, ids_ref[0, 0], acc_v, acc_i, stg, kt=kt,
                merge_window=merge_window, n_groups=n_groups, adm=adm)

    @pl.when(g == n_groups - 1)
    def _flush():
        vals_ref[:] = acc_v[:]
        ids_out_ref[:] = acc_i[:].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("kt", "k", "n_probes",
                                             "interpret", "merge_window"))
def grouped_l2_scan_fused(group_list, slot_pairs, qrot, centers_f32,
                          list_recon, rec_sq, list_indices, kt, k, n_probes,
                          interpret=False, merge_window=1, adm_words=None):
    """Fused grouped recon scan with IN-KERNEL per-query top-k.

    Inputs as :func:`grouped_l2_scan`; instead of per-pair winners the
    kernel returns the batch's FINAL per-query answers —
    ``(vals (k, nq_pad) f32, ids (k, nq_pad) int32)`` sorted ascending
    per column, query q in column q.  Exhausted ranks carry values at
    the finite ``_ACC_WORST`` sentinel (callers map values past
    ``_ACC_WORST/2`` to +inf / id -1).  ``kt`` bounds the per-(query,
    probe) keep-set exactly like the non-fused path: each group
    contributes at most its local top-kt per pair before the merge, so
    results match the scatter+select reference at matched kt.

    ``merge_window`` W amortizes the accumulator merge: steps stage
    their top-kt in a (kt*W, nq_pad) VMEM ring and the merge runs every
    W-th step — bit-identical to W=1 (the merge is order-insensitive
    under the finite sentinel; ring order preserves tie retention).
    Pick W with :func:`fused_merge_window`; k > 64 requires W >= 2.

    ``adm_words`` (n_groups, GROUP, ceil(cap/32)) int32 streams packed
    per-(slot, candidate) admission bits (filtered search): rejected
    candidates fold to the finite sentinel before the windowed merge.
    """
    n_groups = group_list.shape[0]
    nq, rot = qrot.shape
    _, cap, _ = list_recon.shape
    P = nq * n_probes

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
            pl.BlockSpec((k, nq_pad), lambda g, gl: (0, 0)),
            pl.BlockSpec((k, nq_pad), lambda g, gl: (0, 0)),
        ],
        scratch_shapes=vb.fused_scan_scratch(k, kt, merge_window, nq_pad),
    )
    vals, gids = pl.pallas_call(
        functools.partial(_kernel_fused, kt=kt, k=k, n_probes=n_probes,
                          P=P, n_groups=n_groups,
                          merge_window=merge_window, has_adm=has_adm),
        out_shape=[
            jax.ShapeDtypeStruct((k, nq_pad), jnp.float32),
            jax.ShapeDtypeStruct((k, nq_pad), jnp.int32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
    )(*inputs)
    return vals, gids


def _fused_base_bytes(cap: int, rot: int, nq_pad: int,
                      data_elem_bytes: int) -> int:
    return (2 * nq_pad * rot * 4              # query table + one-hot
            + cap * rot * data_elem_bytes     # per-list data block
            + 2 * GROUP * cap * 4)            # distances + local passes


def _fused_static_ok(metric_is_l2: bool, cap: int, rot: int, kt: int,
                     k: int, nq: int) -> bool:
    return (metric_is_l2 and rot % 128 == 0 and cap % 16 == 0
            and GROUP % 16 == 0 and 0 < kt <= _KT_UNROLL
            and 0 < k <= vb.FUSED_K_MAX and nq <= 6144)


def fused_merge_window(cap: int, rot: int, kt: int, k: int, nq: int,
                       data_elem_bytes: int = 2, requested: int = 0) -> int:
    """Host-static merge window for the fused recon scan at this shape
    (0 = no window fits -> fused unsupported).  ``requested`` 0 is auto
    (largest fitting W); k past the unrolled per-step merge needs the
    windowed path, so W >= 2 is forced there."""
    nq_pad = vb.nq_padded(nq)
    return vb.select_merge_window(
        requested, kt=kt, k=k, nq_pad=nq_pad, group=GROUP,
        base_bytes=_fused_base_bytes(cap, rot, nq_pad, data_elem_bytes),
        budget=10 << 20, w_min=1 if k <= _KT_UNROLL else 2)


def supported_fused(metric_is_l2: bool, cap: int, rot: int, kt: int,
                    k: int, nq: int, data_elem_bytes: int = 2,
                    merge_window: int = 0) -> bool:
    """Shapes the fused recon kernel handles.  Beyond :func:`supported`:
    the (k, nq_pad) accumulator pair and the staging ring join the VMEM
    budget (:mod:`raft_tpu.ops.vmem_budget`); kt stays in the unrolled
    regime while k extends to ``FUSED_K_MAX`` through the windowed
    merge (some W must fit — check :func:`fused_merge_window`)."""
    return (_fused_static_ok(metric_is_l2, cap, rot, kt, k, nq)
            and fused_merge_window(cap, rot, kt, k, nq, data_elem_bytes,
                                   merge_window) > 0)


def fused_reject_reason(metric_is_l2: bool, cap: int, rot: int, kt: int,
                        k: int, nq: int, data_elem_bytes: int = 2,
                        merge_window: int = 0) -> str:
    """Reason code for a fused-recon gate miss ('' when supported):
    'dtype' (metric), 'k-too-large' (k/kt bounds), 'bucket-too-wide'
    (batch, layout, or VMEM — no merge window fits).  Drives the
    ``fused_fallback`` counter attrs and flight events."""
    if not metric_is_l2:
        return "dtype"
    if not (0 < kt <= _KT_UNROLL and 0 < k <= vb.FUSED_K_MAX):
        return "k-too-large"
    if not (rot % 128 == 0 and cap % 16 == 0 and GROUP % 16 == 0
            and nq <= 6144):
        return "bucket-too-wide"
    if fused_merge_window(cap, rot, kt, k, nq, data_elem_bytes,
                          merge_window) <= 0:
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

    Candidate-id f32-exactness (|id| < 2^24, required by the one-hot id
    contraction) is data-dependent and checked by the caller on the
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
