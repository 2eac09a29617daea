"""Shared VMEM-budget model for the fused Pallas kernels' windowed merge.

The three fused kernels (:mod:`raft_tpu.ops.pq_group_scan_pallas`,
:mod:`raft_tpu.ops.pq_code_scan_pallas`,
:mod:`raft_tpu.ops.cagra_hop_pallas`) amortize their per-step top-k merge
through a VMEM **staging ring**: each grid step appends its kt candidates
(in a slot of :func:`stage_stride` rows) into a (stride*W, nq_pad)
scratch pair with a cheap one-hot scatter + sentinel fill, and only
every W-th step (and at flush) pays the full
merge into the (k, nq_pad) accumulator.  ``W`` is host-static: it is
chosen here, from shapes only, by one budget model all three kernels
share — staging + accumulator + merge working set must fit the kernel's
existing VMEM budget next to its streaming blocks.  graftlint's
mask-seam pass requires the fused kernels to size their scratch through
:func:`fused_scan_scratch` / :func:`hop_scratch` so the scratch a kernel
allocates and the bytes this model charges cannot drift apart.

Selection is monotone: the amortized per-step merge cost
``k * (k + kt*W) / W`` column passes strictly decreases in W while the
staging write stays O(kt), so ``auto`` picks the LARGEST W that fits,
capped at :data:`MERGE_WINDOW_MAX` (past which the staged rows' own
merge passes dominate and the VMEM spent stops buying wall-clock).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# requested merge_window sentinel: pick the largest window that fits
MERGE_WINDOW_AUTO = 0
# staging rings larger than this stop paying: the merge over k + kt*W
# staged rows grows linearly in W while the amortization factor 1/W
# saturates
MERGE_WINDOW_MAX = 8
# the windowed merge's fori_loop accumulator store lifts the unrolled
# k <= 64 merge bound up to the radix-select regime
FUSED_K_MAX = 256


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def merge_window_request(value) -> int:
    """Normalize the public ``merge_window`` knob ("auto" | int) to the
    integer request the selectors take: 0 = auto, n >= 1 = upper bound.
    Every caller (ivf_pq / cagra SearchParams, distributed.ann, AOT
    exports) parses the knob through here so the accepted spellings
    cannot drift."""
    if value is None or value == "auto":
        return MERGE_WINDOW_AUTO
    w = int(value)
    if w < 0:
        raise ValueError(
            f"merge_window must be 'auto' or an int >= 0, got {value!r}")
    return w


def nq_padded(nq: int) -> int:
    """Lane-padded query-table height shared by the fused scan kernels
    (one sentinel row for empty slots, then 128-lane alignment)."""
    return round_up(nq + 1, 128)


def accumulator_bytes(k: int, nq_pad: int) -> int:
    """The (k, nq_pad) f32 value/id accumulator pair."""
    return 2 * k * nq_pad * 4


def stage_stride(kt: int) -> int:
    """Rows one grid step owns in the staging ring: kt rounded up to
    the 8-row sublane tile, because Mosaic only stores a multi-row block
    at a dynamic row offset it can prove is a multiple of 8.  The pad
    rows keep the sentinel fill and merge as no-ops."""
    return round_up(kt, 8)


def staging_bytes(kt: int, merge_window: int, nq_pad: int) -> int:
    """The (stage_stride(kt)*W, nq_pad) f32 staging-ring pair; W <= 1
    stages nothing (the per-step merge never materializes a window)."""
    if merge_window <= 1:
        return 0
    return 2 * stage_stride(kt) * merge_window * nq_pad * 4


def merge_temps_bytes(k: int, kt: int, merge_window: int, nq_pad: int,
                      group: int) -> int:
    """Transient working set of one merge.

    W <= 1 is the per-step merge: one-hot gather/write-back temps at
    GROUP width, 4 (k+kt, GROUP) f32 arrays (values + ids, in + out).
    W > 1 merges at FULL column width: the concatenated
    (k + kt*W, nq_pad) value/id pair the selection passes sweep.
    """
    if merge_window <= 1:
        return 4 * (k + kt) * group * 4
    return 2 * (k + stage_stride(kt) * merge_window) * nq_pad * 4


def select_merge_window(requested: int, *, kt: int, k: int, nq_pad: int,
                        group: int, base_bytes: int, budget: int,
                        w_min: int = 1,
                        w_max: int = MERGE_WINDOW_MAX) -> int:
    """Host-static merge-window choice for a fused scan shape.

    ``base_bytes`` is the kernel's non-merge VMEM floor (query table,
    streamed data block, distance block, ...); the merge side —
    accumulator + staging ring + merge transients — must fit in
    ``budget - base_bytes``.  ``requested`` is the user knob:
    :data:`MERGE_WINDOW_AUTO` (0) picks the largest fitting W; a
    positive W is honored as an upper bound (clamped down to what
    fits).  ``w_min`` > 1 expresses shapes the per-step merge cannot
    serve (k past the unrolled regime needs the windowed fori_loop
    merge).  Returns the chosen W, or 0 when NO window fits — callers
    treat 0 as "fused unsupported at this shape".
    """
    if requested < 0 or kt <= 0 or k <= 0:
        return 0

    def fits(w: int) -> bool:
        total = (base_bytes + accumulator_bytes(k, nq_pad)
                 + staging_bytes(kt, w, nq_pad)
                 + merge_temps_bytes(k, kt, w, nq_pad, group))
        return total <= budget

    hi = w_max if requested == MERGE_WINDOW_AUTO else min(requested, w_max)
    for w in range(hi, w_min - 1, -1):
        if fits(w):
            return w
    return 0


def fused_scan_scratch(k: int, kt: int, merge_window: int, nq_pad: int):
    """Scratch list for the fused scan kernels: the (k, nq_pad)
    accumulator pair, plus the (stage_stride(kt)*W, nq_pad) staging-ring
    pair when a window is in play.  The fused kernels MUST allocate
    through this helper (graftlint-enforced) so scratch and the budget
    model agree."""
    scratch = [pltpu.VMEM((k, nq_pad), jnp.float32),
               pltpu.VMEM((k, nq_pad), jnp.float32)]
    if merge_window > 1:
        rows = stage_stride(kt) * merge_window
        scratch += [pltpu.VMEM((rows, nq_pad), jnp.float32),
                    pltpu.VMEM((rows, nq_pad), jnp.float32)]
    return scratch


# ---------------------------------------------------------------------------
# fused CAGRA hop
# ---------------------------------------------------------------------------
#
# The hop kernel's "window" is within-hop: the walk needs the fully
# merged sorted buffer before every parent selection, so work cannot be
# deferred ACROSS hops.  W > 1 selects the staged variant — candidates
# are extracted into a sorted staging block (min(itopk, wd) rows) and
# merged with the buffer by one in-kernel bitonic pass, replacing the
# itopk min-extraction rounds over all itopk+wd rows that gated the
# legacy kernel at itopk <= 32.


def hop_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def hop_stage_rows(itopk: int, wd: int) -> int:
    """Rows of the staged-extraction block (top-t of the hop's
    candidates; t beyond min(itopk, wd) can never survive the merge)."""
    return min(itopk, wd)


def hop_merge_rows(itopk: int, wd: int) -> int:
    """Height of the bitonic compare-exchange network: buffer + staged
    block, padded to a power of two."""
    return hop_pow2(itopk + hop_stage_rows(itopk, wd))


def hop_bytes(itopk: int, wd: int, pdim: int, merge_window: int,
              lanes: int) -> int:
    """VMEM model of one fused hop, legacy (W <= 1) or staged (W > 1)."""
    base = (wd * pdim * lanes * 4        # neighbor lanes
            + (pdim + 1) * lanes * 4     # qpT + q_sq
            + 2 * wd * lanes * 4         # nb_sq / nb_id
            + 9 * itopk * lanes * 4)     # buffer triple, in + out
    if merge_window <= 1:
        return base + 4 * (itopk + wd) * lanes * 4   # merge working set
    rows = hop_merge_rows(itopk, wd)
    return (base
            + 2 * hop_stage_rows(itopk, wd) * lanes * 4   # staging block
            + 6 * rows * lanes * 4)      # bitonic working set (d/i/v x2)


def select_hop_window(requested: int, *, itopk: int, wd: int, pdim: int,
                      lanes: int, budget: int, itopk_legacy_max: int,
                      itopk_staged_max: int) -> int:
    """Merge-window choice for the fused hop: 1 = legacy in-pass merge,
    2 = staged extraction + bitonic merge (there is no deeper ring —
    the walk consumes the merged buffer every hop).  ``auto`` keeps the
    proven legacy kernel where it is allowed (itopk within the legacy
    gate) and selects the staged variant for larger itopk; an explicit
    W > 1 forces staging.  Returns 0 when neither variant fits."""
    if requested < 0 or itopk <= 0:
        return 0
    want_staged = (requested > 1
                   or (requested == MERGE_WINDOW_AUTO
                       and itopk > itopk_legacy_max))
    if want_staged:
        if (itopk <= itopk_staged_max
                and hop_bytes(itopk, wd, pdim, 2, lanes) <= budget):
            return 2
        if requested > 1:
            return 0
    if (itopk <= itopk_legacy_max
            and hop_bytes(itopk, wd, pdim, 1, lanes) <= budget):
        return 1
    return 0


def hop_scratch(itopk: int, wd: int, merge_window: int, lanes: int):
    """Scratch list for the fused hop kernel: the staged variant's
    (t, lanes) extraction block pair (distances / ids — staged
    candidates are never visited, so no flag plane).  Sized here
    (graftlint-enforced) for the same reason as
    :func:`fused_scan_scratch`; the legacy variant stages nothing."""
    if merge_window <= 1:
        return []
    t = hop_stage_rows(itopk, wd)
    return [pltpu.VMEM((t, lanes), jnp.float32) for _ in range(2)]
