"""Shared VMEM-budget model for the fused Pallas kernels.

The two fused IVF-PQ list scans (:mod:`raft_tpu.ops.pq_group_scan_pallas`,
:mod:`raft_tpu.ops.pq_code_scan_pallas`) keep three arrays resident in
VMEM for the whole grid: the (nq_pad, width) f32 query table and the
query-major (nq_pad, round_up(k, 128)) f32 value/id accumulator pair.
Every grid step addresses them by ROW: it copies its GROUP slots' query
rows and accumulator rows out by the per-slot query ids streamed through
SMEM, merges, and writes the accumulator rows back — per-step work that
does not grow with the batch.  :func:`fused_scan_bytes` charges the
resident arrays, the per-step row blocks and the kernel's streaming
blocks; a shape is admitted when the total fits
:data:`FUSED_SCAN_BUDGET`, and the kernel raises its scoped VMEM limit to
:func:`fused_scan_vmem_limit` of the same total.

The fused CAGRA hop (:mod:`raft_tpu.ops.cagra_hop_pallas`) keeps its
within-hop staged merge and its own model (:func:`hop_bytes`).

graftlint's mask-seam pass requires the fused kernels to size their
scratch through :func:`fused_scan_scratch` / :func:`hop_scratch` so the
scratch a kernel allocates and the bytes this model charges cannot drift
apart.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# requested merge_window sentinel of the fused CAGRA hop: the selector
# picks the window
MERGE_WINDOW_AUTO = 0
# k ceiling of the fused scans: past the unrolled merge the per-step
# merge runs as a fori_loop over sublane-stacked rows
FUSED_K_MAX = 256
# admission budget of the fused scans' VMEM model; the scoped limit the
# kernel asks for adds FUSED_SCAN_HEADROOM for compiler temporaries
# (a v5e core has 128 MiB of VMEM)
FUSED_SCAN_BUDGET = 32 << 20
FUSED_SCAN_HEADROOM = 16 << 20


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def merge_window_request(value) -> int:
    """Normalize the public ``cagra.SearchParams.merge_window`` knob
    ("auto" | int) to the integer request :func:`select_hop_window`
    takes: 0 = auto, n >= 1 = upper bound."""
    if value is None or value == "auto":
        return MERGE_WINDOW_AUTO
    w = int(value)
    if w < 0:
        raise ValueError(
            f"merge_window must be 'auto' or an int >= 0, got {value!r}")
    return w


def nq_padded(nq: int) -> int:
    """Query-table height shared by the fused scan kernels: one padding
    row (``nq_pad - 1``, zero, never returned) that empty slots address,
    then 128-row alignment."""
    return round_up(nq + 1, 128)


def acc_lanes(k: int) -> int:
    """Lane width of one query-major accumulator row: k rounded up to
    whole 128-lane vregs, so a row block transposes tile by tile."""
    return round_up(k, 128)


def fused_scan_bytes(k: int, kt: int, nq_pad: int, width: int, group: int,
                     stream_bytes: int) -> int:
    """VMEM footprint of a fused scan: resident query table and
    accumulator pair, the per-step row blocks (gathered query rows,
    accumulator rows in and out, the merge's sublane-stacked rows and
    its (k + kt, group) working set), plus ``stream_bytes`` — the
    kernel's double-buffered per-list blocks and distance temporaries."""
    kl = acc_lanes(k)
    return (nq_pad * width * 4                 # query table
            + 2 * nq_pad * kl * 4              # accumulator pair
            + group * width * 4                # gathered query rows
            + 4 * group * kl * 4               # slot rows + merged rows
            + 4 * (k + kt) * group * 4         # merge working set
            + stream_bytes)


def fused_scan_fits(total_bytes: int) -> bool:
    """Whether a fused scan whose VMEM model totals ``total_bytes`` fits
    :data:`FUSED_SCAN_BUDGET` (the scans' admission test)."""
    return total_bytes <= FUSED_SCAN_BUDGET


def fused_scan_vmem_limit(total_bytes: int) -> int:
    """Scoped VMEM limit a fused scan kernel asks the compiler for."""
    return round_up(total_bytes + FUSED_SCAN_HEADROOM, 1 << 20)


def fused_scan_scratch(k: int, nq_pad: int, width: int, group: int):
    """Scratch list for the fused scan kernels, in kernel-argument
    order: the resident (nq_pad, width) query table, the (nq_pad,
    acc_lanes(k)) value/id accumulator pair, the (group, width) gathered
    query rows, the (group, acc_lanes(k)) value/id slot-row pair and the
    (acc_lanes(k), group) value/id merged-row pair.  The fused kernels
    MUST allocate through this helper (graftlint-enforced) so scratch
    and the budget model agree."""
    kl = acc_lanes(k)
    f32 = jnp.float32
    return [pltpu.VMEM((nq_pad, width), f32),
            pltpu.VMEM((nq_pad, kl), f32), pltpu.VMEM((nq_pad, kl), f32),
            pltpu.VMEM((group, width), f32),
            pltpu.VMEM((group, kl), f32), pltpu.VMEM((group, kl), f32),
            pltpu.VMEM((kl, group), f32), pltpu.VMEM((kl, group), f32)]


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
