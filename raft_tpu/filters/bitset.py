"""Per-query admission bitsets — the ``SampleFilter`` predicate layer.

Reference: cpp/include/raft/neighbors/sample_filter_types.hpp — the
``bitset_filter`` a caller attaches to ivf_pq/ivf_flat search so every
(query, candidate) pair is admitted or rejected *inside* the scan, not by
a post-hoc pass that would starve k.  TPU translation: the filter is a
dense per-query bitset over row ids, packed 32 ids per int32 word, shape
``(nq, n_words)`` with ``n_words = ceil(n_rows / 32)``.  Packed words are
what streams through VMEM: the Pallas scan kernels gather one word per
32 candidates and unpack with a shift/mask, so admission costs ~1 bit of
HBM traffic per candidate instead of 32.

The admission seam reuses the tombstone seam (PRs 7/8/10): an
inadmissible candidate folds to the finite ``_ACC_WORST`` distance and
id -1 *before* top-k / the fused merge, so filtered results are
bit-identical to a post-hoc filtered exact scan at full probe — the same
kernel computes the same distances; folding a row to worst before
selection is equivalent to removing it from the candidate set.

Filters are **data, not shape**: ``n_words`` depends only on the index's
id bound (static per generation), never on filter contents, so varying
per-query filters at a fixed serving bucket re-enter the same compiled
executable (0 steady-state recompiles — asserted by the serving tier).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core.error import expects

# ids per packed word; int32 matches the repo's packed-lane idiom
# (ops/pq_code_scan_pallas.pack_code_lanes) and the 32-row list
# alignment (_LIST_ALIGN) so capacity-axis packing never straddles rows
BITS_PER_WORD = 32


def n_words_for(n_rows: int) -> int:
    """Packed word count covering ``n_rows`` ids (≥ 1 so an empty bound
    still has a well-formed (nq, 1) buffer)."""
    return max(1, -(-int(n_rows) // BITS_PER_WORD))


@dataclasses.dataclass(frozen=True)
class SampleFilter:
    """Dense per-query admission bitset over row ids.

    ``words[q, i >> 5] >> (i & 31) & 1`` is the admission bit of id ``i``
    for query ``q``.  Bits at or beyond ``n_rows`` are ignored by every
    consumer (candidates carry in-range ids or the -1/tombstone
    sentinel, which folds before the filter is consulted).
    """

    words: jax.Array        # (nq, n_words) int32 packed admission bits
    n_rows: int             # id bound the bitset covers

    @property
    def nq(self) -> int:
        return int(self.words.shape[0])

    @property
    def n_words(self) -> int:
        return int(self.words.shape[1])

    def admitted_counts(self) -> np.ndarray:
        """Per-query admitted-id count (host-side, for observability and
        the matched-budget recall gate in bench)."""
        w = np.asarray(self.words).view(np.uint32)
        bits = np.unpackbits(w.view(np.uint8), axis=-1,
                             count=self.n_words * BITS_PER_WORD,
                             bitorder="little").reshape(self.nq, -1)
        return bits[:, : self.n_rows].sum(axis=1).astype(np.int64)

    @staticmethod
    def from_words(words, n_rows: int) -> "SampleFilter":
        words = jnp.asarray(words, jnp.int32)
        expects(words.ndim == 2, "SampleFilter: words must be (nq, n_words)")
        expects(words.shape[1] >= n_words_for(n_rows),
                "SampleFilter: words too narrow for n_rows")
        return SampleFilter(words=words, n_rows=int(n_rows))

    @staticmethod
    def from_mask(mask) -> "SampleFilter":
        """Build from a dense (nq, n_rows) boolean admission mask."""
        mask = jnp.asarray(mask)
        expects(mask.ndim == 2, "SampleFilter: mask must be (nq, n_rows)")
        n_rows = int(mask.shape[1])
        return SampleFilter(words=pack_mask(mask), n_rows=n_rows)

    @staticmethod
    def from_ids(ids: Sequence, n_rows: int, nq: int = 1) -> "SampleFilter":
        """Admit exactly ``ids`` (host-side build; same set for each of
        ``nq`` queries).  The hybrid path and tests use this."""
        w = np.zeros(n_words_for(n_rows), np.uint32)
        arr = np.asarray(ids, np.int64).ravel()
        arr = arr[(arr >= 0) & (arr < n_rows)]
        np.bitwise_or.at(w, arr >> 5, np.uint32(1) << (arr & 31).astype(np.uint32))
        words = jnp.asarray(np.broadcast_to(w.view(np.int32), (nq, w.size)))
        return SampleFilter(words=words, n_rows=int(n_rows))

    @staticmethod
    def all_rows(n_rows: int, nq: int = 1) -> "SampleFilter":
        """Admit everything — the identity filter (all-ones words)."""
        words = jnp.full((nq, n_words_for(n_rows)), -1, jnp.int32)
        return SampleFilter(words=words, n_rows=int(n_rows))

    def intersect(self, other: "SampleFilter") -> "SampleFilter":
        """AND-compose two filters (e.g. tenant namespace ∧ predicate)."""
        expects(self.n_rows == other.n_rows,
                "SampleFilter: intersect over mismatched id bounds")
        return SampleFilter(words=self.words & other.words,
                            n_rows=self.n_rows)


def pack_mask(mask) -> jax.Array:
    """Pack a (nq, n) boolean mask into (nq, ceil(n/32)) int32 words,
    little-endian within each word (bit b of word w covers id 32*w+b)."""
    mask = jnp.asarray(mask, jnp.int32)
    nq, n = mask.shape
    nw = n_words_for(n)
    pad = nw * BITS_PER_WORD - n
    if pad:
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    m = mask.reshape(nq, nw, BITS_PER_WORD)
    shifts = jnp.arange(BITS_PER_WORD, dtype=jnp.int32)
    # uint32 intermediate: bit 31 must set the sign bit, not overflow
    w = jnp.sum(m.astype(jnp.uint32) << shifts[None, None, :], axis=-1)
    return w.astype(jnp.int32)


def query_bits(words: jax.Array, qids: jax.Array, ids: jax.Array
               ) -> jax.Array:
    """Gather admission bits — the XLA twin of the in-kernel unpack.

    ``words`` is (nq, n_words) int32; ``qids`` maps each row of ``ids``
    to its query (any shape broadcastable against ``ids`` minus the last
    axis); ``ids`` holds candidate ids (negative = padding/tombstone —
    reported inadmissible here, though every caller folds them first).
    Returns an int32 0/1 array shaped like ``ids``.
    """
    ids = ids.astype(jnp.int32)
    safe = jnp.maximum(ids, 0)
    rows = words[qids]                       # ids.shape[:-1] + (n_words,)
    w = jnp.take_along_axis(rows, safe >> 5, axis=-1, mode="clip")
    bit = (w >> (safe & 31)) & 1
    # ids the bitset does not cover are NOT admitted: the filter declares
    # the id space, so an out-of-range id is outside every predicate
    cov = words.shape[-1] * BITS_PER_WORD
    return jnp.where((ids >= 0) & (ids < cov), bit, 0).astype(jnp.int32)


def group_admission_words(filter_words: jax.Array, group_list: jax.Array,
                          slot_pairs: jax.Array, list_indices: jax.Array,
                          n_probes: int, P: int) -> jax.Array:
    """Admission words for the grouped scan, in **list-slot order**.

    The grouped kernels iterate candidates positionally along a list's
    capacity axis, so the per-(slot, candidate) admission bit must be
    laid out the same way: output is ``(n_groups, GROUP, Wc)`` int32
    with ``Wc = ceil(cap / 32)`` — word ``w`` of slot ``s`` in group
    ``g`` packs the bits of candidates ``32w..32w+31`` of list
    ``group_list[g]`` for the query owning ``slot_pairs[g, s]``.

    Empty slots (pair == ``P``) get query 0's bits; they never surface
    (the scatter drops them, the fused one-hot zero-masks them).
    Padding/tombstone candidates (id < 0) pack a 0 bit, composing the
    filter with the tombstone seam in one word.
    """
    n_groups = group_list.shape[0]
    cap = list_indices.shape[1]
    ids = list_indices[group_list]                     # (n_groups, cap)
    pairs = jnp.minimum(slot_pairs, P - 1) if P > 0 else slot_pairs
    qids = (pairs // max(1, n_probes)).astype(jnp.int32)   # (n_groups, GROUP)
    rows = filter_words[qids]                  # (n_groups, GROUP, n_words)
    safe = jnp.maximum(ids, 0).astype(jnp.int32)           # (n_groups, cap)
    w = jnp.take_along_axis(
        rows, jnp.broadcast_to((safe >> 5)[:, None, :], rows.shape[:2] + (cap,)),
        axis=-1, mode="clip")                     # (n_groups, GROUP, cap)
    bit = (w >> (safe & 31)[:, None, :]) & 1
    cov = filter_words.shape[-1] * BITS_PER_WORD
    bit = jnp.where(((ids >= 0) & (ids < cov))[:, None, :], bit, 0)
    return pack_mask(bit.reshape(-1, cap)).reshape(
        n_groups, slot_pairs.shape[1], -1)


def unpack_words(words: jax.Array, n: int) -> jax.Array:
    """Unpack packed words back to an int32 0/1 mask over ``n`` ids along
    the last axis — shared by the XLA twins and the kernel-side unpack
    (which runs the same shift under Pallas)."""
    shifts = jnp.arange(BITS_PER_WORD, dtype=jnp.int32)
    bits = (words[..., :, None] >> shifts) & 1
    return bits.reshape(words.shape[:-1] + (-1,))[..., :n]


def query_filter_words(f: "FilterLike", nq: int, site: str
                       ) -> Optional[jax.Array]:
    """Normalize a public ``search(filter=)`` argument to per-query packed
    words (nq, n_words) int32, or None when unfiltered.

    Accepts a :class:`SampleFilter` (single-query filters broadcast to
    the batch) or a dense (nq, n_rows) boolean admission mask.  This is
    the ONE seam every index type's search runs its filter through, so
    the accepted forms and the broadcast rule cannot drift between
    ivf_pq / ivf_flat / cagra / brute_force.
    """
    if f is None:
        return None
    if not isinstance(f, SampleFilter):
        arr = jnp.asarray(f)
        expects(arr.ndim == 2 and arr.dtype == jnp.bool_,
                f"{site}: filter must be a SampleFilter or an "
                "(nq, n_rows) bool mask")
        f = SampleFilter.from_mask(arr)
    expects(f.nq in (1, nq),
            f"{site}: filter covers {f.nq} queries, batch has {nq}")
    w = f.words
    if f.nq == 1 and nq != 1:
        w = jnp.broadcast_to(w, (nq, w.shape[1]))
    return w


FilterLike = Union[SampleFilter, jax.Array, np.ndarray, None]


def as_filter(f: FilterLike, n_rows: int) -> Optional[SampleFilter]:
    """Normalize a ``filter=`` argument: SampleFilter passes through
    (bound-checked), a raw 2-D bool/int mask is packed, None is None."""
    if f is None:
        return None
    if isinstance(f, SampleFilter):
        expects(f.n_words >= n_words_for(n_rows),
                "filter: bitset narrower than the index id bound")
        return f
    arr = jnp.asarray(f)
    expects(arr.ndim == 2, "filter: expected SampleFilter or (nq, n) mask")
    if arr.dtype == jnp.int32 and arr.shape[1] == n_words_for(n_rows) \
            and arr.shape[1] != n_rows:
        return SampleFilter.from_words(arr, n_rows)
    expects(arr.shape[1] == n_rows,
            "filter: mask width must equal the index id bound")
    return SampleFilter.from_mask(arr)
