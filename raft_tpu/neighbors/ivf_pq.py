"""IVF-PQ: inverted-file index with product-quantized residuals — the
performance flagship (BASELINE.md north-star workload).

Reference: raft/neighbors/ivf_pq.cuh:224 ``build``, :266 ``extend``, :342
``search``; params/types ivf_pq_types.hpp:48 (index_params: pq_bits, pq_dim,
codebook_kind, force_random_rotation), :110 (search_params: n_probes,
lut_dtype), :264 (index).  Build internals:
detail/ivf_pq_build.cuh:337 ``train_per_subset``, :417 ``train_per_cluster``
(both via kmeans_balanced), :944 ``process_and_fill_codes_kernel``; search:
detail/ivf_pq_search.cuh:133 ``select_clusters``, :611
``compute_similarity_kernel`` (shared-memory LUT), :373
``postprocess_neighbors``; code packing detail/ivf_pq_codepacking.cuh.

TPU design:

- **codebook training** is a ``vmap`` of the balanced-k-means loop over the
  ``pq_dim`` subspaces — one compilation, all books trained in parallel on
  the MXU (the reference loops build_clusters per subspace);
- **encoding** is a single batched argmin over (n, pq_dim, book) distances —
  the ``process_and_fill_codes`` analogue is the same scatter used by
  IVF-Flat's list packer (static-shape padded lists, SURVEY.md §7);
- **search** scans probed lists like IVF-Flat, but each step builds the
  per-(query, probe) look-up table on the fly — an einsum against the
  codebooks (MXU) — then accumulates code distances with a
  ``take_along_axis`` gather over the book axis (VPU).  The LUT never leaves
  VMEM-scale shapes: (q_tile, pq_dim, 2^pq_bits).  ``lut_dtype=bf16``
  halves LUT bandwidth, mirroring the reference's fp8/half LutT option
  (ivf_pq_search.cuh:70).
- the optional **random rotation** (force_random_rotation /
  dim-padding rotation in the reference) is a fixed orthonormal matrix from
  QR of a seeded normal draw, applied before subspace splitting.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import BinaryIO, Optional, Tuple
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_types import KMeansBalancedParams
from raft_tpu.core import platform as _platform
from raft_tpu.core import serialize as ser
from raft_tpu.core.error import expects
from raft_tpu.core.interruptible import interruptible
from raft_tpu.core.mdarray import ensure_array
from raft_tpu.core import tracing as _tracing
from raft_tpu.core.tracing import range as named_range
from raft_tpu import observability as obs
from raft_tpu.integrity import boundary as _boundary
from raft_tpu.integrity import canary as _canary
from raft_tpu.distance.types import DistanceType
from raft_tpu.filters import bitset as _fbits
from raft_tpu.matrix.select_k import select_k
from raft_tpu.neighbors import mutate as _mutate
from raft_tpu.neighbors.ivf_flat import (_append_lists_multi, _pack_lists,
                                         _round_up, _LIST_ALIGN)
from raft_tpu.utils.precision import get_matmul_precision
from raft_tpu.core.outputs import auto_convert_output


class CodebookKind:
    """Reference: ivf_pq_types.hpp ``codebook_gen`` enum."""

    PER_SUBSPACE = 0
    PER_CLUSTER = 1


@dataclasses.dataclass
class IndexParams:
    """Reference: ivf_pq_types.hpp:48 ``index_params``."""

    n_lists: int = 1024
    metric: int = DistanceType.L2Expanded
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    pq_bits: int = 8          # 4..8 supported in the reference
    pq_dim: int = 0           # 0 -> auto: dim/4 rounded (reference heuristic)
    codebook_kind: int = CodebookKind.PER_SUBSPACE
    force_random_rotation: bool = False
    add_data_on_build: bool = True
    # Build the bf16 reconstruction search cache ((n, rot_dim) extra HBM —
    # 2x the codes' footprint per byte of pq_dim*8/rot_dim compression).
    # Set False for datasets whose reconstructions would not fit HBM; search
    # then uses the memory-lean LUT formulation.
    cache_reconstructions: bool = True
    # Recall canaries (integrity.canary): > 0 samples that many sentinel
    # queries at build, stores their exact neighbors inside the index and
    # re-checks recall after load()/extend()/resume (floor below).
    canary_queries: int = 0
    canary_k: int = 10
    canary_floor: float = 0.5


@dataclasses.dataclass
class SearchParams:
    """Reference: ivf_pq_types.hpp:110 ``search_params``."""

    n_probes: int = 20
    # coarse probe ranking controls, inherited from IVF-Flat (ONE copy of
    # the rank arithmetic, ivf_flat._select_clusters): the approx_max_k
    # recall target, and an exact lax.top_k override.  The exact select is
    # also auto-chosen when n_probes is close to n_lists.
    coarse_recall_target: float = 0.95
    exact_coarse: bool = False
    # lut_dtype applies to the LUT formulation only (fp32 | bf16, the fp8
    # analogue); the reconstruction path stores bf16 residuals and always
    # accumulates fp32.
    lut_dtype: object = jnp.float32
    # Which list-scan formulation serves the query batch (plan_scan
    # decides the one that runs; docs/api.md has its table):
    #   "recon"  — bf16 reconstruction cache (2 B/dim/row HBM traffic);
    #   "codes"  — compact-code Pallas kernel: bit-packed codes stream
    #              from HBM (~pq_bits/8 B/subspace/row, ~4x less than
    #              recon at the bench shape) and are decoded in-register
    #              against the VMEM-resident codebook table (the TPU
    #              analogue of the reference's shared-memory LUT scan,
    #              ivf_pq_search.cuh:611); falls back to "lut" off-TPU or
    #              for unsupported shapes (see pq_code_scan_pallas);
    #   "lut"    — the XLA take_along_axis LUT formulation (traceable,
    #              memory-lean; the AOT export path);
    #   "fused"  — the in-kernel top-k variants of "codes"/"recon": a
    #              per-query accumulator lives in VMEM across the whole
    #              scan grid, so candidates never reach HBM and the
    #              scatter + final-select extraction stage disappears
    #              (backed by the compact-code kernel when eligible,
    #              else the recon cache; falls back to the non-fused
    #              path off-TPU or for unsupported shapes, counted by
    #              the ivf_pq.search.fused_fallback counter);
    #   "auto"   — "recon" when the index carries the cache, else "codes"
    #              when the kernel supports the index's static config,
    #              else "lut" — UPGRADED to the fused kernel whenever
    #              the batch's shape supports it on TPU.
    scan_mode: str = "auto"
    # Per-(query, probe) candidates kept by the grouped scans before the
    # final merge (the kernel's kt).  0 -> k.  The grouped kernels are
    # extraction-bound (~3.3 us per kept candidate per group, flat in list
    # size — PERFORMANCE.md round 5), so at refine-heavy operating points
    # a small value (e.g. 4 with refine_ratio>=2) trades a little
    # pre-refine recall for a near-linear scan speedup.  The probe-order
    # LUT formulation (and therefore any off-TPU fallback to it) has no
    # per-pair keep-set and ignores this knob — the fallback errs toward
    # MORE candidates, never fewer.
    per_probe_topk: int = 0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Index:
    """Reference: ivf_pq_types.hpp:264 ``index``.

    ``codebooks``: PER_SUBSPACE (pq_dim, book, pq_len);
                   PER_CLUSTER (n_lists, book, pq_len).
    ``list_codes``: (n_lists, capacity, W) uint8 **bit-packed** PQ codes,
    W = ceil(pq_dim*pq_bits/8) (reference: ivf_pq_codepacking.cuh; at
    pq_bits=8 this is one byte per sub-dim);
    ``rotation``: (dim, rot_dim) orthonormal (identity when not rotated).
    """

    centers: jax.Array
    codebooks: jax.Array
    list_codes: jax.Array
    list_indices: jax.Array
    list_sizes: jax.Array
    rotation: jax.Array
    metric: int = DistanceType.L2Expanded
    codebook_kind: int = CodebookKind.PER_SUBSPACE
    pq_bits: int = 8
    # Derived search-time cache: bf16 PQ reconstructions in list layout
    # (n_lists, capacity, rot_dim).  The codes remain the source of truth
    # (serialization stores codes only; deserialize re-decodes).  On TPU the
    # per-element LUT gather of the reference's compute_similarity_kernel
    # (ivf_pq_search.cuh:611) is VPU-gather-bound (~1e8 elem/s measured); an
    # MXU einsum over cached bf16 reconstructions computes the *identical*
    # quantized distance ||q_rot - recon||^2 at ~100x the throughput.  bf16
    # rounding is finer than the reference's own fp8 LUT option.
    list_recon: Optional[jax.Array] = None
    # Derived with list_recon: per-row squared norms (n_lists, capacity)
    # fp32.  Loop-invariant across searches; caching it keeps a full pass
    # over the recon cache out of every search call (it measurably fused
    # into the probe loop when computed in-call).
    list_recon_sq: Optional[jax.Array] = None
    # Derived search-time cache for scan_mode="codes": the bit-packed
    # codes re-laid out lane-major as (n_lists, Wi, capacity) int32 words
    # (pq_code_scan_pallas.pack_code_lanes) so the Pallas kernel streams
    # ~pq_dim*pq_bits/8 bytes/row, plus the per-row squared norms of the
    # bf16 reconstructions (n_lists, capacity) f32 the distance
    # decomposition needs.  Like list_recon these are derived from the
    # codes (never serialized) and attach lazily on first codes-mode
    # search.
    list_code_lanes: Optional[jax.Array] = None
    list_code_rsq: Optional[jax.Array] = None
    # explicit because list_codes is bit-packed (its trailing axis is the
    # packed byte width, not pq_dim); 0 -> equal to the code width (the
    # pq_bits=8 layout where packing is the identity)
    pq_dim_: int = 0
    # Recall-canary sentinel set (integrity.CanarySet) — host-side
    # metadata, deliberately NOT a pytree leaf (and not aux data either:
    # aux must stay hashable for jit caching), so it does not survive
    # jax transforms; build/extend/serialize carry it explicitly.
    canaries: Optional[object] = None
    # Mutation generation counter (see neighbors/mutate): host-side like
    # canaries — a leaf would be wrong and aux would force a retrace per
    # mutation.  extend/delete/compact stamp parent+1 on the new index.
    generation: int = 0
    # Calibrated group-capacity estimate (round 10): the measured
    # fraction of min(n_lists, P) lists a representative batch's probes
    # touch (see :func:`calibrate_group_capacity`).  0.0 = uncalibrated,
    # which dispatches the grouped scans at the exact-safe worst-case
    # capacity — zero host syncs, no overflow machinery.  Host-side like
    # generation; serialized (v4) through the index envelope.
    group_est: float = 0.0

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def rot_dim(self) -> int:
        return self.rotation.shape[1]

    @property
    def pq_dim(self) -> int:
        # derive from rotation/codebook shapes, NOT list_codes.shape[2]:
        # codes are bit-packed, so their trailing axis is the packed byte
        # width W != pq_dim whenever pq_bits < 8 — an Index constructed
        # directly with default pq_dim_=0 must still decode correctly
        return self.pq_dim_ or self.rotation.shape[1] // self.codebooks.shape[2]

    @property
    def code_width(self) -> int:
        """Packed bytes per vector in ``list_codes``."""
        return self.list_codes.shape[2]

    @property
    def pq_len(self) -> int:
        return self.codebooks.shape[2]

    @property
    def pq_book_size(self) -> int:
        return 1 << self.pq_bits

    @property
    def capacity(self) -> int:
        return self.list_codes.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_sizes))

    def tree_flatten(self):
        leaves = (self.centers, self.codebooks, self.list_codes,
                  self.list_indices, self.list_sizes, self.rotation,
                  self.list_recon, self.list_recon_sq,
                  self.list_code_lanes, self.list_code_rsq)
        return leaves, (self.metric, self.codebook_kind, self.pq_bits,
                        self.pq_dim_)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves[:6], list_recon=leaves[6],
                   list_recon_sq=leaves[7], list_code_lanes=leaves[8],
                   list_code_rsq=leaves[9], metric=aux[0],
                   codebook_kind=aux[1], pq_bits=aux[2], pq_dim_=aux[3])


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

def _make_rotation(dim: int, rot_dim: int, random: bool, seed: int
                   ) -> jax.Array:
    """Orthonormal (dim, rot_dim) transform.  The reference composes
    dim-padding + optional random rotation (ivf_pq_build.cuh rotation matrix);
    identity-pad when not random."""
    if not random and dim == rot_dim:
        return jnp.eye(dim, dtype=jnp.float32)
    key = jax.random.key(seed)
    g = jax.random.normal(key, (dim, rot_dim), jnp.float32) if dim >= rot_dim \
        else jax.random.normal(key, (rot_dim, dim), jnp.float32).T
    q, _ = jnp.linalg.qr(jnp.pad(g, ((0, max(0, rot_dim - dim)), (0, 0))))
    return q[:dim, :rot_dim]


def _subspace_split(x: jax.Array, pq_dim: int) -> jax.Array:
    """(n, rot_dim) -> (n, pq_dim, pq_len)."""
    n, rd = x.shape
    return x.reshape(n, pq_dim, rd // pq_dim)


# ---------------------------------------------------------------------------
# bit-packed code storage (reference: ivf_pq_codepacking.cuh — codes are
# packed to the bit; at pq_bits=4 the index stores HALF the bytes of a
# one-byte-per-subdim layout, which directly caps database size per chip)
# ---------------------------------------------------------------------------

def packed_code_width(pq_dim: int, pq_bits: int) -> int:
    """Bytes per vector of bit-packed codes."""
    return -(-pq_dim * pq_bits // 8)


def _pack_codes(codes: jax.Array, pq_bits: int) -> jax.Array:
    """(..., pq_dim) uint8 codes (< 2^pq_bits) -> (..., W) uint8 packed
    LSB-first, W = ceil(pq_dim*pq_bits/8).  Identity at pq_bits=8."""
    if pq_bits == 8:
        return codes
    *lead, pq_dim = codes.shape
    total = pq_dim * pq_bits
    W = packed_code_width(pq_dim, pq_bits)
    c = codes.astype(jnp.int32)
    bit = jnp.arange(pq_bits, dtype=jnp.int32)
    bits = (c[..., None] >> bit) & 1                   # (..., pq_dim, bits)
    bits = bits.reshape(*lead, total)
    bits = jnp.pad(bits, [(0, 0)] * len(lead) + [(0, W * 8 - total)])
    bits = bits.reshape(*lead, W, 8)
    weights = jnp.int32(1) << jnp.arange(8, dtype=jnp.int32)
    return jnp.sum(bits * weights, axis=-1).astype(jnp.uint8)


def _unpack_codes(packed: jax.Array, pq_dim: int, pq_bits: int) -> jax.Array:
    """Inverse of :func:`_pack_codes`: (..., W) uint8 -> (..., pq_dim)
    uint8.  Each pq_bits-wide field spans at most two bytes; bits past
    the last byte are masked off, so the clipped high-byte read is safe."""
    if pq_bits == 8:
        return packed
    p = packed.astype(jnp.int32)
    W = p.shape[-1]
    bitpos = jnp.arange(pq_dim) * pq_bits
    b0 = bitpos // 8
    shift = bitpos % 8
    lo = jnp.take(p, b0, axis=-1)                      # (..., pq_dim)
    hi = jnp.take(p, jnp.minimum(b0 + 1, W - 1), axis=-1)
    mask = (1 << pq_bits) - 1
    return (((lo | (hi << 8)) >> shift) & mask).astype(jnp.uint8)


# codebook k-means needs ~book_size * a-few-hundred rows; more adds wall
# clock without moving the centroids
_BOOK_TRAIN_ROWS = 65_536


@functools.partial(jax.jit, static_argnames=("book_size", "n_iters"))
def _train_books_per_subspace(resid_sub, keys, book_size, n_iters):
    """Balanced k-means per subspace, sequential over subspaces.

    resid_sub: (pq_dim, n, pq_len) -> codebooks (pq_dim, book, pq_len).
    Reference: train_per_subset (ivf_pq_build.cuh:337) loops
    build_clusters per subspace.  ``lax.map`` (NOT vmap): a vmapped
    balanced loop materializes the (pq_dim, n, book) distance tile at
    once — 16 GB at SIFT-1M scale — while the sequential map peaks at one
    subspace's tile.  Rows are subsampled to _BOOK_TRAIN_ROWS (strided —
    the trainset is already caller-shuffled).
    """
    n = resid_sub.shape[1]
    if n > _BOOK_TRAIN_ROWS:
        stride = n // _BOOK_TRAIN_ROWS
        resid_sub = resid_sub[:, ::stride][:, :_BOOK_TRAIN_ROWS]

    def one(args):
        sub, key = args
        m = sub.shape[0]
        stride = max(m // book_size, 1)
        c0 = sub[::stride][:book_size]
        c0 = jnp.pad(c0, ((0, book_size - c0.shape[0]), (0, 0)), mode="edge")
        centers, _ = kmeans_balanced._balanced_loop(
            sub, c0, key, book_size, n_iters, DistanceType.L2Expanded)
        return centers

    return jax.lax.map(one, (resid_sub, keys))


@functools.partial(jax.jit, static_argnames=("codebook_kind",))
def _encode_chunk(codebooks, r, lab, codebook_kind):
    # d[c, j, k] = ||r[c,j,:] - cb[j,k,:]||^2, summed elementwise over the
    # pq_len axis.  The expanded ||cb||^2 - 2 r.cb form, fused with the
    # argmin, was miscompiled by XLA's TPU backend on a v5e — inside a
    # lax.map, and in a jit that also cast the codes to uint8 (~13% of
    # codes matched a host argmin); codes leave this jit as int32.
    if codebook_kind == CodebookKind.PER_SUBSPACE:
        diff = r[:, :, None, :] - codebooks[None, :, :, :]
    else:
        cb = codebooks[lab]                              # (c, book, pq_len)
        diff = r[:, :, None, :] - cb[:, None, :, :]
    d = jnp.sum(diff * diff, axis=-1)                    # (c, j, k)
    return jnp.argmin(d, axis=-1)


def _encode(codebooks, resid, codebook_kind, labels=None):
    """PQ-encode residuals (n, pq_dim, pq_len) -> (n, pq_dim) uint8.

    Reference: process_and_fill_codes_kernel (ivf_pq_build.cuh:944) — the
    per-subspace argmin over the codebook.  Chunked over rows, one call
    per chunk: the full (n, pq_dim, book) distance tensor is 32 GB at
    SIFT-1M scale.
    """
    n = resid.shape[0]
    chunk = 65_536
    if labels is None:
        labels = jnp.zeros(n, jnp.int32)
    if n <= chunk:
        codes = _encode_chunk(codebooks, resid, labels, codebook_kind)
    else:
        n_pad = -(-n // chunk) * chunk
        rp = jnp.pad(resid, ((0, n_pad - n), (0, 0), (0, 0)))
        lp = jnp.pad(labels, (0, n_pad - n))
        codes = jnp.concatenate([
            _encode_chunk(codebooks, rp[s:s + chunk], lp[s:s + chunk],
                          codebook_kind)
            for s in range(0, n_pad, chunk)])[:n]
    return codes.astype(jnp.uint8)


def build(res, params: IndexParams, dataset, *,
          checkpoint=None, resume: bool = False) -> Index:
    """Build an IVF-PQ index (reference: ivf_pq.cuh:224).

    ``checkpoint`` (a directory path or
    :class:`~raft_tpu.resilience.CheckpointManager`) persists each build
    stage's carry atomically right before its ``interruptible``
    sync point; with ``resume=True`` completed stages are loaded instead
    of recomputed.  Skipped stages still burn the same ``res.next_key()``
    draws they would have consumed, so a resumed build is bit-identical
    to an uninterrupted one.
    """
    from raft_tpu.resilience import as_manager
    ckpt = as_manager(checkpoint)
    with named_range("ivf_pq::build"), \
            obs.build_scope("ivf_pq.build") as rep:
        dataset = ensure_array(dataset, "dataset")
        expects(dataset.ndim == 2, "ivf_pq.build: 2-D dataset required")
        dataset, _ = _boundary.check_matrix(dataset, "dataset",
                                            site="ivf_pq.build",
                                            allow_empty=False)
        n, dim = dataset.shape
        expects(params.n_lists <= n, "ivf_pq.build: n_lists > n_rows")
        expects(4 <= params.pq_bits <= 8,
                "ivf_pq.build: pq_bits in [4, 8] (as the reference)")

        pq_dim = params.pq_dim or max(dim // 4, 1)
        rot_dim = _round_up(dim, pq_dim)
        rotation = _make_rotation(dim, rot_dim,
                                  params.force_random_rotation or
                                  rot_dim != dim, seed=7)

        # ---- coarse quantizer (rotated space) --------------------------
        with obs.stage("ivf_pq.build.kmeans") as st:
            n_train = max(params.n_lists,
                          int(n * params.kmeans_trainset_fraction))
            if n_train < n:
                sel = jax.random.choice(res.next_key(), n, (n_train,),
                                        replace=False)
                trainset = dataset[sel]
            else:
                trainset = dataset
            train_rot = trainset.astype(jnp.float32) @ rotation
            bal = KMeansBalancedParams(n_iters=params.kmeans_n_iters)
            if resume and ckpt is not None and ckpt.has("kmeans"):
                # skip the fit but burn its key draw: the downstream key
                # stream must match an uninterrupted build bit-for-bit
                res.next_key()
                centers = jnp.asarray(ckpt.load("kmeans")["centers"])
            else:
                centers = kmeans_balanced.fit(res, bal, train_rot,
                                              params.n_lists)
                if ckpt is not None:
                    ckpt.save("kmeans", {"centers": np.asarray(centers)})
            # cancellation point: the stage above is durable before a
            # pending cancel() can raise
            interruptible.synchronize(centers)
            st.fence(centers)

        # ---- codebooks over residuals ----------------------------------
        with obs.stage("ivf_pq.build.codebooks") as st:
            book = 1 << params.pq_bits
            if resume and ckpt is not None and ckpt.has("codebooks"):
                # burn this stage's key draws (1 per-subspace, 2
                # per-cluster) for the same reason as above
                res.next_key()
                if params.codebook_kind != CodebookKind.PER_SUBSPACE:
                    res.next_key()
                codebooks = jnp.asarray(ckpt.load("codebooks")["codebooks"])
            else:
                labels_t = kmeans_balanced.predict(res, bal, train_rot,
                                                   centers)
                resid = _subspace_split(train_rot - centers[labels_t],
                                        pq_dim)
                if params.codebook_kind == CodebookKind.PER_SUBSPACE:
                    keys = jax.random.split(res.next_key(), pq_dim)
                    codebooks = _train_books_per_subspace(
                        jnp.transpose(resid, (1, 0, 2)), keys, book,
                        params.kmeans_n_iters)
                else:
                    # per-cluster: one book per coarse list over all its
                    # residual subvectors (train_per_cluster,
                    # ivf_pq_build.cuh:417)
                    flat = resid.reshape(-1, rot_dim // pq_dim)
                    flat_labels = jnp.repeat(labels_t, pq_dim)
                    codebooks = _train_books_per_cluster(
                        res, flat, flat_labels, params.n_lists, book,
                        params.kmeans_n_iters)
                if ckpt is not None:
                    ckpt.save("codebooks",
                              {"codebooks": np.asarray(codebooks)})
            interruptible.synchronize(codebooks)
            st.fence(codebooks)

        index = Index(
            centers=centers, codebooks=codebooks,
            list_codes=jnp.zeros(
                (params.n_lists, _LIST_ALIGN,
                 packed_code_width(pq_dim, params.pq_bits)), jnp.uint8),
            list_indices=jnp.full((params.n_lists, _LIST_ALIGN), -1,
                                  jnp.int32),
            list_sizes=jnp.zeros(params.n_lists, jnp.int32),
            rotation=rotation, metric=params.metric,
            codebook_kind=params.codebook_kind, pq_bits=params.pq_bits,
            pq_dim_=pq_dim)
        if params.add_data_on_build:
            index = extend(res, index, dataset,
                           jnp.arange(n, dtype=jnp.int32))
        if params.cache_reconstructions and index.list_recon is None:
            with obs.stage("ivf_pq.build.recon_cache") as st:
                index = _with_recon(res, index)
                st.fence(index.list_recon)
        if params.canary_queries > 0 and params.add_data_on_build:
            cs = _canary.make(res, dataset, metric=params.metric,
                              n_queries=params.canary_queries,
                              k=params.canary_k, floor=params.canary_floor)
            index.canaries = cs
            cs.build_recall = _canary.measure(res, index, cs)
            if resume:
                _canary.auto_check(res, index, site="resume")
        return rep.attach(index)


def _train_books_per_cluster(res, flat, flat_labels, n_lists, book, n_iters):
    """Per-cluster codebooks: k-means over each list's residual subvectors.

    XLA-friendly approximation of train_per_cluster (ivf_pq_build.cuh:417):
    rather than ragged per-cluster trainsets, run the vmapped balanced loop
    over per-cluster *resampled* fixed-size subsets.
    """
    n = flat.shape[0]
    per = max(book * 4, 256)
    # sample `per` member rows per cluster (with replacement via gumbel over
    # membership mask)
    key = res.next_key()
    g = jax.random.gumbel(key, (n_lists, n))
    member = (flat_labels[None, :] == jnp.arange(n_lists)[:, None])
    scores = jnp.where(member, g, -jnp.inf)
    vals, idx = jax.lax.top_k(scores, per)            # (n_lists, per)
    # clusters with < per members: top_k falls through to -inf scores whose
    # indices point at OTHER clusters' rows — replace them by cycling over
    # the cluster's valid members (top_k sorts valid picks first)
    n_valid = jnp.sum(vals > -jnp.inf, axis=1)        # (n_lists,)
    j_mod = jnp.arange(per)[None, :] % jnp.maximum(n_valid, 1)[:, None]
    idx = jnp.take_along_axis(idx, j_mod, axis=1)
    subsets = flat[idx]                               # (n_lists, per, len)
    keys = jax.random.split(res.next_key(), n_lists)

    def one(sub, k):
        stride = max(per // book, 1)
        c0 = sub[::stride][:book]
        c0 = jnp.pad(c0, ((0, book - c0.shape[0]), (0, 0)), mode="edge")
        centers, _ = kmeans_balanced._balanced_loop(
            sub, c0, k, book, n_iters, DistanceType.L2Expanded)
        return centers

    return jax.vmap(one)(subsets, keys)


def extend(res, index: Index, new_vectors, new_indices=None) -> Index:
    """Encode + add vectors (reference: ivf_pq.cuh:266 ``extend``)."""
    with named_range("ivf_pq::extend"):
        new_vectors = ensure_array(new_vectors, "new_vectors")
        new_vectors, _ = _boundary.check_matrix(
            new_vectors, "new_vectors", site="ivf_pq.extend", dim=index.dim)
        expects(new_vectors.ndim == 2 and new_vectors.shape[1] == index.dim,
                "ivf_pq.extend: dim mismatch")
        n_new = new_vectors.shape[0]
        if new_indices is None:
            new_indices = index.size + jnp.arange(n_new, dtype=jnp.int32)
        else:
            new_indices = ensure_array(new_indices, "new_indices")

        bal = KMeansBalancedParams()
        # chunk the rotate→assign→encode pipeline: at deep scale (10M+
        # rows) the full-width rotation + residual transients are
        # several copies of the dataset and OOM a single chip; per-chunk
        # the peak extra memory is O(chunk * rot_dim)
        chunk = 1 << 20
        # the decoded rows also feed the code-lane cache's row norms, so
        # a lean codes-mode index (lanes attached, no bf16 recon) still
        # gets coherent appended norms
        want_recon_rows = (index.list_recon is not None
                           or index.list_code_lanes is not None)
        with obs.stage("ivf_pq.extend.encode") as st:
            codes_parts, label_parts, recon_parts = [], [], []
            for s0 in range(0, n_new, chunk):
                v = new_vectors[s0:s0 + chunk]
                rot_c = v.astype(jnp.float32) @ index.rotation
                lab_c = kmeans_balanced.predict(res, bal, rot_c,
                                                index.centers)
                resid_c = _subspace_split(rot_c - index.centers[lab_c],
                                          index.pq_dim)
                cu = _encode(index.codebooks, resid_c, index.codebook_kind,
                             lab_c)
                if want_recon_rows:
                    recon_parts.append(_decode_rows(index.codebooks, cu,
                                                    lab_c,
                                                    index.codebook_kind))
                codes_parts.append(_pack_codes(cu, index.pq_bits))
                label_parts.append(lab_c)
            codes = (jnp.concatenate(codes_parts)
                     if len(codes_parts) > 1 else codes_parts[0])
            labels = (jnp.concatenate(label_parts)
                      if len(label_parts) > 1 else label_parts[0])
            recon_rows = None
            if want_recon_rows:
                recon_rows = (jnp.concatenate(recon_parts)
                              if len(recon_parts) > 1 else recon_parts[0])
            st.fence(codes, labels)

        new_counts = jax.ops.segment_sum(
            jnp.ones(n_new, jnp.int32), labels,
            num_segments=index.n_lists)
        needed = index.list_sizes + new_counts
        # fast path: headroom in every touched list — O(n_new) scatter-append
        # (one (n_lists,)-reduction host sync decides; see ivf_flat.extend)
        if int(jnp.max(needed)) <= index.capacity:
            with obs.stage("ivf_pq.extend.pack") as st:
                rsq_rows = (jnp.sum(recon_rows.astype(jnp.float32) ** 2,
                                    axis=-1)
                            if recon_rows is not None else None)
                # every derived cache appends at the same slots in the
                # same scatter pass — `at[name]` records each buffer's
                # position in the returned tuple
                bufs, rows, at = [index.list_codes], [codes], {}

                def _add(name, buf, row):
                    at[name] = len(bufs)
                    bufs.append(buf)
                    rows.append(row)

                if index.list_recon is not None:
                    _add("recon", index.list_recon, recon_rows)
                    if index.list_recon_sq is not None:
                        _add("recon_sq", index.list_recon_sq, rsq_rows)
                # the code-lane cache's row norms may alias list_recon_sq
                # (see _with_code_lanes) — append the shared buffer once
                rsq_shared = (index.list_code_lanes is not None
                              and index.list_code_rsq is not None
                              and index.list_code_rsq is index.list_recon_sq
                              and "recon_sq" in at)
                lane_bufs, lane_rows = (), ()
                if index.list_code_lanes is not None:
                    from raft_tpu.ops import pq_code_scan_pallas as pcs
                    lane_bufs = (index.list_code_lanes,)
                    lane_rows = (pcs.pack_row_lanes(codes),)
                    if index.list_code_rsq is not None and not rsq_shared:
                        _add("code_rsq", index.list_code_rsq, rsq_rows)
                new_bufs, new_lanes, list_idx, sizes = _append_lists_multi(
                    tuple(bufs), tuple(rows), index.list_indices,
                    index.list_sizes, labels, new_indices,
                    lane_bufs, lane_rows)
                st.fence(new_bufs)
            out = Index(
                centers=index.centers, codebooks=index.codebooks,
                list_codes=new_bufs[0], list_indices=list_idx,
                list_sizes=sizes, rotation=index.rotation,
                metric=index.metric, codebook_kind=index.codebook_kind,
                pq_bits=index.pq_bits, pq_dim_=index.pq_dim)
            if index.list_recon is not None:
                out.list_recon = new_bufs[at["recon"]]
                out.list_recon_sq = (new_bufs[at["recon_sq"]]
                                     if "recon_sq" in at
                                     else _recon_sq(out.list_recon))
            if index.list_code_lanes is not None:
                out.list_code_lanes = new_lanes[0]
                if rsq_shared:
                    out.list_code_rsq = out.list_recon_sq
                elif "code_rsq" in at:
                    out.list_code_rsq = new_bufs[at["code_rsq"]]
            _mutate.next_generation(index, out)
            if index.canaries is not None:
                out.canaries = index.canaries
                _canary.auto_check(res, out, site="extend")
            return out

        # flatten existing + concat + repack (same dance as ivf_flat.extend)
        old_valid = (index.list_indices >= 0).ravel()
        old_labels = jnp.repeat(jnp.arange(index.n_lists, dtype=jnp.int32),
                                index.capacity)[old_valid]
        old_codes = index.list_codes.reshape(-1, index.code_width)[old_valid]
        old_ids = index.list_indices.ravel()[old_valid]

        all_codes = jnp.concatenate([old_codes, codes])
        all_ids = jnp.concatenate([old_ids, new_indices.astype(jnp.int32)])
        all_labels = jnp.concatenate([old_labels, labels])

        # +1 before rounding: never leave the fullest list brim-full after
        # a repack (see ivf_flat.extend) — a build lands here via the empty
        # index, so this also guarantees every fresh build has append room
        capacity = _round_up(max(int(jnp.max(needed)) + 1, _LIST_ALIGN),
                             _LIST_ALIGN)
        with obs.stage("ivf_pq.extend.pack") as st:
            list_codes, list_idx, sizes = _pack_lists(
                all_codes, all_labels, all_ids, index.n_lists, capacity)
            st.fence(list_codes)

        out = Index(
            centers=index.centers, codebooks=index.codebooks,
            list_codes=list_codes, list_indices=list_idx,
            list_sizes=sizes, rotation=index.rotation,
            metric=index.metric, codebook_kind=index.codebook_kind,
            pq_bits=index.pq_bits, pq_dim_=index.pq_dim)
        # the cache is attached only when the source index carries one (or
        # at build time per IndexParams.cache_reconstructions) — a lean
        # index never materializes (n, rot_dim) reconstructions
        if index.list_recon is not None:
            out = _with_recon(res, out)
        # repack moved every row, so scan caches rebuild from the fresh
        # codes rather than arriving cold at the next search
        if index.list_code_lanes is not None:
            out = _with_code_lanes(out)
        _mutate.next_generation(index, out)
        if index.canaries is not None:
            out.canaries = index.canaries
            _canary.auto_check(res, out, site="extend")
        return out


def delete(res, index: Index, ids) -> Index:
    """Tombstone-delete rows by source id (the online mutation layer —
    see :mod:`raft_tpu.neighbors.mutate` for the encoding).

    Rewrites the matching ``list_indices`` slots to tombstones; every
    scan formulation (recon/codes/lut and the fused Pallas kernels)
    already masks negative ids to the worst-distance sentinel,
    so deleted rows vanish from results immediately without touching
    the codes, any derived cache, or fused-path eligibility.  Storage
    is reclaimed by :func:`compact`.  Ids not present match nothing.

    Returns a NEW index — the next generation — sharing every array
    except ``list_indices`` with its parent; readers pinned on the
    parent are unaffected.
    """
    with named_range("ivf_pq::delete"):
        ids = ensure_array(ids, "ids")
        expects(ids.ndim == 1, "ivf_pq.delete: 1-D ids required")
        new_li, _ = _mutate.tombstone(index.list_indices, ids)
        out = Index(
            centers=index.centers, codebooks=index.codebooks,
            list_codes=index.list_codes, list_indices=new_li,
            list_sizes=index.list_sizes, rotation=index.rotation,
            metric=index.metric, codebook_kind=index.codebook_kind,
            pq_bits=index.pq_bits, pq_dim_=index.pq_dim,
            list_recon=index.list_recon,
            list_recon_sq=index.list_recon_sq,
            list_code_lanes=index.list_code_lanes,
            list_code_rsq=index.list_code_rsq)
        out.canaries = index.canaries
        _mutate.next_generation(index, out)
        if index.canaries is not None:
            _canary.auto_check(res, out, site="delete")
        return out


def upsert(res, index: Index, ids, vectors) -> Index:
    """Replace-or-insert rows under explicit source ids: tombstone any
    existing rows with these ids, then encode + append ``vectors`` under
    the same ids — one logical mutation, ONE generation bump (the churn
    loop ``upsert -> upsert`` advances the counter like a single
    ``extend``, so generation-keyed caches see one swap per batch, not
    two).  Ids not present simply insert; duplicate live ids are all
    tombstoned first, so each id resolves to exactly one live row."""
    with named_range("ivf_pq::upsert"):
        ids = ensure_array(ids, "ids")
        vectors = ensure_array(vectors, "vectors")
        expects(ids.ndim == 1 and ids.shape[0] == vectors.shape[0],
                "ivf_pq.upsert: ids must be 1-D, one per vector")
        parent_gen = _mutate.generation(index)
        out = extend(res, delete(res, index, ids), vectors,
                     new_indices=ids)
        out.generation = parent_gen + 1
        if obs.enabled():
            obs.registry().counter("ivf_pq.upserts").inc()
        return out


def compact(res, index: Index) -> Index:
    """Reclaim tombstoned slots: stable-partition each list's live rows
    to the front, drop every tombstone, shrink the shared capacity to
    fit the fullest surviving list, and rebuild whichever derived scan
    caches the parent carried from the fresh codes (compaction moves
    rows, so the caches cannot be permuted in place safely at 3
    different layouts).  Returns a new generation sharing
    ``centers``/``codebooks``/``rotation`` with its parent."""
    with named_range("ivf_pq::compact"):
        order, sizes = _mutate.compaction_order(index.list_indices)
        max_size = int(jnp.max(sizes)) if index.n_lists else 0
        capacity = _round_up(max(max_size + 1, _LIST_ALIGN), _LIST_ALIGN)
        capacity = min(capacity, max(index.capacity, _LIST_ALIGN))

        li = jnp.take_along_axis(index.list_indices, order,
                                 axis=1)[:, :capacity]
        codes = jnp.take_along_axis(index.list_codes, order[:, :, None],
                                    axis=1)[:, :capacity]
        live = (jnp.arange(capacity, dtype=jnp.int32)[None, :]
                < sizes[:, None])
        li = jnp.where(live, li, -1)
        codes = jnp.where(live[:, :, None], codes, 0)

        out = Index(
            centers=index.centers, codebooks=index.codebooks,
            list_codes=codes, list_indices=li, list_sizes=sizes,
            rotation=index.rotation, metric=index.metric,
            codebook_kind=index.codebook_kind, pq_bits=index.pq_bits,
            pq_dim_=index.pq_dim)
        if index.list_recon is not None:
            out = _with_recon(res, out)
        if index.list_code_lanes is not None:
            out = _with_code_lanes(out)
        out.canaries = index.canaries
        _mutate.next_generation(index, out)
        if index.canaries is not None:
            _canary.auto_check(res, out, site="compact")
        return out


# ---------------------------------------------------------------------------
# reconstruction cache (TPU-native replacement for the smem LUT scan)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("codebook_kind", "pq_dim",
                                             "pq_bits"))
def _decode_lists(centers, codebooks, list_codes, codebook_kind, pq_dim,
                  pq_bits):
    """Decode every list's PQ codes to bf16 RESIDUAL reconstructions
    (n_lists, capacity, rot_dim) = concat_j codebook_j[code_j].

    Residuals (not absolute vectors) keep magnitudes small so bf16 rounding
    stays small relative to the distances — the absolute form suffers
    catastrophic cancellation when ||x||^2 >> d.  One-time cost per
    build/extend; the per-element codebook gather runs once here instead of
    once per query-probe in the reference's compute_similarity LUT loop
    (ivf_pq_search.cuh:611).
    """
    del centers  # residual space: centers fold in at search time, in fp32
    L, cap, W = list_codes.shape
    pq_len = codebooks.shape[-1]
    mask = (1 << pq_bits) - 1

    def code_at(j):
        """Unpack subspace j's codes only — a full upfront unpack is an
        (L, cap, pq_dim) int32 transient, 4x the packed bytes (2.5 GB at
        deep scale); per-step it is one (L, cap) slice."""
        bitpos = j * pq_bits
        b0 = bitpos // 8
        shift = bitpos % 8
        lo = jnp.take(list_codes, b0, axis=-1).astype(jnp.int32)
        hi = jnp.take(list_codes, jnp.minimum(b0 + 1, W - 1),
                      axis=-1).astype(jnp.int32)
        return ((lo | (hi << 8)) >> shift) & mask

    # One subspace at a time via scan + dynamic_update_slice: a single
    # (L, cap, pq_dim, pq_len) gather output gets its pq_len axis padded to
    # 128 lanes by TPU tiling — a 32x HBM blowup (OOM at realistic sizes).
    # The per-step (L, cap, pq_len) transient keeps peak memory at ~2x the
    # final (L, cap, rot_dim) cache.
    def step(acc, j):
        cj = code_at(j)                                  # (L, cap) int32
        if codebook_kind == CodebookKind.PER_SUBSPACE:
            part = codebooks[j][cj]                      # (L, cap, len)
        else:
            part = codebooks[jnp.arange(L)[:, None], cj]
        return jax.lax.dynamic_update_slice(
            acc, part.astype(jnp.bfloat16), (0, 0, j * pq_len)), None

    acc0 = jnp.zeros((L, cap, pq_dim * pq_len), jnp.bfloat16)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(pq_dim))
    return acc


@functools.partial(jax.jit, static_argnames=("codebook_kind",))
def _decode_rows(codebooks, codes, labels, codebook_kind):
    """Decode (n, pq_dim) codes to bf16 residual reconstructions
    (n, rot_dim) — the row-wise twin of :func:`_decode_lists`, used by the
    extend fast path to update the cache without re-decoding the index."""
    n, pq_dim = codes.shape
    pq_len = codebooks.shape[-1]
    ci = codes.astype(jnp.int32)

    def step(acc, j):
        if codebook_kind == CodebookKind.PER_SUBSPACE:
            part = codebooks[j][ci[:, j]]                # (n, len)
        else:
            part = codebooks[labels, ci[:, j]]
        return jax.lax.dynamic_update_slice(
            acc, part.astype(jnp.bfloat16), (0, j * pq_len)), None

    acc0 = jnp.zeros((n, pq_dim * pq_len), jnp.bfloat16)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(pq_dim))
    return acc


@jax.jit
def _recon_sq(list_recon):
    return jnp.sum(list_recon.astype(jnp.float32) ** 2, axis=-1)


def _with_recon(res, index: Index) -> Index:
    """Attach the derived reconstruction cache (+ squared norms)."""
    index.list_recon = _decode_lists(index.centers, index.codebooks,
                                     index.list_codes, index.codebook_kind,
                                     index.pq_dim, index.pq_bits)
    index.list_recon_sq = _recon_sq(index.list_recon)
    return index


@functools.partial(jax.jit, static_argnames=("pq_dim", "pq_bits"))
def _rsq_from_codes(codebooks, list_codes, pq_dim, pq_bits):
    """Per-row ||recon||^2 (n_lists, cap) f32 straight from the packed
    codes — Σ_j ||cb_bf16[j, code_j]||^2.  Subspaces occupy disjoint
    coordinates of the concatenated reconstruction, so the per-subspace
    norms sum exactly; squaring the *bf16-rounded* codebook keeps the
    value identical to _recon_sq(list_recon) without materializing the
    (n_lists, cap, rot_dim) cache (per-subspace codebooks only)."""
    L, cap, W = list_codes.shape
    mask = (1 << pq_bits) - 1
    cb_sq = jnp.sum(
        codebooks.astype(jnp.bfloat16).astype(jnp.float32) ** 2,
        axis=-1)                                         # (pq_dim, book)

    def step(acc, j):
        bitpos = j * pq_bits
        b0 = bitpos // 8
        shift = bitpos % 8
        lo = jnp.take(list_codes, b0, axis=-1).astype(jnp.int32)
        hi = jnp.take(list_codes, jnp.minimum(b0 + 1, W - 1),
                      axis=-1).astype(jnp.int32)
        cj = ((lo | (hi << 8)) >> shift) & mask          # (L, cap)
        return acc + cb_sq[j][cj], None

    acc, _ = jax.lax.scan(step, jnp.zeros((L, cap), jnp.float32),
                          jnp.arange(pq_dim))
    return acc


def _with_code_lanes(index: Index) -> Index:
    """Attach the lane-major packed-code cache for the compact-code
    kernel (plus the row norms its distance decomposition needs)."""
    from raft_tpu.ops import pq_code_scan_pallas as pcs
    index.list_code_lanes = pcs.pack_code_lanes(index.list_codes)
    if index.list_recon_sq is not None:
        index.list_code_rsq = index.list_recon_sq
    else:
        index.list_code_rsq = _rsq_from_codes(
            index.codebooks, index.list_codes, index.pq_dim, index.pq_bits)
    return index


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "metric"))
def _search_impl_recon(centers, list_recon, list_indices, rotation, queries,
                       k, n_probes, metric, probes=None, list_recon_sq=None,
                       filter_words=None):
    """MXU scan over cached bf16 reconstructions — same quantized distance
    as the LUT path (||q_rot - recon||^2), structured like the IVF-Flat
    interleaved scan instead of the GPU's shared-memory LUT kernel.
    ``probes``/``list_recon_sq`` are accepted precomputed (the public
    search paths already have them); both are derived here when absent."""
    nq = queries.shape[0]
    qrot = (queries.astype(jnp.float32) @ rotation)
    cf = centers.astype(jnp.float32)
    ip_metric = metric == DistanceType.InnerProduct

    q_dot_c = jax.lax.dot_general(qrot, cf, (((1,), (1,)), ((), ())),
                                  precision=get_matmul_precision(),
                                  preferred_element_type=jnp.float32)
    if probes is None:
        probes = _select_clusters(centers, rotation, queries, n_probes,
                                  metric)

    worst = -jnp.inf if ip_metric else jnp.inf
    cap = list_recon.shape[1]
    # loop-invariant: per-row squared norms of the residual reconstructions
    rec_sq = (list_recon_sq if list_recon_sq is not None
              else jnp.sum(list_recon.astype(jnp.float32) ** 2, axis=-1))

    def probe_distances(p):
        """(q, cap) quantized distances + ids for probe rank p."""
        lists = probes[:, p]                         # (q,)
        data = list_recon[lists]                     # (q, cap, rot) bf16
        ids = list_indices[lists]                    # (q, cap)
        if ip_metric:
            # q.x = q.center_l + q.dec_resid
            qb = qrot.astype(jnp.bfloat16)
            ip = jnp.einsum("qd,qcd->qc", qb, data,
                            preferred_element_type=jnp.float32)
            d = ip + jnp.take_along_axis(q_dot_c, lists[:, None], axis=1)
        else:
            # residual space: ||resid_q - dec_resid||^2 — small magnitudes,
            # so the bf16 MXU pass loses no meaningful precision
            sub = qrot - cf[lists]                   # (q, rot) fp32
            ip = jnp.einsum("qd,qcd->qc", sub.astype(jnp.bfloat16), data,
                            preferred_element_type=jnp.float32)
            d = jnp.maximum(jnp.sum(sub * sub, axis=1)[:, None]
                            + rec_sq[lists] - 2.0 * ip, 0.0)
        d = jnp.where(ids >= 0, d, worst)
        if filter_words is not None:
            # admission fold through the same seam as tombstones: a
            # rejected row is worst BEFORE the per-probe top-kt, so the
            # select never spends a slot on it
            adm = _fbits.query_bits(filter_words, jnp.arange(nq), ids)
            d = jnp.where(adm > 0, d, worst)
        return d, ids

    # Hierarchical select (exact): every probe keeps its local top-k inside
    # the scan — any global top-k candidate is necessarily in its own
    # probe's top-k — and ONE final select runs over the (n_probes * k)
    # survivors.  This beats both per-probe merge chains (n_probes running
    # merges) and a single select over all n_probes*cap candidates (a
    # 40k-wide sort dominated the trace at 128 probes): the in-loop top_k
    # is over cap-wide rows and the final sort is k/cap times narrower.
    kt = min(k, cap)

    def acc_step(carry, p):
        alld, alli = carry
        d, ids = probe_distances(p)
        td, ti = select_k(d, kt, in_idx=ids, select_min=not ip_metric)
        alld = jax.lax.dynamic_update_slice(alld, td, (0, p * kt))
        alli = jax.lax.dynamic_update_slice(alli, ti, (0, p * kt))
        return (alld, alli), None

    alld = jnp.full((nq, n_probes * kt), worst, jnp.float32)
    alli = jnp.full((nq, n_probes * kt), -1, jnp.int32)
    (alld, alli), _ = jax.lax.scan(acc_step, (alld, alli),
                                   jnp.arange(n_probes))
    from raft_tpu.neighbors import grouped
    return grouped.finalize_topk(
        alld, alli, nq, k, not ip_metric,
        metric in (DistanceType.L2SqrtExpanded,
                   DistanceType.L2SqrtUnexpanded), select_k)


@functools.partial(jax.jit, static_argnames=("n_probes", "metric",
                                             "recall_target", "exact"))
def _select_clusters(centers, rotation, queries, n_probes, metric,
                     recall_target=0.95, exact=False):
    """Coarse top-``n_probes`` ranking (ivf_pq_search.cuh:133
    ``select_clusters``): rotate queries, then the IVF-Flat ranking —
    ONE copy of the rank arithmetic serves both index types."""
    from raft_tpu.neighbors import ivf_flat as _flat

    qrot = queries.astype(jnp.float32) @ rotation
    return _flat._select_clusters(centers, qrot, n_probes, metric,
                                  recall_target=recall_target, exact=exact)


@functools.partial(jax.jit, static_argnames=("k", "metric", "n_groups",
                                             "block", "use_pallas",
                                             "pallas_interpret", "kt"))
def _search_impl_recon_grouped(centers, list_recon, list_recon_sq,
                               list_indices, rotation, queries, probes, k,
                               metric, n_groups, block, use_pallas=False,
                               pallas_interpret=False, kt=0,
                               filter_words=None):
    """List-centric recon scan over fixed-size pair groups.

    See :mod:`raft_tpu.neighbors.grouped` for the design (and the measured
    failure of the earlier one-bucket-per-list variant).  Each group is
    GROUP (query, probe) pairs of ONE list: the (B, GROUP, rot) query tile
    against the (B, cap, rot) list tile is a full-width batched MXU GEMM,
    each list's data is read ~once, and padding is bounded regardless of
    probe-popularity skew.  Same quantized distance as the probe-order
    path (differences are bf16-accumulation-order level; measured top-k
    overlap >99%); only the iteration order changes.
    """
    from raft_tpu.neighbors import grouped

    nq, n_probes = probes.shape
    P = nq * n_probes
    n_lists, cap, rot = list_recon.shape
    ip_metric = metric == DistanceType.InnerProduct
    worst = -jnp.inf if ip_metric else jnp.inf

    qrot = queries.astype(jnp.float32) @ rotation
    cf = centers.astype(jnp.float32)

    group_list, slot_pairs = grouped.build_groups(probes, n_lists, n_groups)
    # per-(slot, candidate) admission words in list-slot order — shared
    # by the Pallas kernel (streamed through VMEM) and derived once here
    adm_words = None
    if filter_words is not None:
        adm_words = _fbits.group_admission_words(
            filter_words, group_list, slot_pairs, list_indices, n_probes, P)

    # kt < k (SearchParams.per_probe_topk) narrows the per-pair keep-set:
    # the extraction-bound kernel speeds up near-linearly, at the cost of
    # candidates a single probe contributed beyond rank kt
    kt = min(kt or k, cap)
    if use_pallas:
        from raft_tpu.ops import pq_group_scan_pallas as pqp

        if pqp.supported(not ip_metric, cap, rot, kt, nq):
            # fused query-gather + MXU-distance + in-VMEM top-kt + id
            # mapping: neither the distance matrix nor the gathered query
            # residuals ever reach HBM (see the kernel module docstring)
            vals, ti = pqp.grouped_l2_scan(
                group_list, slot_pairs, qrot, cf, list_recon,
                list_recon_sq, list_indices, kt, n_probes,
                interpret=pallas_interpret, adm_words=adm_words)
            outd, outi = grouped.scatter_packed(vals, ti, slot_pairs, P,
                                                not ip_metric)
            return grouped.finalize_topk(
                outd, outi, nq, k, not ip_metric,
                metric in (DistanceType.L2SqrtExpanded,
                           DistanceType.L2SqrtUnexpanded), select_k)

    def distance_block(gl, slot):
        qid = jnp.where(slot < P, slot // n_probes, 0)
        qv = qrot[qid]                                   # (B, G, rot)
        data = list_recon[gl]                            # (B, cap, rot) bf16
        ids = list_indices[gl]
        cfb = cf[gl]                                     # (B, rot)
        if ip_metric:
            ip = jnp.einsum("bqr,bcr->bqc", qv.astype(jnp.bfloat16), data,
                            preferred_element_type=jnp.float32)
            qc = jnp.einsum("bqr,br->bq", qv, cfb,
                            precision=get_matmul_precision())
            d = ip + qc[:, :, None]
        else:
            rsq = list_recon_sq[gl]                      # (B, cap)
            sub = qv - cfb[:, None, :]                   # (B, G, rot)
            ip = jnp.einsum("bqr,bcr->bqc", sub.astype(jnp.bfloat16), data,
                            preferred_element_type=jnp.float32)
            d = jnp.maximum(jnp.sum(sub * sub, axis=-1)[:, :, None]
                            + rsq[:, None, :] - 2.0 * ip, 0.0)
        d = jnp.where(ids[:, None, :] >= 0, d, worst)
        if filter_words is not None:
            qid = jnp.where(slot < P, slot // n_probes, 0)
            adm = _fbits.query_bits(
                filter_words, qid,
                jnp.broadcast_to(ids[:, None, :], d.shape))
            d = jnp.where(adm > 0, d, worst)
        return d, ids

    outd, outi = grouped.scan_and_scatter(
        group_list, slot_pairs, P, cap, k, not ip_metric, block,
        select_k, distance_block, kt=kt)
    return grouped.finalize_topk(
        outd, outi, nq, k, not ip_metric,
        metric in (DistanceType.L2SqrtExpanded,
                   DistanceType.L2SqrtUnexpanded), select_k)


@functools.partial(jax.jit, static_argnames=("k", "kt", "metric", "n_groups",
                                             "pq_bits", "pallas_interpret"))
def _search_impl_codes_grouped(centers, codebooks, list_code_lanes,
                               list_code_rsq, list_indices, rotation,
                               queries, probes, k, kt, metric, n_groups,
                               pq_bits, pallas_interpret=False,
                               filter_words=None):
    """Grouped COMPACT-CODE scan: the Pallas kernel streams lane-major
    packed codes (~pq_bits/8 bytes per subspace per row — the recon path
    reads 2*pq_len) and decodes them in-register against the
    VMEM-resident codebook table via per-subspace one-hot MXU
    contractions (pq_code_scan_pallas).  Distances equal the recon path's
    bit-for-bit: the kernel's bf16 codebook cast reproduces the bf16
    cache values.  L2-family metrics + per-subspace codebooks only —
    search() gates on pq_code_scan_pallas.supported_codes and falls back
    to the LUT formulation otherwise."""
    from raft_tpu.neighbors import grouped
    from raft_tpu.ops import pq_code_scan_pallas as pcs

    nq, n_probes = probes.shape
    P = nq * n_probes
    n_lists = centers.shape[0]
    cap = list_code_lanes.shape[2]
    qrot = queries.astype(jnp.float32) @ rotation
    cf = centers.astype(jnp.float32)

    group_list, slot_pairs = grouped.build_groups(probes, n_lists, n_groups)
    adm_words = None
    if filter_words is not None:
        adm_words = _fbits.group_admission_words(
            filter_words, group_list, slot_pairs, list_indices, n_probes, P)
    kt = min(kt or k, cap)
    vals, ti = pcs.grouped_code_scan(
        group_list, slot_pairs, qrot, cf, list_code_lanes, codebooks,
        list_code_rsq, list_indices, kt, n_probes, pq_bits,
        interpret=pallas_interpret, adm_words=adm_words)
    outd, outi = grouped.scatter_packed(vals, ti, slot_pairs, P, True)
    return grouped.finalize_topk(
        outd, outi, nq, k, True,
        metric in (DistanceType.L2SqrtExpanded,
                   DistanceType.L2SqrtUnexpanded), select_k)


def _fused_epilogue(vals, ids, qorder, nq, k, metric):
    """Shared tail of the fused scans: query-major (nq_pad, k) kernel
    output -> (nq, k) rows, finite-worst sentinel -> the public +inf /
    id -1 contract, sqrt for the sqrt-L2 metrics, and the un-permute of
    the probe-overlap query order.  Note what is ABSENT: no scatter and
    no select — the kernel already holds each query's final top-k."""
    from raft_tpu.ops.pq_group_scan_pallas import _ACC_WORST

    d = vals[:nq]
    i = ids[:nq]
    bad = d >= _ACC_WORST / 2
    d = jnp.where(bad, jnp.inf, d)
    i = jnp.where(bad, -1, i)
    if metric in (DistanceType.L2SqrtExpanded,
                  DistanceType.L2SqrtUnexpanded):
        d = jnp.sqrt(jnp.maximum(d, 0.0))
    inv = jnp.argsort(qorder)
    return d[inv], i[inv]


@functools.partial(jax.jit, static_argnames=("k", "kt", "metric", "n_groups",
                                             "pq_bits", "pallas_interpret"))
def _search_impl_fused_codes_grouped(centers, codebooks, list_code_lanes,
                                     list_code_rsq, list_indices, rotation,
                                     queries, probes, k, kt, metric,
                                     n_groups, pq_bits,
                                     pallas_interpret=False,
                                     filter_words=None):
    """Fused compact-code scan: the grouped code scan with the per-query
    top-k folded INTO the kernel (pq_code_scan_pallas
    ``grouped_code_scan_fused``) — per-pair candidates never reach HBM,
    and the scatter + final-select stages of
    :func:`_search_impl_codes_grouped` do not exist here.  Queries are
    pre-permuted by probe overlap (grouped.probe_overlap_order) so hot
    lists stream once per batch."""
    from raft_tpu.neighbors import grouped
    from raft_tpu.ops import pq_code_scan_pallas as pcs

    nq, n_probes = probes.shape
    n_lists = centers.shape[0]
    cap = list_code_lanes.shape[2]
    qrot = queries.astype(jnp.float32) @ rotation
    cf = centers.astype(jnp.float32)

    qorder = grouped.probe_overlap_order(probes, n_lists)
    group_list, slot_pairs = grouped.build_groups(probes[qorder], n_lists,
                                                  n_groups)
    adm_words = None
    if filter_words is not None:
        # slot pairs decode to PERMUTED query ids — permute the filter
        # rows identically or every query consults its neighbor's bits
        adm_words = _fbits.group_admission_words(
            filter_words[qorder], group_list, slot_pairs, list_indices,
            n_probes, nq * n_probes)
    kt = min(kt or k, cap)
    vals, ids = pcs.grouped_code_scan_fused(
        group_list, slot_pairs, qrot[qorder], cf, list_code_lanes,
        codebooks, list_code_rsq, list_indices, kt, k, n_probes, pq_bits,
        interpret=pallas_interpret, adm_words=adm_words)
    return _fused_epilogue(vals, ids, qorder, nq, k, metric)


@functools.partial(jax.jit, static_argnames=("k", "kt", "metric", "n_groups",
                                             "pallas_interpret"))
def _search_impl_fused_recon_grouped(centers, list_recon, list_recon_sq,
                                     list_indices, rotation, queries,
                                     probes, k, kt, metric, n_groups,
                                     pallas_interpret=False,
                                     filter_words=None):
    """Fused recon scan: :func:`_search_impl_recon_grouped`'s Pallas
    path with the per-query top-k folded into the kernel
    (pq_group_scan_pallas ``grouped_l2_scan_fused``) — same quantized
    distances, no scatter, no final select."""
    from raft_tpu.neighbors import grouped
    from raft_tpu.ops import pq_group_scan_pallas as pqp

    nq, n_probes = probes.shape
    n_lists, cap, _ = list_recon.shape
    qrot = queries.astype(jnp.float32) @ rotation
    cf = centers.astype(jnp.float32)

    qorder = grouped.probe_overlap_order(probes, n_lists)
    group_list, slot_pairs = grouped.build_groups(probes[qorder], n_lists,
                                                  n_groups)
    adm_words = None
    if filter_words is not None:
        adm_words = _fbits.group_admission_words(
            filter_words[qorder], group_list, slot_pairs, list_indices,
            n_probes, nq * n_probes)
    kt = min(kt or k, cap)
    vals, ids = pqp.grouped_l2_scan_fused(
        group_list, slot_pairs, qrot[qorder], cf, list_recon,
        list_recon_sq, list_indices, kt, k, n_probes,
        interpret=pallas_interpret, adm_words=adm_words)
    return _fused_epilogue(vals, ids, qorder, nq, k, metric)


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "k", "n_probes", "metric", "codebook_kind", "lut_dtype", "pq_bits",
    "coarse_recall_target", "exact_coarse"))
def _search_impl(centers, codebooks, list_codes, list_indices, rotation,
                 queries, k, n_probes, metric, codebook_kind, lut_dtype,
                 pq_bits=8, coarse_recall_target=0.95, exact_coarse=False,
                 filter_words=None):
    nq = queries.shape[0]
    qrot = queries.astype(jnp.float32) @ rotation       # (q, rot_dim)
    cf = centers.astype(jnp.float32)
    # pq_dim from rotation/codebook shapes: list_codes' trailing axis is
    # the packed byte width
    pq_dim = rotation.shape[1] // codebooks.shape[-1]
    ip_metric = metric == DistanceType.InnerProduct

    # ---- select_clusters (ivf_pq_search.cuh:133): coarse top-n_probes ----
    probes = _select_clusters(centers, rotation, queries, n_probes, metric,
                              recall_target=coarse_recall_target,
                              exact=exact_coarse)
    q_dot_c = jax.lax.dot_general(qrot, cf, (((1,), (1,)), ((), ())),
                                  precision=get_matmul_precision(),
                                  preferred_element_type=jnp.float32)

    worst = -jnp.inf if ip_metric else jnp.inf
    cap = list_codes.shape[1]
    kt = min(k, cap)
    cb_sq = jnp.sum(codebooks.astype(jnp.float32) ** 2, axis=-1)

    q_sub = _subspace_split(qrot, pq_dim)               # (q, j, l)

    def probe_step(carry, p):
        alld, alli = carry
        lists = probes[:, p]                            # (q,)
        if ip_metric:
            # score = q·x ≈ q·center + Σ_j <q_j, cb[code_j]>: the LUT is the
            # *query* subvectors against the books; q·center folds in below.
            sub = q_sub
        else:
            # d = ||resid_q - codevec||² = ||resid_q||² + Σ_j (||cb||² - 2<r_j,cb>)
            sub = _subspace_split(qrot - cf[lists], pq_dim)
        if codebook_kind == CodebookKind.PER_SUBSPACE:
            ip = jnp.einsum("qjl,jkl->qjk", sub,
                            codebooks.astype(jnp.float32),
                            precision=get_matmul_precision())
            bsq = cb_sq[None, :, :]
        else:
            books = codebooks[lists]                     # (q, book, l)
            ip = jnp.einsum("qjl,qkl->qjk", sub, books.astype(jnp.float32),
                            precision=get_matmul_precision())
            bsq = cb_sq[lists][:, None, :]
        lut = (ip if ip_metric else bsq - 2.0 * ip).astype(lut_dtype)

        codes = _unpack_codes(list_codes[lists], pq_dim,
                              pq_bits)                  # (q, cap, j) uint8
        ids = list_indices[lists]                       # (q, cap)
        # gather LUT entries by code: (q, cap, j) — the compute_similarity
        # kernel's smem-LUT lookup (ivf_pq_search.cuh:611)
        gathered = jnp.take_along_axis(
            lut[:, None, :, :],                         # (q, 1, j, book)
            codes[..., None].astype(jnp.int32),         # (q, cap, j, 1)
            axis=-1)[..., 0]
        d = jnp.sum(gathered.astype(jnp.float32), axis=-1)  # (q, cap)
        if ip_metric:
            d = d + jnp.take_along_axis(q_dot_c, lists[:, None], axis=1)
        else:
            # ||resid_q||² varies across probes — required for cross-probe
            # comparability in the merged top-k
            d = d + jnp.sum(sub * sub, axis=(1, 2))[:, None]
        d = jnp.where(ids >= 0, d, worst)
        if filter_words is not None:
            adm = _fbits.query_bits(filter_words, jnp.arange(nq), ids)
            d = jnp.where(adm > 0, d, worst)
        td, ti = select_k(d, kt, in_idx=ids, select_min=not ip_metric)
        alld = jax.lax.dynamic_update_slice(alld, td, (0, p * kt))
        alli = jax.lax.dynamic_update_slice(alli, ti, (0, p * kt))
        return (alld, alli), None

    # hierarchical select (exact; see _search_impl_recon)
    init = (jnp.full((nq, n_probes * kt), worst, jnp.float32),
            jnp.full((nq, n_probes * kt), -1, jnp.int32))
    (alld, alli), _ = jax.lax.scan(probe_step, init,
                                   jnp.arange(n_probes))
    from raft_tpu.neighbors import grouped
    return grouped.finalize_topk(
        alld, alli, nq, k, not ip_metric,
        metric in (DistanceType.L2SqrtExpanded,
                   DistanceType.L2SqrtUnexpanded), select_k)


_SCAN_MODES = ("auto", "codes", "recon", "lut", "fused")

_L2_METRICS = (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
               DistanceType.L2Unexpanded, DistanceType.L2SqrtUnexpanded)

# the plan forms that scan pair groups (grouped.build_groups) at a static
# group capacity; the other two scan in probe order
_GROUPED_FORMS = ("fused_codes", "fused_recon", "codes", "grouped_recon")


def _codes_mode_eligible(index: Index) -> bool:
    """Static preconditions of the compact-code kernel (the shape/VMEM
    gate runs later, per batch): L2-family metric, per-subspace
    codebooks, and pq_bits that divide an int32 word so no code field
    straddles words."""
    return (index.metric in _L2_METRICS
            and index.codebook_kind == CodebookKind.PER_SUBSPACE
            and index.pq_bits in (4, 8))


@dataclasses.dataclass(frozen=True)
class ScanPlan:
    """The list-scan formulation one search runs (:func:`plan_scan`).

    ``form`` is one of

    - ``fused_codes`` / ``fused_recon``: the Pallas scans that keep each
      query's top-k in the kernel (TPU only);
    - ``codes``: the non-fused Pallas compact-code scan (TPU only);
    - ``grouped_recon``: the grouped recon scan, through the non-fused
      Pallas kernel where ``use_pallas`` and its gate admit the shape,
      else through its XLA twin;
    - ``probe_recon``: the probe-order recon scan;
    - ``lut``: the probe-order LUT scan over the packed codes.

    ``kt`` is the per-(query, probe) keep-set.  The grouped forms
    dispatch at the static group capacity ``n_groups``; ``exact`` False
    (a calibrated capacity) arms the in-graph overflow count, and an
    overflowing batch re-dispatches at ``worst_groups``.  ``lowered``
    marks a sharded search whose requested mode has no formulation on
    its placement (shard status ``SHARD_OK_FALLBACK``).  ``reason`` is
    the fused fallback's reason code, '' when none was noted."""

    form: str
    kt: int = 0
    n_groups: int = 0
    worst_groups: int = 0
    exact: bool = True
    use_pallas: bool = False
    lowered: bool = False
    reason: str = ""


def _note_fused_fallback(reason: str) -> None:
    """Count a search that asked for a fused scan and runs another
    formulation.  Reason codes: "dtype", "k-too-large" and
    "bucket-too-wide" from the kernels' reject helpers; "backend" for
    off-TPU or non-f32-exact ids; "mode" when the resolved mode has no
    fused variant."""
    if obs.enabled():
        reg = obs.registry()
        reg.counter("ivf_pq.search.fused_fallback").inc()
        reg.counter(f"ivf_pq.search.fused_fallback.reason.{reason}").inc()
    from raft_tpu.observability import flight as _flight
    from raft_tpu.observability import trace as _rtrace
    rec = _rtrace.current()
    _flight.record_event("ivf_pq.fused_fallback", reason=reason,
                         trace_id=rec.trace_id if rec else None)


def plan_scan(params, *, placement: str, metric: int, cap: int, rot: int,
              lists: int, pq_bits: int, nq: int, k: int, n_probes: int,
              on_tpu: bool, pq_dim: int = 0, per_subspace: bool = False,
              has_recon: bool = True, code_lanes: bool = False,
              ids_exact: bool = False, group_est: float = 0.0,
              traced: bool = False) -> ScanPlan:
    """Decide the list-scan formulation of one IVF-PQ search.

    The one place that reads ``params.scan_mode`` and
    ``params.per_probe_topk``, asks the Pallas kernels' gates, sizes the
    group capacity and notes a fused fallback
    (``ivf_pq.search.fused_fallback`` and its ``.reason.<code>``
    children, flight event ``ivf_pq.fused_fallback``).  Host-static: the
    inputs are facts the caller observes without a device read, so the
    same facts give the same plan.

    ``placement`` is ``"single"`` (one :class:`Index`), ``"by_list"``
    (a routed ``distributed.ann.RoutedIndex``: shards hold the recon
    cache and, when ``code_lanes``, the lane-major codes, but no packed
    codes) or ``"by_row"`` (a stacked ``distributed.ann.DistributedIndex``:
    recon cache and packed codes, no code lanes).  ``lists`` is the
    list count one scan addresses (a routed shard's slots);
    ``pq_bits`` 0 marks a stacked index without PQ metadata;
    ``has_recon`` whether a single index carries the recon cache (one
    is materialized when "recon" is asked of an index without it);
    ``ids_exact`` whether every candidate id is f32-exact, which the
    Pallas kernels need; ``traced`` a search under an outer trace,
    which takes a probe-order form (the grouped dispatch's overflow gate
    is a host read).

    docs/api.md ("Scan plans") tabulates the outcome.
    """
    from raft_tpu.neighbors import grouped
    from raft_tpu.ops import pq_code_scan_pallas as pcs
    from raft_tpu.ops import pq_group_scan_pallas as pqp

    mode = getattr(params, "scan_mode", "auto") or "auto"
    expects(mode in _SCAN_MODES,
            f"ivf_pq.search: unknown scan_mode {mode!r} "
            f"(one of {_SCAN_MODES})")
    kt = min(int(getattr(params, "per_probe_topk", 0) or 0) or k, cap)
    l2 = metric in _L2_METRICS
    codes_ok = l2 and per_subspace and pq_bits in (4, 8)
    pallas = on_tpu and ids_exact

    def plan(form, reason="", lowered=False):
        if reason:
            _note_fused_fallback(reason)
        if form not in _GROUPED_FORMS:
            return ScanPlan(form, kt=kt, lowered=lowered, reason=reason)
        # a stacked index's jitted dispatch has no overflow path, so it
        # always takes the exact-safe bound
        est = 0.0 if placement == "by_row" else group_est
        n_groups, exact = grouped.group_capacity(nq, n_probes, lists,
                                                 est=est)
        worst = (n_groups if exact
                 else grouped.group_capacity(nq, n_probes, lists)[0])
        return ScanPlan(form, kt=kt, n_groups=n_groups, worst_groups=worst,
                        exact=exact, use_pallas=pallas, reason=reason)

    def recon_miss():
        if not pallas:
            return "backend"
        return (pqp.fused_reject_reason(l2, cap, rot, kt, k, nq)
                or "bucket-too-wide")

    def codes_miss():
        if not pallas:
            return "backend"
        return (pcs.fused_codes_reject_reason(l2, per_subspace, cap, rot,
                                              kt, k, nq, pq_dim, pq_bits)
                or "bucket-too-wide")

    fused_recon_ok = pallas and pqp.supported_fused(l2, cap, rot, kt, k, nq)
    fused_codes_ok = (pallas and codes_ok
                      and (placement == "single" or code_lanes)
                      and pcs.supported_fused_codes(
                          l2, per_subspace, cap, rot, kt, k, nq, pq_dim,
                          pq_bits))

    if placement == "single":
        # "auto" and "fused" resolve to the backing mode that owns the
        # caches and the fallback path: "fused" prefers the codes, "auto"
        # the recon cache the index already carries
        want_fused = mode in ("auto", "fused")
        if mode == "fused":
            mode = ("codes" if codes_ok else "recon" if has_recon
                    else "lut")
        elif mode == "auto":
            mode = ("recon" if has_recon else "codes" if codes_ok
                    else "lut")
        elif mode == "codes" and not l2:
            mode = "recon" if has_recon else "lut"
        if traced:
            # the LUT scan computes the codes kernel's distances, so a
            # traced "codes" search answers the same
            return plan("probe_recon" if mode == "recon" and has_recon
                        else "lut")
        if mode == "lut":
            return plan("lut", "mode" if want_fused else "")
        if mode == "codes":
            if not codes_ok:
                return plan("lut")
            if not (pallas and pcs.supported_codes(
                    l2, per_subspace, cap, rot, kt, nq, pq_dim, pq_bits)):
                # no XLA twin of the codes kernel is worth running (it
                # would decode every row): the LUT scan computes the
                # same quantized distance
                return plan("lut", codes_miss() if want_fused else "")
            if not want_fused:
                return plan("codes")
            return (plan("fused_codes") if fused_codes_ok
                    else plan("codes", codes_miss()))
        if want_fused and fused_recon_ok:
            return plan("fused_recon")
        return plan("grouped_recon", recon_miss() if want_fused else "")

    if mode in ("lut", "codes"):
        # routed shards hold no packed codes; a stacked index holds them
        # but no code lanes, so it answers both with the LUT scan (a
        # formulation downgrade for "codes" on TPU)
        if placement == "by_row" and pq_bits:
            return plan("lut", lowered=mode == "codes" and on_tpu)
        return plan("probe_recon", lowered=True)
    if not (mode == "fused" or (mode == "auto" and on_tpu)):
        return plan("probe_recon")
    if fused_codes_ok:
        return plan("fused_codes")
    if fused_recon_ok:
        return plan("fused_recon")
    return plan("grouped_recon", recon_miss() if mode == "fused" else "")


@auto_convert_output
def search(res, params: SearchParams, index: Index, queries, k: int, *,
           filter=None) -> Tuple[jax.Array, jax.Array]:
    """Search (reference: ivf_pq.cuh:342).  Returns (distances, indices).

    ``params.scan_mode`` picks the list-scan formulation (see
    :class:`SearchParams` and :func:`plan_scan`); "codes" and the fused
    kernels silently fall back to the LUT / XLA formulations off-TPU or
    for unsupported shapes, so the same call works on every backend.

    ``filter`` (a :class:`~raft_tpu.filters.SampleFilter` or an
    (nq, n_rows) bool mask — see docs/api.md, "Filtered search &
    tenancy") restricts each query's candidate set by source id: a
    rejected row folds to the worst-distance sentinel *before* every
    top-k, on every scan mode, so filtered results are bit-identical to
    a post-hoc filtered exact scan at full probe.  Rejected slots
    surface as (+inf/-inf, -1) like tombstones.  Filters are data, not
    shape — varying filters re-enter the same compiled executable.

    Queries pass through the boundary validator (see
    :mod:`raft_tpu.integrity.boundary`): under policy ``mask``,
    non-finite query rows return id -1 / worst distance instead of
    poisoning the batch.

    .. note:: the first search may mutate ``index`` in place, lazily
       attaching derived caches (``list_recon``/``list_recon_sq``, the
       codes-lane cache of the codes scans, the id-exactness cache); the
       derived caches are pytree leaves, so the registered pytree
       structure can change after the first search (one retrace for
       jitted closures over the index).
    """
    queries = ensure_array(queries, "queries")
    queries, ok_rows = _boundary.check_matrix(
        queries, "queries", site="ivf_pq.search", dim=index.dim)
    # legacy shape guard: still fires when the validator policy is "off"
    expects(queries.ndim == 2 and queries.shape[1] == index.dim,
            "ivf_pq.search: query dim mismatch")
    dist, ids = _search_checked(res, params, index, queries, k,
                                filter=filter)
    if ok_rows is not None:
        dist, ids = _boundary.mask_search_outputs(
            dist, ids, ok_rows,
            select_min=index.metric != DistanceType.InnerProduct)
    return dist, ids


def _search_checked(res, params: SearchParams, index: Index, queries,
                    k: int, filter=None) -> Tuple[jax.Array, jax.Array]:
    from raft_tpu.neighbors import grouped

    with named_range("ivf_pq::search"):
        fw = _fbits.query_filter_words(filter, queries.shape[0],
                                       "ivf_pq.search")
        if fw is not None and obs.enabled():
            obs.registry().counter("ivf_pq.search.filtered").inc()
        n_probes = min(params.n_probes, index.n_lists)
        coarse_rt = getattr(params, "coarse_recall_target", 0.95)
        exact_coarse = getattr(params, "exact_coarse", False)
        nq = queries.shape[0]
        on_tpu = _platform.on_tpu()
        # queries or the Index pytree traced by an outer jit/vmap
        traced = (isinstance(queries, jax.core.Tracer)
                  or isinstance(index.centers, jax.core.Tracer))
        plan = plan_scan(
            params, placement="single", metric=index.metric,
            cap=index.capacity, rot=index.rot_dim, lists=index.n_lists,
            pq_bits=index.pq_bits, nq=nq, k=k, n_probes=n_probes,
            on_tpu=on_tpu, pq_dim=index.pq_dim,
            per_subspace=index.codebook_kind == CodebookKind.PER_SUBSPACE,
            has_recon=index.list_recon is not None,
            ids_exact=(on_tpu and not traced
                       and grouped.ids_f32_exact(index, index.list_indices)),
            group_est=index.group_est, traced=traced)
        kt = plan.kt

        def lut_scan():
            return _search_impl(index.centers, index.codebooks,
                                index.list_codes, index.list_indices,
                                index.rotation, queries, k, n_probes,
                                index.metric, index.codebook_kind,
                                jnp.dtype(params.lut_dtype).name,
                                pq_bits=index.pq_bits,
                                coarse_recall_target=coarse_rt,
                                exact_coarse=exact_coarse,
                                filter_words=fw)

        if plan.form == "probe_recon":
            return _search_impl_recon(
                index.centers, index.list_recon, index.list_indices,
                index.rotation, queries, k, n_probes, index.metric,
                list_recon_sq=index.list_recon_sq, filter_words=fw)
        if plan.form == "lut":
            if traced:
                return lut_scan()
            with obs.stage("ivf_pq.search.lut") as st:
                out = lut_scan()
                st.fence(out)
            return out

        # ---- lazy derived caches (one-time per index) -------------------
        if plan.form in ("fused_codes", "codes"):
            if index.list_code_lanes is None or index.list_code_rsq is None:
                # the VMEM-LUT analogue of the reference's per-probe smem
                # LUT build: here the scan tables are built once per index
                with obs.stage("ivf_pq.search.lut_build") as st:
                    index = _with_code_lanes(index)
                    st.fence(index.list_code_lanes, index.list_code_rsq)
        else:
            if index.list_recon is None:
                # One-time materialization of the (n_lists, cap, rot_dim)
                # bf16 cache on an index built without it; the cache stays
                # attached for subsequent searches.
                warnings.warn(
                    "ivf_pq.search: scan_mode='recon' on an index built "
                    "without a reconstruction cache — materializing the "
                    "(n_lists, cap, rot_dim) bf16 cache now (and keeping "
                    "it on the index). Build with "
                    "cache_reconstructions=True or pick another scan_mode "
                    "to avoid this.")
                index = _with_recon(res, index)
            if index.list_recon_sq is None:
                index.list_recon_sq = _recon_sq(index.list_recon)

        with obs.stage("ivf_pq.search.coarse") as st:
            probes = _select_clusters(index.centers, index.rotation,
                                      queries, n_probes, index.metric,
                                      recall_target=coarse_rt,
                                      exact=exact_coarse)
            st.fence(probes)
        # static group capacity (round 10): uncalibrated indexes dispatch
        # at the exact-safe worst-case bound — the shape depends only on
        # (nq, n_probes, n_lists), so NO host sync of a group count
        # exists anywhere on this path and one warmed executable serves
        # every batch at the shape.  A calibrated index (group_est > 0)
        # dispatches at the tightened capacity and arms the in-graph
        # overflow count, enqueued BEFORE the scan so the read overlaps
        # the scan's execution; only the rare batch whose probe skew
        # exceeds the calibrated bound pays a second pass.
        needed_dev = (None if plan.exact
                      else grouped.num_groups(probes, index.n_lists))
        fused = plan.form in ("fused_codes", "fused_recon")

        def run_grouped(stage_label, dispatch):
            with obs.stage(stage_label) as st:
                out = dispatch(plan.n_groups)
                sizes = [plan.n_groups]
                overflow = False
                if needed_dev is not None:
                    with _tracing.annotation("ivf_pq.search.group_sync"):
                        overflow = int(needed_dev) > plan.n_groups
                if overflow:
                    # calibrated capacity exceeded: tick the overflow
                    # counter and re-dispatch at the worst-case bound,
                    # where no pair can drop — results stay exact
                    if obs.enabled():
                        obs.registry().counter(
                            "ivf_pq.search.group_overflow").inc()
                    out = dispatch(plan.worst_groups)
                    sizes.append(plan.worst_groups)
                st.fence(out)
            if fused and obs.enabled():
                _note_skipped_groups(sizes, needed_dev if needed_dev
                                     is not None else grouped.num_groups(
                                         probes, index.n_lists))
            return out

        if plan.form == "fused_codes":
            # one stage where code_scan + extraction used to be two: the
            # kernel output IS the final top-k
            return run_grouped(
                "ivf_pq.search.fused_scan",
                lambda ng: _search_impl_fused_codes_grouped(
                    index.centers, index.codebooks, index.list_code_lanes,
                    index.list_code_rsq, index.list_indices, index.rotation,
                    queries, probes, k, kt, index.metric, ng, index.pq_bits,
                    filter_words=fw))
        if plan.form == "codes":
            return run_grouped(
                "ivf_pq.search.code_scan",
                lambda ng: _search_impl_codes_grouped(
                    index.centers, index.codebooks, index.list_code_lanes,
                    index.list_code_rsq, index.list_indices, index.rotation,
                    queries, probes, k, kt, index.metric, ng,
                    index.pq_bits, filter_words=fw))
        if plan.form == "fused_recon":
            return run_grouped(
                "ivf_pq.search.fused_scan",
                lambda ng: _search_impl_fused_recon_grouped(
                    index.centers, index.list_recon, index.list_recon_sq,
                    index.list_indices, index.rotation, queries, probes, k,
                    kt, index.metric, ng, filter_words=fw))

        cap, rot, G = index.capacity, index.rot_dim, grouped.GROUP

        def dispatch(ng):
            block = grouped.block_size(
                ng,
                G * cap * 8,      # fp32 distances + broadcast ids
                cap * rot * 2,    # bf16 recon slice
                G * rot * 4)      # query gather
            return _search_impl_recon_grouped(
                index.centers, index.list_recon, index.list_recon_sq,
                index.list_indices, index.rotation, queries, probes, k,
                index.metric, ng, block, use_pallas=plan.use_pallas, kt=kt,
                filter_words=fw)

        return run_grouped("ivf_pq.search.scan", dispatch)


def _note_skipped_groups(sizes, needed) -> None:
    """Tick the fused scan's grid counters for dispatches at the group
    counts ``sizes``, given the batch's ``needed`` groups (a device
    scalar, read here: collection is on, so the stage fences already
    synced).  The kernel skips every step past the live groups, of which
    a dispatch at ``n`` holds ``min(needed, n)``."""
    needed = int(needed)
    reg = obs.registry()
    for n in sizes:
        reg.counter("ivf_pq.search.groups_dispatched").inc(n)
        reg.counter("ivf_pq.search.groups_skipped").inc(
            n - min(needed, n))


def calibrate_group_capacity(res, index: Index, queries,
                             n_probes: int) -> float:
    """Measure the grouped-scan capacity estimate on a representative
    query batch and store it on the index (round 10).

    The grouped dispatch needs a static group count; without calibration
    it uses the exact-safe worst case ``ceil(P/G) + min(n_lists, P)``
    (see :func:`raft_tpu.neighbors.grouped.group_capacity`).  Real probe
    distributions touch far fewer lists than the bound assumes, so this
    measures the touched-list fraction under the index's own coarse
    router and records it as ``index.group_est`` — searches then
    dispatch at the tightened capacity with the in-graph overflow
    fallback armed.  Repeated calls ratchet the estimate upward (max),
    so calibrating on several batches converges to the widest observed
    distribution.  The estimate rides the serialization envelope (v4);
    loading a pre-v4 stream leaves the index uncalibrated, which is
    always correct (worst-bound dispatch).

    Returns the stored estimate (a fraction of ``min(n_lists, P)``).
    """
    from raft_tpu.neighbors import grouped

    queries = ensure_array(queries, "queries")
    expects(queries.ndim == 2 and queries.shape[1] == index.dim,
            "ivf_pq.calibrate_group_capacity: queries must be "
            f"(n, {index.dim})")
    n_probes = min(int(n_probes), index.n_lists)
    expects(n_probes >= 1,
            "ivf_pq.calibrate_group_capacity: n_probes must be >= 1")
    probes = _select_clusters(index.centers, index.rotation,
                              jnp.asarray(queries), n_probes, index.metric)
    P = int(queries.shape[0]) * n_probes
    touched = int(grouped.touched_lists(probes, index.n_lists))
    est = touched / max(min(index.n_lists, P), 1)
    index.group_est = max(float(index.group_est), est)
    return index.group_est


# ---------------------------------------------------------------------------
# serialization (reference: ivf_pq_serialize.cuh:38 kSerializationVersion)
# ---------------------------------------------------------------------------

# v2: list_codes are bit-packed; pq_dim is stored explicitly
# v3: trailing recall-canary block (nested envelope, may be absent)
# v4: calibrated group-capacity estimate (group_est float64 scalar)
#     between the fixed header and the mdspans
_SERIALIZATION_VERSION = 4
_MIN_READ_VERSION = 2


def serialize(res, stream: BinaryIO, index: Index) -> None:
    """CRC32-enveloped versioned dump (reference: ivf_pq_serialize.cuh)."""
    with ser.enveloped_writer(stream) as body:
        ser.serialize_scalar(res, body, np.int32(_SERIALIZATION_VERSION))
        ser.serialize_scalar(res, body, np.int32(index.metric))
        ser.serialize_scalar(res, body, np.int32(index.codebook_kind))
        ser.serialize_scalar(res, body, np.int32(index.pq_bits))
        ser.serialize_scalar(res, body, np.int32(index.pq_dim))
        ser.serialize_scalar(res, body, np.float64(index.group_est))
        for arr in (index.centers, index.codebooks, index.list_codes,
                    index.list_indices, index.list_sizes, index.rotation):
            ser.serialize_mdspan(res, body, arr)
        _canary.to_stream(res, body, index.canaries)


def deserialize(res, stream: BinaryIO, *,
                cache_reconstructions: bool = True) -> Index:
    """Truncated / bit-flipped streams raise
    :class:`~raft_tpu.core.serialize.CorruptIndexError`."""
    body = ser.open_envelope(stream)
    version = int(ser.deserialize_scalar(res, body))
    if not _MIN_READ_VERSION <= version <= _SERIALIZATION_VERSION:
        raise ValueError(
            f"ivf_pq serialization version mismatch: got {version}, "
            f"expected {_MIN_READ_VERSION}..{_SERIALIZATION_VERSION}")
    metric = int(ser.deserialize_scalar(res, body))
    kind = int(ser.deserialize_scalar(res, body))
    pq_bits = int(ser.deserialize_scalar(res, body))
    pq_dim = int(ser.deserialize_scalar(res, body))
    # back-compat read window: pre-v4 streams carry no capacity estimate
    # — the index loads uncalibrated (worst-bound dispatch, always safe)
    group_est = (float(ser.deserialize_scalar(res, body))
                 if version >= 4 else 0.0)
    arrays = [jnp.asarray(ser.deserialize_mdspan(res, body))
              for _ in range(6)]
    index = Index(*arrays, metric=metric, codebook_kind=kind,
                  pq_bits=pq_bits, pq_dim_=pq_dim, group_est=group_est)
    if version >= 3:
        index.canaries = _canary.from_stream(res, body)
    # the reconstruction cache is derived state: re-decode from codes —
    # unless the caller opted out (indexes too large for the cache, the
    # same regime as IndexParams.cache_reconstructions=False)
    if cache_reconstructions:
        index = _with_recon(res, index)
    return index


def save(res, filename: str, index: Index, *, retry_policy=None,
         deadline=None) -> None:
    """Atomic file dump (tmp + fsync + rename) with transient-IO retry."""
    from raft_tpu.resilience import save_index
    save_index("ivf_pq.save", lambda b: serialize(res, b, index),
               filename, retry_policy, deadline)


def load(res, filename: str, *, cache_reconstructions: bool = True,
         retry_policy=None, deadline=None) -> Index:
    """File-load overload; transient IO retries, corruption fails fast.

    Indexes carrying recall canaries are health-checked before being
    returned: a loaded index whose recall dropped below the stored floor
    raises :class:`~raft_tpu.integrity.IntegrityError` here, not in
    production traffic."""
    from raft_tpu.resilience import load_index
    index = load_index(
        "ivf_pq.load",
        lambda b: deserialize(
            res, b, cache_reconstructions=cache_reconstructions),
        filename, retry_policy, deadline)
    _canary.auto_check(res, index, site="load")
    return index
