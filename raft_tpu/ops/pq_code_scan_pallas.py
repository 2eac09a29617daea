"""Pallas TPU kernels: grouped IVF-PQ scans over COMPACT codes.

The recon-cache kernel (:mod:`raft_tpu.ops.pq_group_scan_pallas`) streams
2 bytes/dim/row of bf16 reconstructions from HBM.  The reference instead
scans the bit-packed PQ codes against a shared-memory LUT
(``compute_similarity_kernel``, ivf_pq_search.cuh:611) — ~pq_dim
bytes/row.  This module is the TPU analogue, two kernels:

- **code scan** (:func:`grouped_code_scan`): each program DMAs its list's
  *packed codes* — an (Wi, cap) int32 block with candidates on the LANE
  axis (``Wi = ceil(pq_dim*pq_bits/32)`` words; the naive (cap, Wi)
  layout lane-pads Wi to 128 and forfeits the traffic win) — and the
  full (pq_dim, pq_len, book) codebook table, which is a few hundred KB
  and VMEM-resident for the whole grid.  Mosaic has no row gather, so
  per subspace the LUT lookup becomes a **transposed one-hot MXU
  contraction**: ``onehotT (book, cap) = (iota == code_j)``, then
  ``reconT_j = cbT_j (pq_len, book) @ onehotT`` decodes the whole
  subspace column block in one matmul.  Decoding to ``reconT (rot, cap)``
  and running ONE shared distance GEMM costs ~book/pq_len times fewer
  MACs than contracting a per-query LUT against the one-hots
  (pq_dim·G·book·cap vs pq_dim·pq_len·book·cap + G·rot·cap).  The bf16
  codebook cast makes the decoded values bit-identical to the bf16 recon
  cache, so distances match the recon kernel's.  It reuses the recon
  kernel's one-hot query gather and top-kt extraction.
- **fused code scan** (:func:`grouped_code_scan_fused`): the same decode
  and distance block feeding the recon scan's row-addressed per-query
  top-k merge (``pq_group_scan_pallas._fused_merge``).

Codes must not straddle int32 words for the in-register unpack to be one
shift+mask: gated to ``32 % pq_bits == 0`` → pq_bits ∈ {4, 8} (the
reference's default and its half-width option).  Other widths fall back
to the recon / XLA LUT paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.neighbors.grouped import GROUP
from raft_tpu.ops import vmem_budget as vb
from raft_tpu.ops.pq_group_scan_pallas import (_KT_MAX, _KT_UNROLL,
                                               _copy_slot_rows,
                                               _extract_topk,
                                               _fused_init,
                                               _fused_merge,
                                               _gather_queries,
                                               _scratch_shapes,
                                               _unpack_admission,
                                               fused_scan_call,
                                               fused_stream_bytes,
                                               query_table, row_table)
from raft_tpu.ops.pq_group_scan_pallas import _ACC_WORST  # noqa: F401 (re-export)

_VMEM_BUDGET = 10 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def code_lane_words(pq_dim: int, pq_bits: int) -> int:
    """int32 words per row in the lane-major packed-code layout."""
    return -(-(-(-pq_dim * pq_bits // 8)) // 4)


@jax.jit
def pack_code_lanes(list_codes: jax.Array) -> jax.Array:
    """(n_lists, cap, W) uint8 packed codes -> (n_lists, Wi, cap) int32.

    Byte k of a row lands in word ``k // 4`` at bit ``8*(k % 4)`` —
    LSB-first, so the bit stream is unchanged and subspace j still
    starts at bit ``j*pq_bits``.  Candidates move to the LANE axis: the
    (cap, Wi) orientation would lane-pad Wi (16 words at bench shape) to
    128 — an 8x HBM blowup that would erase the codes path's entire
    traffic advantage.
    """
    L, cap, W = list_codes.shape
    Wi = -(-W // 4)
    b = jnp.pad(list_codes, ((0, 0), (0, 0), (0, Wi * 4 - W)))
    b = b.astype(jnp.int32).reshape(L, cap, Wi, 4)
    shifts = (8 * jnp.arange(4, dtype=jnp.int32))[None, None, None, :]
    words = jnp.sum(jax.lax.shift_left(b, shifts), axis=-1)
    return jnp.transpose(words, (0, 2, 1))


def pack_row_lanes(codes: jax.Array) -> jax.Array:
    """(n, W) uint8 packed code rows -> (n, Wi) int32 lane words — the
    row-wise twin of :func:`pack_code_lanes`, used by the extend fast
    path to scatter-append into the lane-major cache without re-packing
    the whole index."""
    n, W = codes.shape
    Wi = -(-W // 4)
    b = jnp.pad(codes, ((0, 0), (0, Wi * 4 - W)))
    b = b.astype(jnp.int32).reshape(n, Wi, 4)
    shifts = (8 * jnp.arange(4, dtype=jnp.int32))[None, None, :]
    return jnp.sum(jax.lax.shift_left(b, shifts), axis=-1)


def _decode_reconT(codes_ref, cb_ref, pq_dim, pq_bits, rot_pad, cap):
    """In-register decode of one list's codes to (rot_pad, cap) bf16 —
    the transposed recon block.  Python-unrolled over subspaces: the
    word/shift offsets are static, and each step is one VPU shift+mask
    plus one (pq_len, book) x (book, cap) MXU matmul.  The bf16 cast of
    the codebook reproduces the bf16 recon cache bit-for-bit."""
    mask = (1 << pq_bits) - 1
    book = cb_ref.shape[2]
    pq_len = cb_ref.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (book, cap), 0)
    parts = []
    for j in range(pq_dim):
        bitpos = j * pq_bits
        w, sh = bitpos // 32, bitpos % 32
        word = codes_ref[0, w:w + 1, :]                  # (1, cap) int32
        # arithmetic >> then & mask == logical shift (sh + pq_bits <= 32)
        cj = (word >> sh) & mask if sh else word & mask
        onehotT = (rows == cj).astype(jnp.bfloat16)      # (book, cap)
        cbT_j = cb_ref[j].astype(jnp.bfloat16)           # (pq_len, book)
        rT = jax.lax.dot_general(cbT_j, onehotT,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        parts.append(rT.astype(jnp.bfloat16))            # (pq_len, cap)
    rot = pq_dim * pq_len
    if rot_pad > rot:
        parts.append(jnp.zeros((rot_pad - rot, cap), jnp.bfloat16))
    return jnp.concatenate(parts, axis=0)                # (rot_pad, cap)


def _kernel_codes(gl_ref, slot_ref, qrot_ref, cf_ref, codes_ref, cb_ref,
                  rsq_ref, ids_ref, *rest, kt, n_probes, P, pq_dim,
                  pq_bits, has_adm=False):
    adm_ref, rest = (rest[0], rest[1:]) if has_adm else (None, rest)
    vals_ref, ids_out_ref, vscratch, pscratch = rest
    qv = _gather_queries(slot_ref, qrot_ref, n_probes, P)
    sub = qv - cf_ref[0, 0][None, :]                     # (G, rot_pad) f32
    sub_sq = jnp.sum(sub * sub, axis=1)                  # (G,)
    cap = codes_ref.shape[2]
    reconT = _decode_reconT(codes_ref, cb_ref, pq_dim, pq_bits,
                            qrot_ref.shape[2], cap)      # (rot_pad, cap)
    ip = jax.lax.dot_general(sub.astype(jnp.bfloat16), reconT,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    d = sub_sq[:, None] + rsq_ref[0, 0][None, :] - 2.0 * ip
    d = jnp.maximum(d, 0.0)
    adm = _unpack_admission(adm_ref, cap) if has_adm else None
    _extract_topk(d, ids_ref[0, 0], vals_ref, ids_out_ref, vscratch,
                  pscratch, kt, adm=adm)


def _kernel_codes_fused(gl_ref, nlive_ref, qid_ref, q_hbm, cf_ref,
                        codes_ref, cb_ref, rsq_ref, ids_ref, *rest, kt, k,
                        pq_dim, pq_bits, n_groups, has_adm=False):
    """Fused compact-code scan: the ``_kernel_codes`` decode + distance
    block feeding the row-addressed per-query merge of
    ``pq_group_scan_pallas._fused_merge`` instead of per-pair output
    rows — candidates never reach HBM; the final query-major answers
    are copied out once, on the last grid step.  Steps past the batch's
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
        sub = qrows[...] - cf_ref[0, 0][None, :]         # (G, rot_pad) f32
        sub_sq = jnp.sum(sub * sub, axis=1)              # (G,)
        cap = codes_ref.shape[2]
        reconT = _decode_reconT(codes_ref, cb_ref, pq_dim, pq_bits,
                                qrows.shape[1], cap)     # (rot_pad, cap)
        ip = jax.lax.dot_general(sub.astype(jnp.bfloat16), reconT,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        d = sub_sq[:, None] + rsq_ref[0, 0][None, :] - 2.0 * ip
        d = jnp.maximum(d, 0.0)
        adm = _unpack_admission(adm_ref, cap) if has_adm else None
        _fused_merge(qid_ref, d, ids_ref[0, 0], kt, k, acc_v, acc_i,
                     rows_v, rows_i, mer_v, mer_i, adm=adm)

    @pl.when(g == n_groups - 1)
    def _flush():
        pltpu.sync_copy(acc_v, vals_hbm)
        pltpu.sync_copy(acc_i, ids_hbm)


@functools.partial(jax.jit, static_argnames=("kt", "k", "n_probes",
                                             "pq_bits", "interpret"))
def grouped_code_scan_fused(group_list, slot_pairs, qrot, centers_f32,
                            codes_lanes, codebooks, rsq, list_indices, kt,
                            k, n_probes, pq_bits, interpret=False,
                            adm_words=None):
    """Fused compact-code scan with IN-KERNEL per-query top-k.

    Inputs as :func:`grouped_code_scan`; output contract as
    ``pq_group_scan_pallas.grouped_l2_scan_fused`` — the batch's final
    ``(vals (nq_pad, k) f32, ids (nq_pad, k) int32)``, ascending per
    row, exhausted ranks at the finite ``_ACC_WORST`` sentinel.
    ``adm_words`` (n_groups, GROUP, ceil(cap/32)) int32 streams packed
    per-(slot, candidate) admission bits (filtered search).
    """
    nq, rot = qrot.shape
    _, _, cap = codes_lanes.shape
    pq_dim, book, pq_len = codebooks.shape
    Wi = codes_lanes.shape[1]
    rot_pad = _round_up(rot, 128)
    stream_specs = [
        pl.BlockSpec((1, 1, rot_pad), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, Wi, cap), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((pq_dim, pq_len, book), lambda g, gl: (0, 0, 0)),
        pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
    ]
    stream_inputs = [_pad_lanes(centers_f32, rot_pad)[:, None, :],
                     codes_lanes,
                     jnp.swapaxes(codebooks.astype(jnp.float32), 1, 2),
                     rsq[:, None, :], list_indices[:, None, :]]
    kernel = functools.partial(_kernel_codes_fused, kt=kt, k=k,
                               pq_dim=pq_dim, pq_bits=pq_bits,
                               n_groups=group_list.shape[0],
                               has_adm=adm_words is not None)
    return fused_scan_call(
        kernel, group_list=group_list, slot_pairs=slot_pairs,
        n_probes=n_probes, P=nq * n_probes,
        q_table=row_table(qrot, vb.nq_padded(nq), rot_pad),
        stream_specs=stream_specs, stream_inputs=stream_inputs,
        adm_words=adm_words, k=k,
        total_bytes=_fused_codes_bytes(cap, rot, kt, k, nq, pq_dim,
                                       pq_bits),
        interpret=interpret)


def _pad_lanes(x, width):
    """Zero-pad the trailing (lane) axis of a 2-D array to ``width``."""
    if x.shape[-1] == width:
        return x.astype(jnp.float32)
    return jnp.pad(x.astype(jnp.float32),
                   ((0, 0), (0, width - x.shape[-1])))


@functools.partial(jax.jit, static_argnames=("kt", "n_probes", "pq_bits",
                                             "interpret"))
def grouped_code_scan(group_list, slot_pairs, qrot, centers_f32,
                      codes_lanes, codebooks, rsq, list_indices, kt,
                      n_probes, pq_bits, interpret=False, adm_words=None):
    """Fused grouped scan over packed PQ codes + local top-kt.

    Same contract as ``pq_group_scan_pallas.grouped_l2_scan`` with the
    bf16 recon cache replaced by ``codes_lanes`` (n_lists, Wi, cap) int32
    (:func:`pack_code_lanes`) + ``codebooks`` (pq_dim, book, pq_len);
    ``rsq`` (n_lists, cap) f32 row norms of the bf16 reconstructions.
    rot_dim need not be 128-aligned: queries/centers are lane-padded here
    and the decoded block pads with zero rows (the deep conf's rot=96).
    """
    n_groups = group_list.shape[0]
    nq, rot = qrot.shape
    _, _, cap = codes_lanes.shape
    pq_dim, book, pq_len = codebooks.shape
    Wi = codes_lanes.shape[1]
    P = nq * n_probes
    rot_pad = _round_up(rot, 128)

    nq_pad = _round_up(nq + 1, 128)
    qrot_pad = query_table(qrot, nq_pad, rot_pad)
    cf_pad = _pad_lanes(centers_f32, rot_pad)
    # (pq_dim, pq_len, book): books on lanes — the (.., book, pq_len)
    # orientation would lane-pad pq_len (2 at bench shape) to 128
    cbT = jnp.swapaxes(codebooks.astype(jnp.float32), 1, 2)

    has_adm = adm_words is not None
    in_specs = [
        pl.BlockSpec((1, 1, GROUP), lambda g, gl: (g, 0, 0)),
        pl.BlockSpec((3, nq_pad, rot_pad), lambda g, gl: (0, 0, 0)),
        pl.BlockSpec((1, 1, rot_pad), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, Wi, cap), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((pq_dim, pq_len, book), lambda g, gl: (0, 0, 0)),
        pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
        pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
    ]
    inputs = [group_list, slot_pairs[:, None, :], qrot_pad,
              cf_pad[:, None, :], codes_lanes, cbT, rsq[:, None, :],
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
        functools.partial(_kernel_codes, kt=kt, n_probes=n_probes, P=P,
                          pq_dim=pq_dim, pq_bits=pq_bits, has_adm=has_adm),
        out_shape=[
            jax.ShapeDtypeStruct((n_groups, GROUP, kt), jnp.float32),
            jax.ShapeDtypeStruct((n_groups, GROUP, kt), jnp.int32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
    )(*inputs)
    return vals, gids


def _decode_bytes(cap: int, rot: int, pq_dim: int, pq_bits: int) -> int:
    """VMEM of the code scans' decode: the packed-code block, the
    VMEM-resident codebook table, the decoded reconT block with its
    concat temporary and one subspace's one-hot transient."""
    book = 1 << pq_bits
    pq_len = max(rot // pq_dim, 1) if pq_dim else 1
    rot_pad = _round_up(rot, 128)
    Wi = code_lane_words(pq_dim, pq_bits)
    return (_round_up(Wi, 8) * cap * 4           # packed-code block
            + pq_dim * _round_up(pq_len, 8) * _round_up(book, 128) * 4
            + 2 * rot_pad * cap * 2              # reconT + concat temp
            + _round_up(book, 8) * cap * 2)      # one-hot transient


def supported_codes(metric_is_l2: bool, per_subspace: bool, cap: int,
                    rot: int, kt: int, nq: int, pq_dim: int,
                    pq_bits: int) -> bool:
    """Shapes/configs the code-scan kernel handles.

    pq_bits must divide 32 (in-register unpack is one static shift+mask
    per subspace), codebooks must be PER_SUBSPACE (a per-cluster table
    would re-DMA book*rot per group), and the summed VMEM footprint —
    query table + one-hot, packed-code block, codebook table, decoded
    reconT block, distances + extraction temps — stays under budget.
    Candidate-id f32-exactness is data-dependent and checked by the
    caller (grouped.ids_f32_exact), as for the recon kernel."""
    if not (metric_is_l2 and per_subspace and pq_bits in (4, 8)):
        return False
    if not (pq_dim and rot % pq_dim == 0):
        return False
    rot_pad = _round_up(rot, 128)
    nq_pad = _round_up(nq + 1, 128)
    vmem = (2 * nq_pad * rot_pad * 4            # query table + one-hot
            + _decode_bytes(cap, rot, pq_dim, pq_bits)
            + 2 * GROUP * cap * 4)              # distances + extraction
    return (cap % 16 == 0 and GROUP % 16 == 0 and 0 < kt <= _KT_MAX
            and nq <= 6144 and vmem <= _VMEM_BUDGET)


def _fused_codes_bytes(cap: int, rot: int, kt: int, k: int, nq: int,
                       pq_dim: int, pq_bits: int) -> int:
    Wi = code_lane_words(pq_dim, pq_bits)
    return vb.fused_scan_bytes(
        k, kt, vb.nq_padded(nq), _round_up(rot, 128), GROUP,
        fused_stream_bytes(cap, _round_up(Wi, 8) * cap * 4)
        + _decode_bytes(cap, rot, pq_dim, pq_bits))


def supported_fused_codes(metric_is_l2: bool, per_subspace: bool, cap: int,
                          rot: int, kt: int, k: int, nq: int, pq_dim: int,
                          pq_bits: int) -> bool:
    """Shapes the FUSED code-scan kernel handles: the static
    :func:`supported_codes` preconditions plus the fused kernel's own
    VMEM model (resident query table and accumulator, row blocks,
    streamed codes, decode — :func:`vmem_budget.fused_scan_fits`); kt
    stays unrolled while k extends to ``vmem_budget.FUSED_K_MAX``
    through the looped per-step merge."""
    if not supported_codes(metric_is_l2, per_subspace, cap, rot, kt, nq,
                           pq_dim, pq_bits):
        return False
    return (0 < kt <= _KT_UNROLL and 0 < k <= vb.FUSED_K_MAX
            and vb.fused_scan_fits(_fused_codes_bytes(
                cap, rot, kt, k, nq, pq_dim, pq_bits)))


def fused_codes_reject_reason(metric_is_l2: bool, per_subspace: bool,
                              cap: int, rot: int, kt: int, k: int, nq: int,
                              pq_dim: int, pq_bits: int) -> str:
    """Reason code for a fused-codes gate miss ('' when supported):
    'dtype' (metric / codebook layout / pq_bits), 'k-too-large' (k/kt
    bounds), 'bucket-too-wide' (batch, alignment, or VMEM)."""
    if not (metric_is_l2 and per_subspace and pq_bits in (4, 8)
            and pq_dim and rot % pq_dim == 0):
        return "dtype"
    if not (0 < kt <= _KT_UNROLL and 0 < k <= vb.FUSED_K_MAX):
        return "k-too-large"
    if supported_fused_codes(metric_is_l2, per_subspace, cap, rot, kt, k,
                             nq, pq_dim, pq_bits):
        return ""
    return "bucket-too-wide"
