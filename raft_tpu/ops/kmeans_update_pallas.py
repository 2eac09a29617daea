"""k-means assignment + centroid-update pass (XLA distance + Pallas epilogue).

The ``fusedL2NN`` + ``update_centroids`` analogue (reference:
distance/fused_l2_nn.cuh:100 feeding cluster/detail/kmeans.cuh:432): one
logical pass over the data per Lloyd iteration that computes per-row
nearest centroids and accumulates the weighted per-cluster sums/counts.

Round-5 structure — a two-stage split, measured faster than the fully
fused round-4 kernel (12.1 ms vs 20.5 ms best-observed for the whole
pass at 1M x 128, k=1024, tile 2048 on one v5e):

1. **Distance + argmin (XLA)** — ``argmin ||x-c||^2 = argmin (||c||^2 -
   2 x.c)`` (the per-row ``||x||^2`` term cannot change the argmin and
   is never computed).  XLA fuses the row min/argmin into the matmul
   loop without materializing the (n, K) block in HBM, and its matmul
   schedule reaches ~120 TF/s on this part where a hand-written Mosaic
   grid loop over the same shape measured ~21 TF/s (profiles/
   kmeans_decomp_r5.py: a (2048,128)@(128,1024) step per grid tick is
   too small to hide Mosaic's per-step overhead, and fatter K blocks
   blow VMEM).  Do not re-fuse stage 1 into the kernel — this split IS
   the optimization.
2. **One-hot epilogue (Pallas)** — per data tile, expand labels to a
   one-hot block and accumulate the **weighted per-cluster sums as an
   MXU matmul** (``onehot_w^T @ x``) into a VMEM-resident (K, dim)
   accumulator, plus counts as a VPU column reduce.  The round-3 XLA
   Lloyd loop was epilogue-bound precisely here: ``segment_sum`` lowers
   to a serialized HBM scatter-add (23.7 ms measured vs 10.9 ms for
   this kernel), and labels round-trip through HBM either way, so the
   epilogue — not the distance matmul — is what Pallas should own.

Padding contract (callers: :func:`fused_assign_update`):
- rows are padded to the tile size with **zero weights** — padded rows
  contribute nothing to sums/counts;
- K is padded to a lane multiple with ``c_sq = +inf`` sentinels — the
  argmin never selects a padded cluster;
- dim is padded with zero columns on both x and centroids — distances
  and sums are unchanged; callers slice the result.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _epi_kernel(x_ref, w_ref, lab_ref, sums_ref, counts_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[...]                                   # (T, dim) bf16
    lab = lab_ref[...]                               # (T, 1) int32
    k_pad = counts_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], k_pad), 1)
    onehot_w = (cols == lab).astype(jnp.float32) * w_ref[...]

    # weighted sums: (K, dim) += onehot_w^T @ x  (MXU, f32 accumulate;
    # the one-hot factor is exact in bf16 — values are 0 or w, and
    # integer/short-float weights survive the cast for the common
    # uniform-weight case)
    sums_ref[...] += jax.lax.dot_general(
        onehot_w.astype(jnp.bfloat16), x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    counts_ref[...] += jnp.sum(onehot_w, axis=0, keepdims=True)


def _round_up(v, m):
    return -(-v // m) * m


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def fused_assign_update(x, weights, centroids, tile=1024, interpret=False):
    """One assignment+update pass (see module docstring for the split).

    ``x`` (n, dim); ``weights`` (n,) f32; ``centroids`` (k, dim).
    Returns ``(sums (k, dim) f32, counts (k,) f32, dmin (n,) f32)`` —
    the weighted per-cluster sums, total weights, and each row's
    ``min_c(||c||^2 - 2 x.c)`` (add the row's own ``||x||^2`` for a
    true squared distance); callers derive the means and keep old
    centroids for empty clusters (update_centroids contract, reference
    detail/kmeans.cuh:285).

    bf16 MXU passes with f32 accumulation: x is rounded once (~1e-3
    relative) — within Lloyd's self-correcting tolerance (see
    test_kmeans_fused_matches_xla).
    """
    n, dim = x.shape
    k = centroids.shape[0]
    k_pad = _round_up(k, 128)
    d_pad = _round_up(dim, 128)
    # row padding serves both stages: the epilogue needs a tile
    # multiple, stage 1 a chunk multiple (chunk = a tile multiple, so
    # one padded size fits both — computed up front to pad exactly once)
    n_pad = _round_up(n, tile)
    n_chunks = -(-n_pad // (128 * tile))
    chunk = _round_up(-(-n_pad // n_chunks), tile)
    n_pad = chunk * n_chunks

    cf = centroids.astype(jnp.float32)
    c_sq = jnp.sum(cf * cf, axis=1)
    csq_p = jnp.full((1, k_pad), jnp.inf, jnp.float32).at[0, :k].set(c_sq)
    c_p = jnp.zeros((k_pad, d_pad), jnp.bfloat16)
    c_p = c_p.at[:k, :dim].set(cf.astype(jnp.bfloat16))
    x_p = jnp.zeros((n_pad, d_pad), jnp.bfloat16)
    x_p = x_p.at[:n, :dim].set(x.astype(jnp.bfloat16))
    w_p = jnp.zeros((n_pad, 1), jnp.float32)
    w_p = w_p.at[:n, 0].set(weights.astype(jnp.float32))

    # stage 1 (XLA): fused matmul + row argmin/min (padded rows get a
    # harmless real argmin; their zero weight drops them from the
    # epilogue).  Chunked over rows with lax.map so peak memory is
    # O(chunk * k_pad) by construction — XLA fuses the reductions into
    # the matmul at the sizes measured, but nothing guarantees that at
    # every (n, k), and a materialized (n_pad, k_pad) f32 block at
    # 50M x 1024 would be ~200 GB.
    def _assign_chunk(xc):
        ip = jax.lax.dot_general(xc, c_p, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        d = csq_p - 2.0 * ip
        return jnp.argmin(d, axis=1).astype(jnp.int32), jnp.min(d, axis=1)

    labels, dmin = jax.lax.map(_assign_chunk,
                               x_p.reshape(n_pad // chunk, chunk, d_pad))
    labels = labels.reshape(n_pad)
    dmin = dmin.reshape(n_pad)

    # stage 2 (Pallas): one-hot epilogue
    sums, counts = pl.pallas_call(
        _epi_kernel,
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((tile, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, k_pad), jnp.float32),
        ],
        interpret=interpret,
    )(x_p, w_p, labels[:, None])
    return sums[:k, :dim], counts[0, :k], dmin[:n]


def supported(n: int, dim: int, k: int, metric_is_l2: bool,
              tile: int = 1024) -> bool:
    """Shapes the epilogue kernel handles at this tile; callers fall
    back to the XLA path otherwise.  VMEM: x tile + one-hot (f32 + the
    bf16 cast) + accumulator must fit; the distance block lives in
    stage 1 (XLA) and costs no VMEM here.  The k_pad*d_pad cap keeps
    the VMEM-resident sums accumulator bounded, which also bounds the
    stage-1 regime to sizes where XLA's matmul+argmin fusion is
    verified (k <= ~4096 at dim 128)."""
    k_pad = _round_up(k, 128)
    d_pad = _round_up(dim, 128)
    vmem = (tile * d_pad * 2            # x tile bf16
            + tile * k_pad * 6          # one-hot f32 + bf16 cast
            + k_pad * d_pad * 4         # sums accumulator
            + 2 * k_pad * 4)
    return (metric_is_l2 and n >= tile and vmem <= (15 << 20)
            and k_pad * d_pad * 4 <= (4 << 20))


def best_tile(n: int, dim: int, k: int, metric_is_l2: bool) -> int:
    """Largest supported data tile (descending ladder), 0 if none —
    large cluster counts shrink the tile so the one-hot block stays
    inside VMEM (k=4096 @ dim 128 fits at 512)."""
    for tile in (2048, 1024, 512, 256):
        if supported(n, dim, k, metric_is_l2, tile=tile):
            return tile
    return 0


def fused_tile(n: int, dim: int, k: int) -> int:
    """The ONE backend+shape gate for routing a Lloyd-style loop through
    this pass (kmeans.fit and kmeans_balanced share it; each checks
    its own metric family first).  dim < 32 is unprofitable — lane
    padding makes the bf16 tiles mostly zeros."""
    from raft_tpu.core.platform import on_tpu

    if not on_tpu() or dim < 32:
        return 0
    return best_tile(n, dim, k, True)
