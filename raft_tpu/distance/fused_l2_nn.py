"""Fused L2 distance + 1-nearest-neighbor argmin.

Reference: raft/distance/fused_l2_nn.cuh:100 ``fusedL2NN`` / :205
``fusedL2NNMinReduce`` — the k-means / IVF hot kernel: for each row of x, the
index and distance of its nearest row in y, computed WITHOUT materialising the
(m, n) distance matrix.

TPU design: scan over database tiles.  Each step does one (m, tile_n) gemm on
the MXU plus a running (min, argmin) epilogue on the VPU; XLA keeps the tile
resident and fuses the epilogue, so HBM traffic is O(m*k + n*k + m) — the same
property the CUDA kernel's register-tile epilogue buys.  Peak memory is
m * tile_n.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from raft_tpu.core.error import expects
from raft_tpu.utils.precision import get_matmul_precision
from raft_tpu.core.outputs import auto_convert_output, raw

_TILE_N = 2048


@auto_convert_output
def fused_l2_nn(
    x: jax.Array,
    y: jax.Array,
    *,
    sqrt: bool = False,
    tile_n: int = _TILE_N,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """For each row of x (m, k): (min L2 distance, argmin index) over rows of y (n, k).

    Reference contract: fused_l2_nn.cuh:100 (out as KeyValuePair<idx, dist>);
    we return the pair as two arrays (dists (m,), idx (m,) int32).

    ``use_pallas=True`` runs the hand-written Pallas kernel
    (:mod:`raft_tpu.ops.fused_l2_nn_pallas`) — measured at parity with this
    XLA formulation on a v5e chip (both HBM-bound at the k-means shape);
    it exists as the foundation for fused epilogues XLA cannot express.
    The kernel compiles only for a TPU; ``pallas_interpret=True`` runs it
    in the Pallas interpreter instead (CPU tests).
    """
    expects(x.ndim == 2 and y.ndim == 2 and x.shape[1] == y.shape[1],
            "fused_l2_nn: (m,k),(n,k) inputs required")
    if use_pallas:
        from raft_tpu.core.platform import on_tpu
        from raft_tpu.ops.fused_l2_nn_pallas import fused_l2_nn_pallas
        expects(pallas_interpret or on_tpu(),
                "fused_l2_nn(use_pallas=True) needs a TPU backend "
                "(pass pallas_interpret=True to run the interpreter)")
        return fused_l2_nn_pallas(x, y, sqrt=sqrt,
                                  interpret=pallas_interpret)
    m, k = x.shape
    n = y.shape[0]
    tile_n = min(tile_n, n)
    if not isinstance(x, jax.core.Tracer) and not isinstance(
            y, jax.core.Tracer):
        # eager call: route through jit — op-by-op dispatch of the tile
        # scan costs ~27x on a remote-attached TPU.  The precision policy
        # is part of the jit key (a global read inside a cached trace
        # would go stale under matmul_precision()).
        return _fused_l2_nn_jit(x, y, sqrt=sqrt, tile_n=tile_n,
                                precision=get_matmul_precision())
    return _impl(x, y, sqrt=sqrt, tile_n=tile_n)


def _impl(x, y, *, sqrt, tile_n, precision=None):
    m, k = x.shape
    n = y.shape[0]
    # bound the (m, tile_n) working tile: at m=1M, tile_n=2048 it is 8 GB
    # fp32 — chunk the x side so the transient stays ~1 GB
    tile_m = 131_072
    if m > tile_m:
        outs = [_impl(x[s:s + tile_m], y, sqrt=sqrt, tile_n=tile_n,
                      precision=precision)
                for s in range(0, m, tile_m)]
        return (jnp.concatenate([o[0] for o in outs]),
                jnp.concatenate([o[1] for o in outs]))
    n_tiles = -(-n // tile_n)
    padded = n_tiles * tile_n

    xf = x.astype(jnp.float32)
    yf = jnp.pad(y.astype(jnp.float32), ((0, padded - n), (0, 0)))
    x_sq = jnp.sum(xf * xf, axis=1)
    y_sq = jnp.sum(yf * yf, axis=1)
    y_tiles = yf.reshape(n_tiles, tile_n, k)
    ysq_tiles = y_sq.reshape(n_tiles, tile_n)

    init = (jnp.full((m,), jnp.inf, jnp.float32),
            jnp.zeros((m,), jnp.int32))

    def step(carry, tile):
        best_d, best_i = carry
        yt, ysq, t = tile
        # (m, tile_n) distances for this tile: ||x||^2 + ||y||^2 - 2 x.y
        ip = jax.lax.dot_general(xf, yt, (((1,), (1,)), ((), ())),
                                 precision=precision or get_matmul_precision(),
                                 preferred_element_type=jnp.float32)
        d = x_sq[:, None] + ysq[None, :] - 2.0 * ip
        # mask padding
        valid = (t * tile_n + jnp.arange(tile_n)) < n
        d = jnp.where(valid[None, :], jnp.maximum(d, 0.0), jnp.inf)
        tile_best = jnp.min(d, axis=1)
        tile_arg = jnp.argmin(d, axis=1).astype(jnp.int32) + t * tile_n
        upd = tile_best < best_d
        return (jnp.where(upd, tile_best, best_d),
                jnp.where(upd, tile_arg, best_i)), None

    (best_d, best_i), _ = jax.lax.scan(
        step, init, (y_tiles, ysq_tiles, jnp.arange(n_tiles)))
    if sqrt:
        best_d = jnp.sqrt(best_d)
    return best_d, best_i


_fused_l2_nn_jit = jax.jit(_impl,
                           static_argnames=("sqrt", "tile_n", "precision"))


@auto_convert_output
def fused_l2_nn_min_reduce(x: jax.Array, y: jax.Array, *,
                           sqrt: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Alias matching fused_l2_nn.cuh:205 ``fusedL2NNMinReduce``."""
    return raw(fused_l2_nn)(x, y, sqrt=sqrt)
