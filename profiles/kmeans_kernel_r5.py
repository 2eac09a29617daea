"""Round-5 k-means kernel experiments: find where the fused pass's
time goes and which variant clears 80 it/s at 1M x 128, k=1024.

Variants:
  base      — current fused_assign_update (tile sweep)
  nodmin    — drop the dmin output (plain Lloyd does not need it)
  uw        — uniform-weight specialization (onehot straight to bf16,
              no w multiply; counts from the f32 one-hot sum)
  mxuonly   — distance matmul only (no epilogue): isolates the MXU
              floor so the epilogue's share is measurable
"""

import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402

from raft_tpu.core.platform import setup_compile_cache  # noqa: E402
setup_compile_cache()

import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from raft_tpu.ops.kmeans_update_pallas import (  # noqa: E402
    _round_up,
    fused_assign_update,
)


def _kernel_uw(x_ref, c_ref, csq_ref, sums_ref, counts_ref, dmin_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[...]
    ip = jax.lax.dot_general(x, c_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    d = csq_ref[...] - 2.0 * ip
    labels = jnp.argmin(d, axis=1)
    dmin_ref[...] = jnp.min(d, axis=1, keepdims=True)
    cols = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    onehot = (cols == labels[:, None]).astype(jnp.bfloat16)
    sums_ref[...] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    counts_ref[...] += jnp.sum(onehot.astype(jnp.float32), axis=0,
                               keepdims=True)


def _kernel_mxuonly(x_ref, c_ref, csq_ref, sums_ref, counts_ref,
                    dmin_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[...]
    ip = jax.lax.dot_general(x, c_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    d = csq_ref[...] - 2.0 * ip
    dmin_ref[...] = jnp.min(d, axis=1, keepdims=True)
    counts_ref[...] += jnp.sum(d, axis=0, keepdims=True)  # placeholder


@functools.partial(jax.jit, static_argnames=("tile", "which"))
def run_variant(x, centroids, tile, which):
    n, dim = x.shape
    k = centroids.shape[0]
    n_pad = _round_up(n, tile)
    k_pad = _round_up(k, 128)
    d_pad = _round_up(dim, 128)
    cf = centroids.astype(jnp.float32)
    c_sq = jnp.sum(cf * cf, axis=1)
    csq_p = jnp.full((1, k_pad), jnp.inf, jnp.float32).at[0, :k].set(c_sq)
    c_p = jnp.zeros((k_pad, d_pad), jnp.bfloat16).at[:k, :dim].set(
        cf.astype(jnp.bfloat16))
    x_p = jnp.zeros((n_pad, d_pad), jnp.bfloat16).at[:n, :dim].set(
        x.astype(jnp.bfloat16))
    kern = {"uw": _kernel_uw, "mxuonly": _kernel_mxuonly}[which]
    sums, counts, dmin = pl.pallas_call(
        kern,
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((tile, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
    )(x_p, c_p, csq_p)
    return sums[:k, :dim], counts[0, :k], dmin[:n, 0]


def time_it(fn, reps=10):
    out = fn()
    np.asarray(jax.tree_util.tree_leaves(out)[0])    # forced warm readback
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    np.asarray(jax.tree_util.tree_leaves(out)[0])
    return (time.perf_counter() - t0) / reps


def main():
    n, dim, k = 1_000_000, 128, 1024
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, dim)).astype(np.float32))
    ones = jnp.ones((n,), jnp.float32)
    x.block_until_ready()

    for tile in (512, 1024, 2048):
        try:
            ms = time_it(lambda: fused_assign_update(x, ones, c,
                                                     tile=tile)) * 1e3
            print(json.dumps({"variant": "base", "tile": tile,
                              "ms": round(ms, 2)}), flush=True)
        except Exception as e:                        # VMEM overflow etc
            print(json.dumps({"variant": "base", "tile": tile,
                              "error": str(e)[:120]}), flush=True)
    for which in ("uw", "mxuonly"):
        for tile in (1024, 2048):
            try:
                ms = time_it(lambda: run_variant(x, c, tile, which)) * 1e3
                print(json.dumps({"variant": which, "tile": tile,
                                  "ms": round(ms, 2)}), flush=True)
            except Exception as e:
                print(json.dumps({"variant": which, "tile": tile,
                                  "error": str(e)[:120]}), flush=True)
    # correctness spot-check: uw matches base on a slice
    s0, c0, d0 = fused_assign_update(x[:65536], ones[:65536], c, tile=1024)
    s1, c1, d1 = run_variant(x[:65536], c, 1024, "uw")
    print(json.dumps({
        "uw_sums_close": bool(jnp.allclose(s0, s1, rtol=1e-3, atol=1e-2)),
        "uw_counts_equal": bool(jnp.array_equal(c0, c1)),
        "uw_dmin_close": bool(jnp.allclose(d0, d1, rtol=1e-3,
                                           atol=1e-2))}), flush=True)


if __name__ == "__main__":
    main()
