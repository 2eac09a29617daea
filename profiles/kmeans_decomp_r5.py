"""Round-5 k-means kernel decomposition: isolate matmul / min / argmin /
epilogue shares at tile 2048 so the 80 it/s push targets the real cost.

Variants (cumulative):
  mm        — distance matmul only, write one ip column (no k-reduction)
  mmmin     — + row min over k (dmin output)
  mmargmin  — + argmin (labels), still no epilogue
  full      — + one-hot epilogue matmul + counts (== kmeans_kernel_r5 uw)
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

from raft_tpu.ops.kmeans_update_pallas import _round_up  # noqa: E402


def _make_kernel(which):
    def kern(x_ref, c_ref, csq_ref, sums_ref, counts_ref, dmin_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            sums_ref[...] = jnp.zeros_like(sums_ref)
            counts_ref[...] = jnp.zeros_like(counts_ref)

        x = x_ref[...]
        ip = jax.lax.dot_general(x, c_ref[...], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        d = csq_ref[...] - 2.0 * ip
        if which == "mm":
            dmin_ref[...] = d[:, :1]
            return
        dmin = jnp.min(d, axis=1, keepdims=True)
        dmin_ref[...] = dmin
        if which == "mmmin":
            return
        labels = jnp.argmin(d, axis=1)
        if which == "mmargmin":
            counts_ref[...] += jnp.sum(labels.astype(jnp.float32)
                                       )[None, None]
            return
        cols = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
        onehot = (cols == labels[:, None]).astype(jnp.bfloat16)
        sums_ref[...] += jax.lax.dot_general(
            onehot, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        counts_ref[...] += jnp.sum(onehot.astype(jnp.float32), axis=0,
                                   keepdims=True)
    return kern


@functools.partial(jax.jit, static_argnames=("tile", "which"))
def run(x, centroids, tile, which):
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
    sums, counts, dmin = pl.pallas_call(
        _make_kernel(which),
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
    return sums, counts, dmin


def time_it(fn, reps=10):
    out = fn()
    np.asarray(jax.tree_util.tree_leaves(out)[0])
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
    x.block_until_ready()
    for which in ("mm", "mmmin", "mmargmin", "full"):
        for tile in (2048, 4096):
            try:
                ms = time_it(lambda: run(x, c, tile, which)) * 1e3
                print(json.dumps({"variant": which, "tile": tile,
                                  "ms": round(ms, 2)}), flush=True)
            except Exception as e:
                print(json.dumps({"variant": which, "tile": tile,
                                  "error": str(e)[:120]}), flush=True)


if __name__ == "__main__":
    main()
