"""Decompose the grouped-scan kernel's ~22.5 us/group flat cost
(measured round 5: same per-group time at cap 160 and cap 416):
variants remove the one-hot query gather and/or the in-VMEM top-kt
extraction to see where the time actually goes."""

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
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from raft_tpu.neighbors.grouped import GROUP  # noqa: E402
from raft_tpu.ops import pq_group_scan_pallas as pqp  # noqa: E402


def _kernel_var(gl_ref, slot_ref, q_ref, data_ref, dsq_ref, ids_ref,
                *outs, kt, n_probes, P, gather, extract):
    if gather:
        qv = pqp._gather_queries(slot_ref, q_ref, n_probes, P)
    else:
        qv = q_ref[0]                                   # pre-gathered (G, d)
    q_sq = jnp.sum(qv * qv, axis=1)
    data = data_ref[0]
    ip = jax.lax.dot_general(qv, data, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    d = jnp.maximum(q_sq[:, None] + dsq_ref[0, 0][None, :] - 2.0 * ip, 0.0)
    if extract:
        vals_ref, ids_out_ref, vs, ps = outs
        pqp._extract_topk(d, ids_ref[0, 0], vals_ref, ids_out_ref, vs, ps,
                          kt)
    else:
        outs[0][0] = d                                  # raw block out


@functools.partial(jax.jit, static_argnames=("kt", "n_probes", "gather",
                                             "extract"))
def run_var(group_list, slot_pairs, q_in, list_data, d_sq, list_indices,
            kt, n_probes, gather, extract):
    n_groups = group_list.shape[0]
    _, cap, dim = list_data.shape
    nq = q_in.shape[0] if gather else 0
    P = slot_pairs.shape[0] * GROUP  # upper bound, fine for sentinel math

    if gather:
        nq_pad = -(-(nq + 1) // 128) * 128
        q_pad = jnp.zeros((nq_pad, dim), jnp.float32).at[:nq].set(q_in)
        q_spec = pl.BlockSpec((nq_pad, dim), lambda g, gl: (0, 0))
    else:
        q_pad = q_in                                    # (n_groups, G, dim)
        q_spec = pl.BlockSpec((1, GROUP, dim), lambda g, gl: (g, 0, 0))

    outs_spec = ([pl.BlockSpec((1, GROUP, kt), lambda g, gl: (g, 0, 0)),
                  pl.BlockSpec((1, GROUP, kt), lambda g, gl: (g, 0, 0))]
                 if extract else
                 [pl.BlockSpec((1, GROUP, cap), lambda g, gl: (g, 0, 0))])
    outs_shape = ([jax.ShapeDtypeStruct((n_groups, GROUP, kt), jnp.float32),
                   jax.ShapeDtypeStruct((n_groups, GROUP, kt), jnp.int32)]
                  if extract else
                  [jax.ShapeDtypeStruct((n_groups, GROUP, cap),
                                        jnp.float32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_groups,),
        in_specs=[
            pl.BlockSpec((1, 1, GROUP), lambda g, gl: (g, 0, 0)),
            q_spec,
            pl.BlockSpec((1, cap, dim), lambda g, gl: (gl[g], 0, 0)),
            pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
            pl.BlockSpec((1, 1, cap), lambda g, gl: (gl[g], 0, 0)),
        ],
        out_specs=outs_spec,
        scratch_shapes=pqp._scratch_shapes(kt) if extract else [],
    )
    return pl.pallas_call(
        functools.partial(_kernel_var, kt=kt, n_probes=n_probes, P=P,
                          gather=gather, extract=extract),
        out_shape=outs_shape, grid_spec=grid_spec,
    )(group_list, slot_pairs[:, None, :], q_pad, list_data, d_sq[:, None, :],
      list_indices[:, None, :])


def main():
    from raft_tpu import DeviceResources
    from raft_tpu.neighbors import grouped, ivf_flat

    n, dim, latent, nq = 1_000_000, 128, 16, 5000
    rng = np.random.default_rng(0)
    Z = rng.normal(size=(n + nq, latent)).astype(np.float32)
    A = rng.normal(size=(latent, dim)).astype(np.float32) / np.sqrt(latent)
    X = (Z @ A + 0.05 * rng.normal(
        size=(n + nq, dim))).astype(np.float32)
    db = jnp.asarray(X[:n])
    queries = jnp.asarray(X[n:])
    db.block_until_ready()
    res = DeviceResources(seed=0)

    def timeit(fn, reps=5):
        np.asarray(jax.tree_util.tree_leaves(fn())[0]).ravel()[:1]
        t0 = time.perf_counter()
        for _ in range(reps):
            o = fn()
        np.asarray(jax.tree_util.tree_leaves(o)[0]).ravel()[:1]
        return (time.perf_counter() - t0) / reps * 1e3

    for nlist, nprobe in ((16384, 256), (4096, 128)):
        index = ivf_flat.build(res, ivf_flat.IndexParams(n_lists=nlist), db)
        probes = ivf_flat._select_clusters(index.centers, queries, nprobe,
                                           index.metric)
        ng = grouped.round_groups(int(grouped.num_groups(probes, nlist)))
        gl, sp = grouped.build_groups(probes, nlist, ng)
        dsq = jnp.sum(index.list_data.astype(jnp.float32) ** 2, axis=-1)
        ld = index.list_data.astype(jnp.float32)
        qf = queries.astype(jnp.float32)
        # pre-gathered queries for the no-onehot variants
        P = nq * nprobe
        qid = jnp.where(sp < P, sp // nprobe, 0)        # (ng, G)
        qg = qf[qid]                                    # (ng, G, dim)
        kt = 10
        for gather in (True, False):
            for extract in (True, False):
                q_in = qf if gather else qg
                try:
                    ms = timeit(lambda: run_var(
                        gl, sp, q_in, ld, dsq, index.list_indices, kt,
                        nprobe, gather, extract))
                    print(json.dumps({
                        "nlist": nlist, "n_groups": ng,
                        "gather": gather, "extract": extract,
                        "ms": round(ms, 1),
                        "us_per_group": round(ms * 1e3 / ng, 2)}),
                        flush=True)
                except Exception as e:
                    print(json.dumps({"nlist": nlist, "gather": gather,
                                      "extract": extract,
                                      "error": str(e)[:120]}), flush=True)
        # cost of producing the pre-gathered queries (XLA gather)
        ms = timeit(lambda: qf[jnp.where(sp < P, sp // nprobe, 0)])
        print(json.dumps({"nlist": nlist, "xla_query_gather_ms":
                          round(ms, 1)}), flush=True)


if __name__ == "__main__":
    main()
