"""Candidate refinement (exact re-ranking).

Reference: raft/neighbors/refine.cuh:105 ``refine`` — given approximate
candidate neighbors (e.g. from IVF-PQ or CAGRA's graph build), recompute exact
distances to the candidates and keep the best k (detail/refine.cuh; the host
path ``refine_host`` is what CAGRA's build uses).

TPU design: one gather of the candidate vectors (q, n_cand, d) + a batched
distance einsum + top-k — entirely fused by XLA; invalid candidate slots
(id < 0, the reference's out-of-list marker) are masked to +inf.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import ensure_array
from raft_tpu.core.tracing import range as named_range
from raft_tpu.integrity import boundary as _boundary
from raft_tpu import observability as obs
from raft_tpu.distance.types import DistanceType
from raft_tpu.matrix.select_k import select_k
from raft_tpu.utils.precision import get_matmul_precision
from raft_tpu.core.outputs import auto_convert_output


def exact_distances(queries, cand_vecs, valid, metric):
    """Exact (q, n_cand) distances from each query to its candidate
    vectors ``cand_vecs`` (q, n_cand, d), in f32 at the library's matmul
    precision; slots where ``valid`` is False read the metric's worst
    value (+inf, or -inf for inner product).  The arithmetic every
    re-rank shares: :func:`refine` here, and the routed index's re-rank
    on the shard that owns the rows (``distributed.ann``)."""
    qf = queries.astype(jnp.float32)
    cf = cand_vecs.astype(jnp.float32)
    if metric == DistanceType.InnerProduct:
        ip = jnp.einsum("qd,qcd->qc", qf, cf,
                        precision=get_matmul_precision())
        return jnp.where(valid, ip, -jnp.inf)
    # squared L2 (sqrt applied for the sqrt metrics below)
    diff2 = jnp.sum(cf * cf, axis=-1) - 2.0 * jnp.einsum(
        "qd,qcd->qc", qf, cf, precision=get_matmul_precision())
    d = jnp.maximum(diff2 + jnp.sum(qf * qf, axis=-1, keepdims=True), 0.0)
    if metric in (DistanceType.L2SqrtExpanded,
                  DistanceType.L2SqrtUnexpanded):
        d = jnp.sqrt(d)
    return jnp.where(valid, d, jnp.inf)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _refine_impl(dataset, queries, candidates, k, metric):
    valid = candidates >= 0
    safe = jnp.where(valid, candidates, 0)
    d = exact_distances(queries, dataset[safe], valid, metric)
    if metric == DistanceType.InnerProduct:
        vals, pos = jax.lax.top_k(d, k)
    else:
        vals, pos = select_k(d, k, select_min=True)
    idx = jnp.take_along_axis(candidates, pos, axis=1)
    return vals, idx


@auto_convert_output
def refine(
    res,
    dataset,
    queries,
    candidates,
    k: int,
    *,
    metric: int = DistanceType.L2Unexpanded,
) -> Tuple[jax.Array, jax.Array]:
    """Exact re-rank of candidate ids; returns (distances, indices) (q, k).

    Reference: neighbors/refine.cuh:105 (metric limited to L2/IP families
    there too).  ``candidates`` is (q, n_candidates) int ids into ``dataset``;
    negative ids are treated as empty slots.
    """
    with named_range("refine"):
        dataset = ensure_array(dataset, "dataset")
        queries = ensure_array(queries, "queries")
        candidates = ensure_array(candidates, "candidates")
        expects(candidates.ndim == 2
                and candidates.shape[0] == queries.shape[0],
                "refine: (q, n_candidates) ids required")
        expects(k <= candidates.shape[1],
                "refine: k exceeds candidate count")
        expects(metric in (DistanceType.L2Expanded,
                           DistanceType.L2SqrtExpanded,
                           DistanceType.L2Unexpanded,
                           DistanceType.L2SqrtUnexpanded,
                           DistanceType.InnerProduct),
                "refine: L2 / InnerProduct metrics only (as the reference)")
        queries, ok_rows = _boundary.check_matrix(
            queries, "queries", site="refine", dim=dataset.shape[1])
        with obs.stage("refine") as st:
            out = _refine_impl(dataset, queries, candidates, k, metric)
            st.fence(out)
        if ok_rows is not None:
            out = _boundary.mask_search_outputs(
                out[0], out[1], ok_rows,
                select_min=metric != DistanceType.InnerProduct)
        return out
