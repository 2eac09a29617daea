"""The plain reference: exact k nearest neighbours under squared L2.

Straightforward ``jax.numpy`` in float32, computed in blocks so that it
fits next to the data: queries in blocks of ``QUERY_BLOCK`` rows, the
database in blocks of ``DB_BLOCK`` rows, a running top-k merged block by
block.  Within a block only the ``k`` groups of ``GROUP`` columns with the
smallest minima are ranked, which is exact: every one of the k nearest
lies in such a group.  The cross term runs at ``Precision.HIGHEST``
(float32 on the TPU's matrix unit); ``precision="high"`` computes it in
the three bf16 passes of ``Precision.HIGH`` instead: the control that the
comparison must refuse.
Imports nothing of the library.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 1024
DB_BLOCK = 65536
PAIR_BLOCK = 8192
GROUP = 128


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def cross(q, x, precision: str):
    """``q @ x.T`` at ``"highest"``, or in the three bf16 passes of
    ``"high"``: ``Precision.HIGH`` on a TPU; elsewhere, where backends
    ignore the flag, spelled out (hi*hi + hi*lo + lo*hi, each product of
    bf16 values exact in float32, so only the dropped terms differ)."""
    if precision == "highest":
        return jnp.dot(q, x.T, precision=HIGHEST)
    if precision == "high" and jax.default_backend() == "tpu":
        return jnp.dot(q, x.T, precision=jax.lax.Precision.HIGH)
    if precision == "high":
        qh, ql = _split(q)
        xh, xl = _split(x)

        def dot(a, b):
            return jnp.dot(a, b.T, precision=HIGHEST)
        return dot(qh, xh) + (dot(qh, xl) + dot(ql, xh))
    raise ValueError(f"unknown precision {precision!r}")


def _topk_exact(d, k: int, width: int = GROUP):
    """Exact smallest ``k`` of each row of ``d`` (nq, n), n a multiple of
    ``width``: the k nearest lie in the k groups of ``width`` columns with
    the smallest minima, so only those k * width columns are ranked."""
    nq, n = d.shape
    g = d.reshape(nq, n // width, width)
    kg = min(k, n // width)
    _, gid = jax.lax.top_k(-jnp.min(g, axis=2), kg)           # (nq, kg)
    cand = jnp.take_along_axis(g, gid[:, :, None], axis=1)    # (nq, kg, w)
    v, p = jax.lax.top_k(-cand.reshape(nq, kg * width), k)
    col = (jnp.take_along_axis(gid, p // width, axis=1) * width
           + p % width)
    return -v, col


@functools.partial(jax.jit, static_argnames=("k", "precision", "block"))
def _knn_block(q, db, db_sq, *, k, precision, block):
    nq = q.shape[0]
    q_sq = jnp.sum(q * q, axis=1)

    def step(carry, b):
        best_d, best_i = carry
        xb = jax.lax.dynamic_slice_in_dim(db, b * block, block)
        sq = jax.lax.dynamic_slice_in_dim(db_sq, b * block, block)
        d = q_sq[:, None] + sq[None, :] - 2.0 * cross(q, xb, precision)
        v, j = _topk_exact(d, k)
        cat_d = jnp.concatenate([best_d, v], axis=1)
        cat_i = jnp.concatenate([best_i, j + b * block], axis=1)
        v2, p = jax.lax.top_k(-cat_d, k)
        return (-v2, jnp.take_along_axis(cat_i, p, axis=1)), None

    init = (jnp.full((nq, k), jnp.inf, jnp.float32),
            jnp.full((nq, k), -1, jnp.int32))
    (d, i), _ = jax.lax.scan(step, init, jnp.arange(db.shape[0] // block))
    return d, i


def _pad_rows(x, multiple: int, value=0.0):
    pad = (-x.shape[0]) % multiple
    if not pad:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=value)


def knn(queries, db, k: int, precision: str = "highest"):
    """Exact k nearest neighbours of every query row: host numpy
    ``(distances (nq, k) float32, ids (nq, k) int32)``, ascending."""
    queries = jnp.asarray(queries, jnp.float32)
    db = jnp.asarray(db, jnp.float32)
    n = db.shape[0]
    block = min(DB_BLOCK, -(-n // GROUP) * GROUP)
    # padded database rows get an infinite norm, so they never rank
    db_sq = _pad_rows(jnp.sum(db * db, axis=1), block, value=jnp.inf)
    db = _pad_rows(db, block)
    qblock = min(QUERY_BLOCK, queries.shape[0])
    qs = _pad_rows(queries, qblock)
    out_d, out_i = [], []
    for s in range(0, qs.shape[0], qblock):
        d, i = _knn_block(qs[s:s + qblock], db, db_sq, k=k,
                          precision=precision, block=block)
        out_d.append(np.asarray(d))
        out_i.append(np.asarray(i))
    nq = queries.shape[0]
    return (np.concatenate(out_d)[:nq], np.concatenate(out_i)[:nq])


@jax.jit
def _pair_block(pool, rows, db, ids):
    q = pool[rows]                                      # (b, dim)
    x = db[jnp.clip(ids, 0, db.shape[0] - 1)]          # (b, k, dim)
    diff = q[:, None, :] - x
    return jnp.sum(diff * diff, axis=-1)


def pair_distances(pool, rows, db, ids) -> np.ndarray:
    """Squared L2 from query ``pool[rows[j]]`` to each of ``ids[j]``, as
    ``sum((q - x)**2)`` in float32 (no cancellation): host numpy (n, k)."""
    rows = jnp.asarray(rows, jnp.int32)
    ids = jnp.asarray(ids, jnp.int32)
    n = ids.shape[0]
    block = min(PAIR_BLOCK, n)
    rs, ix = _pad_rows(rows, block), _pad_rows(ids, block)
    out = [np.asarray(_pair_block(pool, rs[s:s + block], db,
                                  ix[s:s + block]))
           for s in range(0, rs.shape[0], block)]
    return np.concatenate(out)[:n]
