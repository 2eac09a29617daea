"""Time the probe grouping (``grouped.build_groups``) alone, old against new.

    python3 profiles/group_build.py [--reps 50] [--tiny]

Lays out the (query, probe) pair groups at two shapes and prints one JSON
line per (shape, formulation): milliseconds per call and whether the
layout equals the old formulation's bit for bit.

- ``batch``: the SIFT-1M IVF-PQ batch cell, 5,000 queries x 72 probes
  over 4,096 lists, at the worst-bound 6,909 groups;
- ``shard``: one shard of the routed cell, the same pairs over a shard's
  2,049 list slots (4,862 groups), three quarters of them the
  out-of-range sentinel of the lists the shard does not own.

Formulations:

- ``old``: the layout before it was derived from the sorted keys — an
  argsort, then a ``segment_sum`` count, three gathers and one scatter,
  each over every pair (kept here only, as the baseline);
- ``new``: ``grouped.build_groups`` as the checkout has it (per-list
  tables; each group's window of sorted pairs moved as the two aligned
  128-wide rows that hold it, shifted into place by a barrel shift);
- ``window``: the same tables, each window moved by one gather of an
  unaligned 128-wide slice;
- ``scatter``: the layout from per-pair prefix scans (in-list rank by
  ``cummax``, group index by ``cumsum``) and one scatter of the sorted
  pairs, sorted and unique;
- ``search``: the per-pair prefix scans, the group heads found by
  ``jnp.searchsorted`` and the window gather of ``window``;
- ``sort``: the one sort alone, the floor of every formulation;
- ``num_groups_old`` / ``num_groups``: the group count, by
  ``segment_sum`` and by the checkout's ``grouped.num_groups``.

``--tiny`` rehearses the script off the chip at a toy size.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from raft_tpu.neighbors import grouped  # noqa: E402

G = grouped.GROUP


def old_build_groups(probes, n_lists, n_groups):
    """The parent layout: argsort, count, rank, ``jnp.repeat``."""
    P = probes.size
    pl = probes.reshape(-1)
    order = jnp.argsort(pl)
    pl_s = pl[order]
    counts = jax.ops.segment_sum(jnp.ones(P, jnp.int32), pl,
                                 num_segments=n_lists)
    groups_per_list = -(-counts // G)
    gstart = jnp.cumsum(groups_per_list) - groups_per_list
    group_list = jnp.repeat(jnp.arange(n_lists, dtype=jnp.int32),
                            groups_per_list, total_repeat_length=n_groups)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(P) - starts[pl_s]
    g = gstart[jnp.minimum(pl_s, n_lists - 1)] + rank // G
    s = rank % G
    slot_pairs = jnp.full((n_groups, G), P, jnp.int32)
    vals = jnp.where(pl_s < n_lists, order, P)
    slot_pairs = slot_pairs.at[g, s].set(vals, mode="drop")
    return group_list, slot_pairs


def old_num_groups(probes, n_lists):
    counts = jax.ops.segment_sum(
        jnp.ones(probes.size, jnp.int32), probes.reshape(-1),
        num_segments=n_lists)
    return jnp.sum(-(-counts // G))


def _tables(probes, n_lists, n_groups):
    """``grouped.build_groups``' per-list and per-group tables:
    ``(pl_s, order, group_list, start, length)``."""
    P = probes.size
    pl_s, order = grouped._sort_pairs(probes, n_lists)
    lstart = grouped._list_starts(pl_s, n_lists)
    gstart, gend = grouped._group_bounds(lstart)
    g = jax.lax.iota(jnp.int32, n_groups)
    gl = jnp.sum(gend[None, :] <= g[:, None], axis=1, dtype=jnp.int32)
    group_list = jnp.minimum(gl, n_lists - 1)
    offset = lstart[:-1] - G * gstart
    start = jnp.where(gl < n_lists, offset[group_list] + G * g, P)
    length = jnp.minimum(lstart[1:][group_list] - start, G)
    return pl_s, order, group_list, start, length


def _mask(win, length, P):
    return jnp.where(jnp.arange(G)[None, :] < length[:, None], win, P)


def _window_gather(order, start):
    """One gather of a ``G``-wide window of the sorted pairs per group."""
    src = jnp.concatenate([order, jnp.full((G,), order.shape[0],
                                           jnp.int32)])
    return jax.vmap(lambda s: jax.lax.dynamic_slice(src, (s,), (G,)))(start)


def window_build_groups(probes, n_lists, n_groups):
    P = probes.size
    _, order, group_list, start, length = _tables(probes, n_lists, n_groups)
    return group_list, _mask(_window_gather(order, start), length, P)


def _pair_scans(probes, n_lists):
    """Per-pair prefix scans: sorted keys, order, the in-list rank, and
    the running count of groups opened."""
    pl_s, order = grouped._sort_pairs(probes, n_lists)
    i = jax.lax.iota(jnp.int32, pl_s.shape[0])
    head = jnp.concatenate([jnp.ones((1,), bool), pl_s[1:] != pl_s[:-1]])
    rank = i - jax.lax.cummax(jnp.where(head, i, 0))
    ghead = (pl_s < n_lists) & ((rank & (G - 1)) == 0)
    csum = jnp.cumsum(ghead, dtype=jnp.int32)
    return pl_s, order, rank, csum


def scatter_build_groups(probes, n_lists, n_groups):
    P = probes.size
    group_list = _tables(probes, n_lists, n_groups)[2]
    pl_s, order, rank, csum = _pair_scans(probes, n_lists)
    flat = jnp.where(pl_s < n_lists, (csum - 1) * G + (rank & (G - 1)),
                     n_groups * G)
    slot_pairs = jnp.full((n_groups * G,), P, jnp.int32).at[flat].set(
        order, mode="drop", indices_are_sorted=True, unique_indices=True)
    return group_list, slot_pairs.reshape(n_groups, G)


def search_build_groups(probes, n_lists, n_groups):
    P = probes.size
    pl_s, order, rank, csum = _pair_scans(probes, n_lists)
    n_valid = jnp.sum(pl_s < n_lists, dtype=jnp.int32)
    heads = jnp.searchsorted(csum, jnp.arange(1, n_groups + 2),
                             side="left").astype(jnp.int32)
    start = heads[:-1]
    live = start < P
    length = jnp.minimum(heads[1:], n_valid) - start
    group_list = jnp.where(live, pl_s[jnp.minimum(start, P - 1)],
                           n_lists - 1)
    return group_list, _mask(_window_gather(order, start), length, P)


def sort_only(probes, n_lists, n_groups):
    return grouped._sort_pairs(probes, n_lists)


FORMS = {
    "old": old_build_groups,
    "new": grouped.build_groups,
    "window": window_build_groups,
    "scatter": scatter_build_groups,
    "search": search_build_groups,
    "sort": sort_only,
}


def shapes(tiny):
    if tiny:
        return {"batch": (200, 8, 64), "shard": (200, 8, 33)}
    return {"batch": (5000, 72, 4096), "shard": (5000, 72, 2049)}


def make_probes(rng, name, nq, n_probes, n_lists):
    """Distinct lists per query; for a shard, the lists it does not own
    (three quarters) are the out-of-range sentinel ``n_lists``."""
    if name == "batch":
        return rng.random((nq, n_lists)).argpartition(
            n_probes, axis=1)[:, :n_probes].astype(np.int32)
    total = 4 * (n_lists - 1)
    p = rng.random((nq, total)).argpartition(
        n_probes, axis=1)[:, :n_probes]
    # the shard owns every fourth global list, at local slot list // 4
    return np.where(p % 4 == 0, p // 4, n_lists).astype(np.int32)


def time_call(fn, args, reps):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.tiny:
        raise SystemExit("needs a TPU (or --tiny to rehearse)")
    rng = np.random.default_rng(0)
    for name, (nq, n_probes, n_lists) in shapes(a.tiny).items():
        probes = jnp.asarray(make_probes(rng, name, nq, n_probes, n_lists))
        n_groups, _ = grouped.group_capacity(nq, n_probes, n_lists)
        ref = None
        for form, fn in FORMS.items():
            f = jax.jit(functools.partial(fn, n_lists=n_lists,
                                          n_groups=n_groups))
            sec, out = time_call(f, (probes,), a.reps)
            line = {"shape": name, "form": form, "n_groups": n_groups,
                    "ms_per_call": sec * 1e3}
            if form == "old":
                ref = [np.asarray(x) for x in out]
            elif form != "sort":
                line["equal_old"] = all(
                    np.array_equal(np.asarray(x), r)
                    for x, r in zip(out, ref))
            line["device"] = dev.device_kind
            print(json.dumps(line), flush=True)
        for form, fn in (("num_groups_old", old_num_groups),
                         ("num_groups", grouped.num_groups)):
            f = jax.jit(functools.partial(fn, n_lists=n_lists))
            sec, out = time_call(f, (probes,), a.reps)
            print(json.dumps({"shape": name, "form": form,
                              "value": int(out), "ms_per_call": sec * 1e3,
                              "device": dev.device_kind}), flush=True)


if __name__ == "__main__":
    main()
