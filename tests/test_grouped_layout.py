"""Pair-group layout of ``grouped.build_groups`` / ``grouped.num_groups``.

The fused list scans read the layout's invariants directly (live groups
first, real slots first in each group, the tail aliasing the last list),
so the layout is checked exactly against a NumPy transcription of its
definition: a stable argsort of the pairs by list, per-list counts, the
in-list rank, and ``jnp.repeat``'s padding of the group -> list map.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.neighbors import grouped

G = grouped.GROUP


def reference_layout(probes: np.ndarray, n_lists: int, n_groups: int):
    """NumPy transcription of the layout: ``(group_list, slot_pairs,
    needed)``."""
    pl = probes.reshape(-1).astype(np.int64)
    P = pl.size
    order = np.argsort(pl, kind="stable")
    pl_s = pl[order]
    valid = pl < n_lists
    counts = np.bincount(pl[valid], minlength=n_lists)
    per_list = -(-counts // G)
    gstart = np.cumsum(per_list) - per_list
    starts = np.cumsum(counts) - counts
    lists = np.repeat(np.arange(n_lists), per_list)
    # jnp.repeat(total_repeat_length=n) truncates, and pads with the
    # last list
    group_list = np.full(n_groups, n_lists - 1, np.int64)
    group_list[:min(n_groups, lists.size)] = lists[:n_groups]
    slot_pairs = np.full((n_groups, G), P, np.int64)
    for pos in range(P):
        lst = pl_s[pos]
        if lst >= n_lists:
            continue
        rank = pos - starts[lst]
        g = gstart[lst] + rank // G
        if g < n_groups:
            slot_pairs[g, rank % G] = order[pos]
    return group_list, slot_pairs, int(per_list.sum())


def _random_probes(rng, nq, n_probes, n_lists):
    """Distinct lists per query, as the coarse select gives them."""
    return np.stack([rng.choice(n_lists, n_probes, replace=False)
                     for _ in range(nq)]).astype(np.int32)


def _case(name, rng):
    """``(probes, n_lists, n_groups)`` of one named case; ``n_groups``
    None means the worst bound of ``grouped.group_capacity``."""
    if name == "random":
        return _random_probes(rng, 300, 8, 64), 64, None
    if name == "sentinels_mixed":
        p = _random_probes(rng, 257, 6, 40)
        # sentinels at the bound and far past it, as the super-tile
        # dedupe and the routed shards' unowned probes write them
        p = np.where(rng.random(p.shape) < 0.4,
                     rng.choice([40, 41, 1000], p.shape), p)
        return p.astype(np.int32), 40, None
    if name == "one_list":
        return np.full((700, 1), 5, np.int32), 16, None
    if name == "each_list_once":
        return rng.permutation(96).reshape(12, 8).astype(np.int32), 96, None
    if name == "single_pair":
        return np.array([[3]], np.int32), 8, None
    if name == "all_sentinels":
        return np.full((50, 4), 32, np.int32), 32, None
    if name == "pairs_fill_blocks":
        # P a multiple of the block width, no sentinel: the last block
        # holds no padding key
        return _random_probes(rng, 64, 4, 16), 16, None
    if name == "above_worst":
        return _random_probes(rng, 200, 5, 24), 24, "above"
    if name == "below_need":
        # the calibrated regime's overflow: groups past capacity drop
        return np.full((600, 1), 2, np.int32), 8, 3
    if name == "lone_pair_lone_list":
        return np.full((1, 1), 0, np.int32), 1, None
    raise KeyError(name)


CASES = ("random", "sentinels_mixed", "one_list", "each_list_once",
         "single_pair", "all_sentinels", "pairs_fill_blocks", "above_worst",
         "below_need", "lone_pair_lone_list")


@pytest.mark.parametrize("seed", (0, 1))
@pytest.mark.parametrize("name", CASES)
def test_layout_matches_reference(name, seed):
    rng = np.random.default_rng(seed)
    probes, n_lists, n_groups = _case(name, rng)
    nq, n_probes = probes.shape
    worst, exact = grouped.group_capacity(nq, n_probes, n_lists)
    assert exact
    if n_groups is None:
        n_groups = worst
    elif n_groups == "above":
        n_groups = worst + 37
    build = jax.jit(grouped.build_groups, static_argnums=(1, 2))
    gl, sp = build(jnp.asarray(probes), n_lists, n_groups)
    gl, sp = np.asarray(gl), np.asarray(sp)
    ref_gl, ref_sp, needed = reference_layout(probes, n_lists, n_groups)
    assert gl.dtype == np.int32 and sp.dtype == np.int32
    np.testing.assert_array_equal(gl, ref_gl)
    np.testing.assert_array_equal(sp, ref_sp)
    assert int(grouped.num_groups(jnp.asarray(probes), n_lists)) == needed

    # the invariants the fused kernels read
    P = probes.size
    real = sp < P
    live = real.any(axis=1)
    n_live = int(live.sum())
    assert n_live == min(needed, n_groups)
    assert live[:n_live].all() and not live[n_live:].any()
    lengths = real.sum(axis=1)
    assert (real == (np.arange(G)[None, :] < lengths[:, None])).all()
    assert (gl[n_live:] == n_lists - 1).all()
    assert (np.diff(gl[:n_live]) >= 0).all()
    flat = probes.reshape(-1)
    held = sp[real]
    assert len(np.unique(held)) == held.size
    assert (flat[held] == np.repeat(gl, lengths)).all()
    if n_groups >= needed:
        # every in-range pair holds a slot; sentinels hold none
        assert set(held.tolist()) == set(np.flatnonzero(flat < n_lists)
                                         .tolist())


@pytest.mark.parametrize("shape", ((0, 6), (4, 0)))
def test_empty_batch_layout(shape):
    probes = np.zeros(shape, np.int32)
    gl, sp = grouped.build_groups(jnp.asarray(probes), 8, 3)
    ref_gl, ref_sp, needed = reference_layout(probes, 8, 3)
    np.testing.assert_array_equal(np.asarray(gl), ref_gl)
    np.testing.assert_array_equal(np.asarray(sp), ref_sp)
    assert int(grouped.num_groups(jnp.asarray(probes), 8)) == needed == 0


_DEF = re.compile(r"^\s*(?:ROOT\s+)?(\S+)\s*=\s*\w+\[([\d,]*)\]")
_MOVE = re.compile(r"\s(gather|scatter)\(([^)]*)\)")


def _index_rows(lowered):
    """``(op, rows)`` for every gather and scatter of a lowered program:
    ``rows`` is the leading extent of its index operand (the second
    operand of both), read from the HLO text."""
    text = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    shapes = {}
    for line in text.splitlines():
        m = _DEF.match(line)
        if m:
            shapes[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
    moves = []
    for line in text.splitlines():
        m = _MOVE.search(line)
        if m:
            operands = [o.strip().split(" ")[-1]
                        for o in m.group(2).split(",")]
            dims = shapes[operands[1]]
            moves.append((m.group(1), dims[0] if dims else 1))
    return moves, text.count(" sort(")


def test_cell_shape_lowers_to_one_sort_and_no_full_width_moves():
    """At the batch cell's shape (5,000 queries x 72 probes over 4,096
    lists, the worst-bound 6,909 groups) the layout costs one sort: no
    gather or scatter is indexed per (query, probe) pair."""
    nq, n_probes, n_lists = 5000, 72, 4096
    n_groups, _ = grouped.group_capacity(nq, n_probes, n_lists)
    assert n_groups == 6909
    probes = jax.ShapeDtypeStruct((nq, n_probes), jnp.int32)
    P = nq * n_probes
    moves, sorts = _index_rows(jax.jit(functools.partial(
        grouped.build_groups, n_lists=n_lists, n_groups=n_groups)).lower(
        probes))
    assert sorts == 1
    assert ("gather", n_groups) in moves, "one window move a group"
    assert all(rows < P for _, rows in moves), moves

    moves, sorts = _index_rows(jax.jit(functools.partial(
        grouped.num_groups, n_lists=n_lists)).lower(probes))
    assert sorts == 1
    assert all(rows < P for _, rows in moves), moves
