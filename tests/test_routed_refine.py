"""The routed (``by_list``) index's re-rank on the shard that owns the row.

A routed r = 2 index over 20,000 x 128 rows and 64 lists, on a 4-device
sub-mesh of the 8 forced CPU devices, searched with ``refine_ratio=2``.
The tolerances are tight enough that a bf16 re-rank fails them: returned
distances match the exact distances of the returned ids within 5e-5
relative (a bf16 re-rank errs by about 1e-2).
"""

import functools
import io
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import observability as obs
from raft_tpu.comms import CommsSession
from raft_tpu.core.error import LogicError
from raft_tpu.distributed import ann
from raft_tpu.distributed.routing import RoutingPolicy
from raft_tpu.neighbors import grouped
from raft_tpu.neighbors import ivf_pq
from raft_tpu.neighbors.refine import refine

N, DIM, N_LISTS, NQ, K, RATIO, N_PROBES, N_DEV = (20_000, 128, 64, 200, 10,
                                                  2, 8, 4)
# the single-chip recall may beat the routed one only by PQ ties that the
# two CPU formulations order differently
RECALL_MARGIN = 0.005
REL_TOL = 5e-5
SCAN_MODES = ("auto", "fused")       # probe-order recon / grouped twin


def _vectors(rng, n):
    """SIFT-shaped rows: a 16-d latent mixed up to 128 d, plus noise."""
    z = rng.normal(size=(n, 16))
    a = rng.normal(size=(16, DIM)) / 4.0
    return (z @ a + 0.05 * rng.normal(size=(n, DIM))).astype(np.float32)


@pytest.fixture(scope="module")
def handle():
    devs = jax.devices()
    if len(devs) < N_DEV:
        pytest.skip(f"needs {N_DEV} devices")
    mesh = jax.sharding.Mesh(np.asarray(devs[:N_DEV]), ("data",))
    s = CommsSession(mesh=mesh, axis_name="data").init()
    yield s.worker_handle(seed=0)
    s.destroy()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(25)
    x = _vectors(rng, N + NQ)
    return x[:N], x[N:]


@pytest.fixture(scope="module")
def built(handle, data):
    db, _ = data
    params = ivf_pq.IndexParams(n_lists=N_LISTS, pq_dim=32,
                                kmeans_n_iters=10)
    base = ivf_pq.build(handle, params, db)
    routed = ann.shard_by_list(handle, base, replication_factor=2,
                               dataset=db)
    return base, routed


def _search(handle, index, q, mode="auto", **kw):
    sp = ivf_pq.SearchParams(n_probes=N_PROBES, scan_mode=mode)
    d, i = ann.search(handle, sp, index, q, K, refine_ratio=RATIO, **kw)
    return np.asarray(d), np.asarray(i)


def _exact_knn(db, q, k):
    d = ((q[:, None, :].astype(np.float64) - db[None].astype(np.float64))
         ** 2).sum(-1)
    return np.argsort(d, axis=1, kind="stable")[:, :k]


def _recall(ids, truth):
    return np.mean([len(set(a) & set(b)) / truth.shape[1]
                    for a, b in zip(ids, truth)])


@pytest.mark.parametrize("mode", SCAN_MODES)
def test_distances_are_exact(handle, data, built, mode):
    db, q = data
    d, i = _search(handle, built[1], q, mode)
    assert (i >= 0).all() and (i < N).all()
    x = db[i].astype(np.float64)
    exact = ((x - q[:, None, :].astype(np.float64)) ** 2).sum(-1)
    np.testing.assert_allclose(d, exact, rtol=REL_TOL, atol=0)
    # ascending, as refine returns them
    assert (np.diff(d, axis=1) >= 0).all()


@pytest.mark.parametrize("mode", SCAN_MODES)
def test_recall_no_worse_than_single_chip_refine(handle, data, built, mode):
    db, q = data
    base, routed = built
    truth = _exact_knn(db, q, K)
    sp = ivf_pq.SearchParams(n_probes=N_PROBES)
    _, cand = ivf_pq.search(handle, sp, base, q, K * RATIO)
    _, single = refine(handle, db, q, cand, K)
    _, i = _search(handle, routed, q, mode)
    r_single = _recall(np.asarray(single), truth)
    r_routed = _recall(i, truth)
    assert r_single > 0.5            # the operating point does real work
    assert r_routed >= r_single - RECALL_MARGIN


@pytest.mark.parametrize("mode", SCAN_MODES)
def test_bit_identical_across_replica_assignments(handle, data, built, mode):
    _, q = data
    routed = built[1]
    d0, i0 = _search(handle, routed, q, mode)
    policy = RoutingPolicy(N_DEV)
    for _ in range(3):              # the policy's tables move per batch
        d, i = _search(handle, routed, q, mode, routing=policy)
        np.testing.assert_array_equal(i, i0)
        np.testing.assert_array_equal(d, d0)
    for s in range(N_DEV):
        d, i = _search(handle, routed, q, mode, failed_shards=(s,))
        np.testing.assert_array_equal(i, i0)
        np.testing.assert_array_equal(d, d0)


def test_filtered_refine_returns_only_admitted_rows(handle, data, built):
    db, q = data
    admit = np.zeros((NQ, N), bool)
    admit[:, 1::2] = True                # odd ids only
    d, i = _search(handle, built[1], q, filter=admit)
    assert (i >= 0).all() and (i % 2 == 1).all()
    exact = ((db[i].astype(np.float64) - q[:, None, :]) ** 2).sum(-1)
    np.testing.assert_allclose(d, exact, rtol=REL_TOL, atol=0)


def test_same_after_rebalance_and_serialization(handle, data, built):
    _, q = data
    routed = built[1]
    d0, i0 = _search(handle, routed, q)
    moved = ann.rebalance_placement(handle, routed)
    assert moved.list_rows is not None
    d, i = _search(handle, moved, q)
    np.testing.assert_array_equal(i, i0)
    np.testing.assert_array_equal(d, d0)
    buf = io.BytesIO()
    ann.serialize_routed(handle, buf, routed)
    buf.seek(0)
    back = ann.deserialize_routed(handle, buf)
    assert back.list_rows is not None
    d, i = _search(handle, back, q)
    np.testing.assert_array_equal(i, i0)
    np.testing.assert_array_equal(d, d0)


def test_compaction_moves_the_rows_with_their_lists(handle, data, built):
    from raft_tpu.serving.rebalancer import rebalance_routed
    _, q = data
    deleted = ann.delete(handle, built[1], np.arange(0, N, 3))
    d0, i0 = _search(handle, deleted, q)
    out = rebalance_routed(handle, deleted)
    assert out is not deleted
    # dead slots dropped from the occupied prefix: the rows moved too
    assert int(np.sum(np.asarray(out.list_indices) <= -2)) < int(
        np.sum(np.asarray(deleted.list_indices) <= -2))
    _assert_rows_placed(data[0], out)
    d, i = _search(handle, out, q)
    np.testing.assert_array_equal(i, i0)
    np.testing.assert_array_equal(d, d0)


def test_rows_leaf_holds_exactly_the_owned_rows(data, built):
    _assert_rows_placed(data[0], built[1])
    np.testing.assert_array_equal(ann.global_list_sizes(built[1]),
                                  np.asarray(built[0].list_sizes))


def _assert_rows_placed(db, routed):
    rows = np.asarray(routed.list_rows)
    li = np.asarray(routed.list_indices)
    pos = np.asarray(routed.row_pos)
    cap, placement = routed.capacity, routed.placement
    held_total = 0
    for s in range(N_DEV):
        lists = placement.shard_lists(s)
        # the shard's slots hold its owned lists at every rank, the
        # dummy slot and the padding after them stay zero
        want = np.zeros_like(rows[s])
        live = li[s] >= 0
        want[live] = db[li[s][live]]
        np.testing.assert_array_equal(rows[s], want)
        assert not rows[s, len(lists):].any()
        held = np.flatnonzero(pos[s] >= 0)
        np.testing.assert_array_equal(np.sort(held), np.sort(li[s][live]))
        flat = rows[s].reshape(-1, DIM)
        np.testing.assert_array_equal(flat[pos[s][held]], db[held])
        assert held.size < N                 # no shard holds every row
        held_total += held.size
    # two copies of every live row
    assert held_total == 2 * np.unique(li[li >= 0]).size
    assert rows.shape[1:] == (placement.n_local + 1, cap, DIM)


def test_refine_refused_without_rows(handle, data, built):
    db, q = data
    no_rows = ann.shard_by_list(handle, built[0], replication_factor=2)
    assert no_rows.list_rows is None
    with pytest.raises(LogicError, match="raw rows"):
        _search(handle, no_rows, q)
    by_row = ann.build(handle, ivf_pq.IndexParams(n_lists=8, pq_dim=16,
                                                  kmeans_n_iters=2),
                       db[:2000])
    with pytest.raises(LogicError, match="by_list"):
        _search(handle, by_row, q)


def test_refined_rows_counter_and_spans(handle, data, built, monkeypatch):
    _, q = data
    names = []
    real = ann._annotation

    def spy(name):
        names.append(name)
        return real(name)
    monkeypatch.setattr(ann, "_annotation", spy)
    obs.enable()
    try:
        before = obs.snapshot()["counters"].get(
            "distributed.routed.refined_rows", 0)
        _search(handle, built[1], q, routing=RoutingPolicy(N_DEV))
        after = obs.snapshot()["counters"].get(
            "distributed.routed.refined_rows", 0)
    finally:
        obs.disable()
    assert after - before == NQ * K * RATIO
    assert names == ["distributed.route", "distributed.dispatch"]


def test_routed_groups_skipped_counter(handle, data, built, monkeypatch):
    """A routed fused search ticks ``distributed.routed.groups_skipped``
    by each shard's all-empty tail (its static grid less the groups its
    owned probes need), summed over the shards, beside
    ``groups_dispatched``.  The fused form is forced on the CPU, its
    kernels in interpret mode."""
    _, q = data
    routed = built[1]
    monkeypatch.setattr(ann, "_platform",
                        types.SimpleNamespace(on_tpu=lambda: True))
    for name in ("_search_impl_fused_recon_grouped",
                 "_search_impl_fused_codes_grouped"):
        monkeypatch.setattr(ivf_pq, name, functools.partial(
            getattr(ivf_pq, name), pallas_interpret=True))
    sp = ivf_pq.SearchParams(n_probes=N_PROBES, scan_mode="fused")
    form = ann._plan(sp, routed, NQ, N_PROBES, K * RATIO).form
    assert form in ("fused_codes", "fused_recon")
    names = ("distributed.routed.groups_dispatched",
             "distributed.routed.groups_skipped")
    obs.enable()
    try:
        reg = obs.registry()
        before = [reg.counter(n).value for n in names]
        ann.search(handle, sp, routed, q, K, refine_ratio=RATIO)
        after = [reg.counter(n).value for n in names]
    finally:
        obs.disable()
    slots = routed.local_centers.shape[1]
    n_groups, _ = grouped.group_capacity(NQ, N_PROBES, slots)
    probes = ivf_pq._select_clusters(routed.coarse_centers, routed.rotation,
                                     q, N_PROBES, routed.metric)
    owner = np.asarray(routed.owner)[np.asarray(probes)]
    local = np.asarray(routed.local_slot)[np.asarray(probes)]
    needed = [int(grouped.num_groups(
        jnp.asarray(np.where(owner == s, local, slots), jnp.int32), slots))
        for s in range(N_DEV)]
    assert after[0] - before[0] == N_DEV * n_groups
    assert after[1] - before[1] == sum(n_groups - n for n in needed) > 0
