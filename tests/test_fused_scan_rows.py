"""Row-addressed fused IVF-PQ list scans (interpret mode on CPU).

The fused recon and codes kernels address the query table and the
per-query top-k accumulator by row.  Against the non-fused kernel of the
same path plus a host-side stable sort at matched kt, their answers must
be bit-equal: values at every rank, ids at every live rank (exhausted
ranks carry the ``_ACC_WORST`` sentinel value).

Geometry: 127 queries, so the last real query sits at query-table row
``nq_pad - 2`` next to the padding row; 9 lists of capacity 32 (last 5
rows tombstoned), 5 probes per query, every query probing list 0, so
one group is full but for one slot; the static group capacity leaves
all-sentinel tail groups; list rows come in runs of three identical
rows, so equal distances straddle the k-th rank.

The same pairs also run on a grid whose all-empty tail is most of its
groups, which must answer bit for bit as the grid of exactly the live
groups, and on a grid with no real slot at all, which must return only
the sentinel pair: the kernels skip every step past the live groups.
"""

import inspect
import types

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import DeviceResources
from raft_tpu import observability as obs
from raft_tpu.neighbors import grouped
from raft_tpu.neighbors import ivf_pq
from raft_tpu.ops import pq_code_scan_pallas as pcs
from raft_tpu.ops import pq_group_scan_pallas as pgs
from raft_tpu.ops import vmem_budget as vb

N_LISTS, CAP, ROT, NQ, N_PROBES, KT = 9, 32, 128, 127, 5, 16
PQ_DIM, PQ_BITS = 16, 8


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(7)
    probes = np.stack([np.concatenate(
        [[0], rng.choice(np.arange(1, N_LISTS), N_PROBES - 1,
                         replace=False)]) for _ in range(NQ)])
    probes = probes.astype(np.int32)
    n_groups, _ = grouped.group_capacity(NQ, N_PROBES, N_LISTS)
    n_live = int(grouped.num_groups(jnp.asarray(probes), N_LISTS))
    # the capacity grid, the exact live grid and a grid whose tail is
    # three quarters of its groups
    grids = {name: grouped.build_groups(jnp.asarray(probes), N_LISTS, n)
             for name, n in (("capacity", n_groups), ("live", n_live),
                             ("long_tail", 4 * n_live))}
    gl, sp = grids["capacity"]
    P = NQ * N_PROBES
    book, pq_len = 1 << PQ_BITS, ROT // PQ_DIM
    runs = rng.integers(0, book, (N_LISTS, -(-CAP // 3), PQ_DIM))
    codes = np.repeat(runs, 3, axis=1)[:, :CAP].astype(np.uint8)
    codebooks = rng.standard_normal((PQ_DIM, book, pq_len)).astype(
        np.float32)
    recon = codebooks[np.arange(PQ_DIM), codes].reshape(N_LISTS, CAP, ROT)
    recon = jnp.asarray(recon, jnp.bfloat16)
    ids = rng.permutation(1 << 20)[:N_LISTS * CAP].reshape(N_LISTS, CAP)
    ids[:, -5:] = -1
    adm = rng.integers(0, 1 << 32, (4 * n_live, grouped.GROUP, CAP // 32),
                       dtype=np.uint32).view(np.int32)
    return dict(
        gl=gl, sp=sp, P=P, n_live=n_live, grids=grids, qrot=jnp.asarray(
            rng.standard_normal((NQ, ROT)).astype(np.float32)),
        centers=jnp.asarray(
            rng.standard_normal((N_LISTS, ROT)).astype(np.float32)),
        recon=recon, rsq=jnp.sum(jnp.asarray(recon, jnp.float32) ** 2, -1),
        codes=pcs.pack_code_lanes(jnp.asarray(codes)),
        codebooks=jnp.asarray(codebooks), ids=jnp.asarray(ids),
        adm=jnp.asarray(adm), refs={})


def _head(c, path, gl=None, sp=None):
    gl = c["gl"] if gl is None else gl
    sp = c["sp"] if sp is None else sp
    if path == "recon":
        return (gl, sp, c["qrot"], c["centers"], c["recon"], c["rsq"],
                c["ids"])
    return (gl, sp, c["qrot"], c["centers"], c["codes"], c["codebooks"],
            c["rsq"], c["ids"])


def _fused(c, path, k, filtered, grid="capacity"):
    """The fused kernel's raw (vals, ids) over one of the case's grids;
    ``empty`` is the capacity grid with every slot the sentinel."""
    gl, sp = c["grids"]["capacity" if grid == "empty" else grid]
    if grid == "empty":
        sp = jnp.full_like(sp, c["P"])
    adm = c["adm"][:gl.shape[0]] if filtered else None
    if path == "recon":
        out = pgs.grouped_l2_scan_fused(*_head(c, path, gl, sp), KT, k,
                                        N_PROBES, interpret=True,
                                        adm_words=adm)
    else:
        out = pcs.grouped_code_scan_fused(*_head(c, path, gl, sp), KT, k,
                                          N_PROBES, PQ_BITS,
                                          interpret=True, adm_words=adm)
    return tuple(np.asarray(x) for x in out)


def _reference(c, path, filtered):
    """Per-query candidates of the non-fused kernel, host-sorted
    (stable) in probe order: list of (values, ids) per query."""
    key = (path, filtered)
    if key not in c["refs"]:
        adm = c["adm"][:c["gl"].shape[0]] if filtered else None
        if path == "recon":
            nv, ni = pgs.grouped_l2_scan(*_head(c, path), KT, N_PROBES,
                                         interpret=True, adm_words=adm)
        else:
            nv, ni = pcs.grouped_code_scan(*_head(c, path), KT, N_PROBES,
                                           PQ_BITS, interpret=True,
                                           adm_words=adm)
        outd, outi = grouped.scatter_packed(nv, ni, c["sp"], c["P"], True)
        outd, outi = np.asarray(outd), np.asarray(outi)
        per_q = []
        for q in range(NQ):
            cd = outd[q * N_PROBES:(q + 1) * N_PROBES].reshape(-1)
            ci = outi[q * N_PROBES:(q + 1) * N_PROBES].reshape(-1)
            fin = np.isfinite(cd)
            order = np.argsort(cd[fin], kind="stable")
            per_q.append((cd[fin][order], ci[fin][order]))
        c["refs"][key] = per_q
    return c["refs"][key]


def test_geometry(case):
    """The edges the parity cases rely on are really there."""
    sp = np.asarray(case["sp"])
    assert vb.nq_padded(NQ) - 2 == NQ - 1
    assert (sp == case["P"]).all(axis=1).any()        # all-sentinel tail
    assert ((sp < case["P"]).sum(axis=1) == NQ).any()  # list 0's group
    for name, (_, slots) in case["grids"].items():
        live = pgs.live_groups(pgs.slot_query_rows(
            slots, N_PROBES, case["P"], vb.nq_padded(NQ)))
        assert int(live[0]) == case["n_live"], name
    assert 4 * case["n_live"] > sp.shape[0] > case["n_live"]


def test_slot_query_rows():
    """Row 0 maps each slot to its query row (an empty slot to the
    padding row); row 1 holds one past the group's last real slot, an
    empty slot inside the run included."""
    G, P, n_probes, nq_pad = grouped.GROUP, 50, 5, 128
    sp = np.full((3, G), P, np.int32)
    sp[0, :4] = [0, 7, 12, 49]
    sp[1, [0, 2]] = [5, 10]
    rows = np.asarray(pgs.slot_query_rows(jnp.asarray(sp), n_probes, P,
                                          nq_pad))
    assert rows.shape == (3, 2, G) and rows.dtype == np.int32
    np.testing.assert_array_equal(rows[0, 0, :5], [0, 1, 2, 9, 127])
    np.testing.assert_array_equal(rows[1, 0, :3], [1, 127, 2])
    assert (rows[2, 0] == nq_pad - 1).all()
    np.testing.assert_array_equal(rows[:, 1], np.repeat([[4], [3], [0]], G,
                                                        axis=1))


# (path, k, filtered, grid): every k on the capacity grid; the cell's
# k on the long-tail grid and on the all-empty one
PARITY_CASES = ([(p, k, f, "capacity") for p in ("recon", "codes")
                 for k in (10, 20, 64, 128) for f in (False, True)]
                + [(p, 20, f, g) for g in ("long_tail", "empty")
                   for p in ("recon", "codes") for f in (False, True)])


@pytest.mark.parametrize(
    "path,k,filtered,grid", PARITY_CASES,
    ids=["-".join(map(str, c[:3] if c[3] == "capacity" else c))
         for c in PARITY_CASES])
def test_fused_rows_match_unfused_sort(case, path, k, filtered, grid):
    v, i = _fused(case, path, k, filtered, grid)
    assert v.shape == (vb.nq_padded(NQ), k) and i.dtype == np.int32
    if grid == "empty":
        # what a routed shard that owns none of the probed lists sees
        assert (v == pgs._ACC_WORST).all() and (i == -1).all()
        return
    if grid == "long_tail":
        v_live, i_live = _fused(case, path, k, filtered, "live")
        np.testing.assert_array_equal(v, v_live)
        np.testing.assert_array_equal(i, i_live)
    ties = 0
    for q, (rd, ri) in enumerate(_reference(case, path, filtered)):
        n = min(k, rd.size)
        np.testing.assert_array_equal(v[q, :n], rd[:n])
        np.testing.assert_array_equal(i[q, :n], ri[:n])
        assert (v[q, n:] == pgs._ACC_WORST).all()
        ties += int(rd.size > k and rd[k - 1] == rd[k])
    if k <= 20:
        assert ties > 0          # a tie straddles the k-th rank somewhere


@pytest.fixture(scope="module")
def small_index():
    rng = np.random.default_rng(26)
    db = rng.standard_normal((3000, ROT)).astype(np.float32)
    q = rng.standard_normal((60, ROT)).astype(np.float32)
    res = DeviceResources()
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=32, kmeans_n_iters=2)
    return res, ivf_pq.build(res, params, db), q


@pytest.mark.parametrize("collect", [True, False])
def test_skip_counters_tick_by_the_tail(small_index, monkeypatch, collect):
    """A fused search ticks ``ivf_pq.search.groups_dispatched`` by its
    grid and ``ivf_pq.search.groups_skipped`` by the grid's all-empty
    tail (n_groups - n_live, n_live as the kernel counts it) while
    collection is on, and neither while it is off.  The fused dispatch
    is forced on the CPU, its kernel in interpret mode."""
    res, index, q = small_index
    monkeypatch.setattr(ivf_pq, "_platform",
                        types.SimpleNamespace(on_tpu=lambda: True))
    seen = []

    def spy(real):
        sig = inspect.signature(real)

        def run(*args, **kw):
            bound = sig.bind(*args, **kw).arguments
            seen.append((bound["probes"], bound["n_groups"]))
            return real(*args, **kw, pallas_interpret=True)
        return run

    for name in ("_search_impl_fused_recon_grouped",
                 "_search_impl_fused_codes_grouped"):
        monkeypatch.setattr(ivf_pq, name, spy(getattr(ivf_pq, name)))
    names = ("ivf_pq.search.groups_dispatched",
             "ivf_pq.search.groups_skipped")
    reg = obs.registry()
    before = [reg.counter(n).value for n in names]
    was = obs.enabled()
    (obs.enable if collect else obs.disable)()
    try:
        sp = ivf_pq.SearchParams(n_probes=4, scan_mode="fused")
        ivf_pq.search(res, sp, index, q, 10)
    finally:
        (obs.enable if was else obs.disable)()
    after = [reg.counter(n).value for n in names]
    (probes, n_groups), = seen
    nq, n_probes = probes.shape
    _, slots = grouped.build_groups(probes, index.n_lists, n_groups)
    n_live = int(pgs.live_groups(pgs.slot_query_rows(
        slots, n_probes, nq * n_probes, vb.nq_padded(nq)))[0])
    assert 0 < n_live < n_groups
    if collect:
        assert after[0] - before[0] == n_groups
        assert after[1] - before[1] == n_groups - n_live
    else:
        assert after == before
