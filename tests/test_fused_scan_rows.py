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
"""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.neighbors import grouped
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
    gl, sp = grouped.build_groups(jnp.asarray(probes), N_LISTS, n_groups)
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
    adm = rng.integers(0, 1 << 32, (n_groups, grouped.GROUP, CAP // 32),
                       dtype=np.uint32).view(np.int32)
    return dict(
        gl=gl, sp=sp, P=P, qrot=jnp.asarray(
            rng.standard_normal((NQ, ROT)).astype(np.float32)),
        centers=jnp.asarray(
            rng.standard_normal((N_LISTS, ROT)).astype(np.float32)),
        recon=recon, rsq=jnp.sum(jnp.asarray(recon, jnp.float32) ** 2, -1),
        codes=pcs.pack_code_lanes(jnp.asarray(codes)),
        codebooks=jnp.asarray(codebooks), ids=jnp.asarray(ids),
        adm=jnp.asarray(adm), refs={})


def _head(c, path):
    if path == "recon":
        return (c["gl"], c["sp"], c["qrot"], c["centers"], c["recon"],
                c["rsq"], c["ids"])
    return (c["gl"], c["sp"], c["qrot"], c["centers"], c["codes"],
            c["codebooks"], c["rsq"], c["ids"])


def _reference(c, path, filtered):
    """Per-query candidates of the non-fused kernel, host-sorted
    (stable) in probe order: list of (values, ids) per query."""
    key = (path, filtered)
    if key not in c["refs"]:
        adm = c["adm"] if filtered else None
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


@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("k", [10, 20, 64, 128])
@pytest.mark.parametrize("path", ["recon", "codes"])
def test_fused_rows_match_unfused_sort(case, path, k, filtered):
    adm = case["adm"] if filtered else None
    if path == "recon":
        v, i = pgs.grouped_l2_scan_fused(*_head(case, path), KT, k,
                                         N_PROBES, interpret=True,
                                         adm_words=adm)
    else:
        v, i = pcs.grouped_code_scan_fused(*_head(case, path), KT, k,
                                           N_PROBES, PQ_BITS,
                                           interpret=True, adm_words=adm)
    v, i = np.asarray(v), np.asarray(i)
    assert v.shape == (vb.nq_padded(NQ), k) and i.dtype == np.int32
    ties = 0
    for q, (rd, ri) in enumerate(_reference(case, path, filtered)):
        n = min(k, rd.size)
        np.testing.assert_array_equal(v[q, :n], rd[:n])
        np.testing.assert_array_equal(i[q, :n], ri[:n])
        assert (v[q, n:] == pgs._ACC_WORST).all()
        ties += int(rd.size > k and rd[k - 1] == rd[k])
    if k <= 20:
        assert ties > 0          # a tie straddles the k-th rank somewhere
