"""Replicated durable ingest over the routed index (round 19): owner-
routed writes through the shared coarse quantizer, per-shard WALs with
quorum acks, the two-LSN broadcast-tombstone upsert scheme, typed
Unavailable refusal, the write-path kill matrix at every
``ingest.dist.*`` boundary (zero acked-row loss + bit-identical
post-recovery search at r=2 + zero steady-state recompiles), the
catch-up WAL delta phase, per-shard torn-tail repair at every record
boundary, and the fold under ONE placement-generation bump."""

import os

import jax
import numpy as np
import pytest

from raft_tpu import observability as obs
from raft_tpu.comms import CommsSession
from raft_tpu.core import serialize as ser
from raft_tpu.neighbors import delta as _delta
from raft_tpu.neighbors import ivf_pq, mutate
from raft_tpu.observability import flight
from raft_tpu.resilience import FaultInjected, FaultPlan
from raft_tpu.serving.dist_ingest import (
    DistIngestConfig,
    RoutedIngest,
    Unavailable,
)
from raft_tpu.serving.ingest import scan_wal

# the CI chaos job pins this so a red matrix cell replays the identical
# kill schedule locally
SEED = int(os.environ.get("RAFT_TPU_FAULT_SEED", "20260805"))

DIST_KILL_SITES = ("ingest.dist.route", "ingest.dist.append",
                   "ingest.dist.ack", "ingest.dist.replicate",
                   "ingest.dist.fold", "ingest.dist.catch_up")

N, DIM, NL, NQ, K = 2048, 32, 32, 16, 10

NEW_IDS = np.arange(N, N + 32)
MOVED_IDS = np.arange(N, N + 8)
DEL_BASE = np.arange(40, 45)


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.disable()
    obs.reset()
    flight.clear()
    yield
    obs.disable()
    obs.reset()
    flight.clear()


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches():
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def rhandle():
    devs = jax.devices()
    if len(devs) < 8:
        devs = jax.devices("cpu")
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    mesh = jax.sharding.Mesh(np.asarray(devs[:8]), ("data",))
    s = CommsSession(mesh=mesh, axis_name="data").init()
    yield s.worker_handle(seed=0)
    s.destroy()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    db = rng.normal(size=(N, DIM)).astype(np.float32)
    q = rng.normal(size=(NQ, DIM)).astype(np.float32)
    new_rows = rng.normal(size=(32, DIM)).astype(np.float32)
    moved = rng.normal(size=(8, DIM)).astype(np.float32)
    return db, q, new_rows, moved


@pytest.fixture(scope="module")
def built(rhandle, data):
    from raft_tpu.distributed import ann
    db, _, _, _ = data
    params = ivf_pq.IndexParams(n_lists=NL, pq_dim=8, kmeans_n_iters=3,
                                cache_reconstructions=True)
    base = ivf_pq.build(rhandle, params, db)
    return base, ann.shard_by_list(rhandle, base, replication_factor=2)


def _fresh_tracker():
    from raft_tpu.distributed import health
    return health.HealthTracker(8, health.HealthConfig(
        suspect_after=1, fail_after=1, ok_to_clear=1, dwell_s=0.0))


def _mk(rhandle, built, wal_dir, *, tracker=None, policy=None, **cfg):
    base, routed = built
    ing = RoutedIngest(rhandle, routed, base,
                       config=DistIngestConfig(wal_dir=str(wal_dir),
                                               **cfg),
                       tracker=tracker, policy=policy)
    ing.recover()
    return ing


def _write_stream(ing, data):
    """The shared write sequence every matrix cell replays: two upsert
    batches, a delete touching base ids, and a re-upsert whose vectors
    moved (the two-LSN list-move case)."""
    _, _, new_rows, moved = data
    acked = []
    acked.append(ing.write(NEW_IDS[:16], new_rows[:16]))
    acked.append(ing.write(NEW_IDS[16:], new_rows[16:]))
    acked.append(ing.write(DEL_BASE, op="delete"))
    acked.append(ing.write(MOVED_IDS, moved))
    return acked


def _record_offsets(blob):
    """Byte offset of every framed record in a WAL blob."""
    head = ser._ENVELOPE_HEADER
    offsets = []
    off = 0
    while off < len(blob):
        offsets.append(off)
        _m, _v, length, _crc = head.unpack_from(blob, off)
        off += head.size + length
    assert off == len(blob)
    return offsets


class TestRoutedWritePath:
    def test_upsert_replicates_to_every_owner(self, rhandle, built,
                                              data, tmp_path):
        from raft_tpu.distributed import ann
        _, routed = built
        _, _, new_rows, _ = data
        ing = _mk(rhandle, built, tmp_path / "w")
        lsn = ing.write(NEW_IDS[:16], new_rows[:16])
        assert lsn == 2          # two-LSN scheme: tombstone + upsert
        homes = ann.route_vectors(routed, new_rows[:16])
        owners, _slots = routed.placement.rank_tables()
        for j, i in enumerate(NEW_IDS[:16]):
            g = int(homes[j])
            for rank in range(owners.shape[0]):
                s = int(owners[rank, g])
                assert int(i) in ing.memtables[s]._slot_of, (i, s)
        # the broadcast tombstone lands on EVERY shard (on the owners
        # it doubles as the main-index mask for the upserted id)
        for s in range(8):
            for i in NEW_IDS[:16]:
                assert int(i) in ing.memtables[s]._tombs
        ing.close()

    def test_moved_upsert_leaves_no_stale_copy(self, rhandle, built,
                                               data, tmp_path):
        _, _, new_rows, moved = data
        ing = _mk(rhandle, built, tmp_path / "w")
        ing.write(MOVED_IDS, new_rows[:8])
        ing.write(MOVED_IDS, moved)     # vectors moved: maybe new lists
        # exactly r live copies of each id across ALL memtables — the
        # broadcast tombstone killed every stale copy on old owners
        r = built[1].placement.replication_factor
        for i in MOVED_IDS:
            copies = sum(1 for m in ing.memtables
                         if int(i) in m._slot_of)
            assert copies == r, (i, copies)
        # and the live copies hold the NEW vector
        sp = ivf_pq.SearchParams(n_probes=NL)
        _, ids = ing.search(sp, moved, K)
        np.testing.assert_array_equal(np.asarray(ids)[:, 0], MOVED_IDS)
        ing.close()

    def test_delete_broadcasts_and_masks_main(self, rhandle, built,
                                              data, tmp_path):
        _, q, _, _ = data
        ing = _mk(rhandle, built, tmp_path / "w")
        ing.write(DEL_BASE, op="delete")
        for s in range(8):
            for i in DEL_BASE:
                assert int(i) in ing.memtables[s]._tombs
        sp = ivf_pq.SearchParams(n_probes=NL)
        _, ids = ing.search(sp, q, K)
        assert not np.isin(np.asarray(ids), DEL_BASE).any()
        ing.close()

    def test_unavailable_when_every_replica_down(self, rhandle, built,
                                                 data, tmp_path):
        from raft_tpu.distributed import ann
        _, routed = built
        _, _, new_rows, _ = data
        tr = _fresh_tracker()
        ing = _mk(rhandle, built, tmp_path / "w", tracker=tr)
        vec = new_rows[:1]
        g = int(ann.route_vectors(routed, vec)[0])
        owners, _ = routed.placement.rank_tables()
        for rank in range(owners.shape[0]):
            s = int(owners[rank, g])
            tr.note_timeout(s)
            tr.note_timeout(s)      # suspect -> failed
        sizes_before = [os.path.getsize(ing.wal_path(s))
                        for s in range(8)]
        with obs.collecting():
            with pytest.raises(Unavailable):
                ing.write(np.asarray([N]), vec)
            assert obs.registry().counter(
                "serving.ingest.dist.unavailable").value == 1
        # refused BEFORE any WAL byte anywhere
        assert sizes_before == [os.path.getsize(ing.wal_path(s))
                                for s in range(8)]
        ev = flight.events("serving.ingest.dist.unavailable")
        assert ev and g in ev[-1]["attrs"]["lists"]
        ing.close()

    def test_quorum_one_acks_with_a_replica_down(self, rhandle, built,
                                                 data, tmp_path):
        from raft_tpu.distributed import ann
        _, routed = built
        _, _, new_rows, _ = data
        tr = _fresh_tracker()
        ing = _mk(rhandle, built, tmp_path / "w", tracker=tr,
                  write_quorum=1)
        vec = new_rows[:1]
        g = int(ann.route_vectors(routed, vec)[0])
        owners, _ = routed.placement.rank_tables()
        dead = int(owners[0, g])
        tr.note_timeout(dead)
        tr.note_timeout(dead)
        lsn = ing.write(np.asarray([N]), vec)
        assert lsn > 0
        # the row is readable from the surviving replica (masked view
        # for the dead shard; id<0 seam + k-bounded merge)
        sp = ivf_pq.SearchParams(n_probes=NL)
        _, ids = ing.search(sp, vec, K)
        assert int(np.asarray(ids)[0, 0]) == N
        ing.close()

    def test_leader_append_failure_fails_ack_under_full_quorum(
            self, rhandle, built, data, tmp_path):
        _, _, new_rows, _ = data
        tr = _fresh_tracker()
        ing = _mk(rhandle, built, tmp_path / "w", tracker=tr)
        with FaultPlan(seed=SEED).at("ingest.dist.append",
                                     times=1).active():
            with pytest.raises(FaultInjected):
                ing.write(np.asarray([N]), new_rows[:1])
        # the leader took a write-error strike (hard evidence)
        assert any(st in ("SUSPECT", "FAILED") for st in tr.states())
        assert flight.events("serving.ingest.dist.write_error")
        # idempotent retry acks once the fault clears
        assert ing.write(np.asarray([N]), new_rows[:1]) > 0
        ing.close()

    def test_all_fsyncs_failing_fails_ack(self, rhandle, built, data,
                                          tmp_path):
        """Satellite: the per-shard WALs inherit the ``ingest.fsync``
        failure path — a sync that raises fails the ack for every row
        riding that shard's group commit."""
        _, _, new_rows, _ = data
        ing = _mk(rhandle, built, tmp_path / "w")
        with FaultPlan(seed=SEED).at("ingest.fsync", times=8).active():
            with pytest.raises(FaultInjected):
                ing.write(NEW_IDS[:4], new_rows[:4])
        assert ing.write(NEW_IDS[:4], new_rows[:4]) > 0
        ing.close()


class TestKillMatrix:
    """The acceptance matrix: a seed-pinned single-shard kill at every
    ``ingest.dist.*`` boundary, r=2 — every acked row survives, the
    recovered full-probe search is bit-identical to the never-killed
    control, and the fail -> catch-up -> readmit arc triggers zero
    steady-state recompiles."""

    KILL_SHARD = 2

    @pytest.fixture(scope="class")
    def control(self, rhandle, built, data, tmp_path_factory):
        _, q, _, moved = data
        ing = _mk(rhandle, built,
                  tmp_path_factory.mktemp("ctl") / "w")
        acked = _write_stream(ing, data)
        assert all(a > 0 for a in acked)
        sp = ivf_pq.SearchParams(n_probes=NL)
        d1, i1 = ing.search(sp, q, K)
        d2, i2 = ing.search(sp, moved, K)
        np.testing.assert_array_equal(np.asarray(i2)[:, 0], MOVED_IDS)
        assert not np.isin(np.asarray(i1), DEL_BASE).any()
        ing.close()
        return (np.asarray(d1), np.asarray(i1), np.asarray(d2),
                np.asarray(i2))

    def _drop_shard_state(self, ing, s):
        """Simulate the killed shard's process loss: its WAL bytes and
        memtable are gone."""
        if ing._wals[s] is not None:
            ing._wals[s].close()
            ing._wals[s] = None
        os.unlink(ing.wal_path(s))
        ing.memtables[s].reset()

    @pytest.mark.parametrize("site", DIST_KILL_SITES)
    def test_kill_matrix_zero_acked_loss_bit_identical(
            self, rhandle, built, data, control, tmp_path, site):
        from raft_tpu.distributed import health
        _, q, _, moved = data
        s = self.KILL_SHARD
        tr = _fresh_tracker()
        ing = _mk(rhandle, built, tmp_path / "w", tracker=tr)
        sp = ivf_pq.SearchParams(n_probes=NL)
        plan = FaultPlan(seed=SEED).kill_shard_at(site, s)
        if site == "ingest.dist.catch_up":
            # this site only fires inside the delta phase below
            acked = _write_stream(ing, data)
            tr.note_timeout(s)
            tr.note_timeout(s)
        else:
            with plan.active():
                # kill_shard_at is a membership change, not an
                # exception: every write still acks (the quorum
                # re-plans onto survivors once the kill is observed)
                acked = _write_stream(ing, data)
                if site == "ingest.dist.fold":
                    assert ing.fold() is not None
                tr.note_timeout(s)
                tr.note_timeout(s)   # the decision loop declares FAILED
        assert all(a > 0 for a in acked)
        assert s in tr.failed_shards()
        self._drop_shard_state(ing, s)
        # acked rows remain visible while the shard is down (replicas
        # hold every acked row; the dead shard joins as a masked view)
        _, ids_down = ing.search(sp, moved, K)
        np.testing.assert_array_equal(np.asarray(ids_down)[:, 0],
                                      MOVED_IDS)
        # catch-up delta phase + canary-gated readmission
        if site == "ingest.dist.catch_up":
            with plan.active():
                caught = health.catch_up(rhandle, ing.index, s,
                                         tracker=tr, ingest=ing)
        else:
            caught = health.catch_up(rhandle, ing.index, s, tracker=tr,
                                     ingest=ing)
        assert health.readmit(rhandle, ing, caught, s, tracker=tr)
        assert s not in tr.failed_shards()
        assert flight.events("serving.ingest.dist.catch_up")
        d1, i1 = ing.search(sp, q, K)
        d2, i2 = ing.search(sp, moved, K)
        if site == "ingest.dist.fold":
            # the fold drained the delta tier into the index: the same
            # rows answer, now from the folded main
            np.testing.assert_array_equal(np.asarray(i2)[:, 0],
                                          MOVED_IDS)
            assert not np.isin(np.asarray(i1), DEL_BASE).any()
        else:
            cd1, ci1, cd2, ci2 = control
            np.testing.assert_array_equal(np.asarray(i1), ci1)
            np.testing.assert_allclose(np.asarray(d1), cd1,
                                       rtol=0, atol=0)
            np.testing.assert_array_equal(np.asarray(i2), ci2)
            np.testing.assert_allclose(np.asarray(d2), cd2,
                                       rtol=0, atol=0)
        # the kill really fired at the scripted site
        assert sum(spec.fired for spec in plan.specs) == 1
        ing.close()

    def test_failover_write_read_zero_recompiles(self, rhandle, built,
                                                 data, tmp_path):
        """Routing tables and memtable views are data, not shape: the
        fail -> re-plan -> read arc reuses every warmed executable."""
        _, q, _, moved = data
        tr = _fresh_tracker()
        ing = _mk(rhandle, built, tmp_path / "w", tracker=tr)
        sp = ivf_pq.SearchParams(n_probes=NL)
        _write_stream(ing, data)
        assert ing.prewarm([1, 8, 16]) > 0
        ing.search(sp, q, K)                 # warm healthy read
        ing.search(sp, moved, K)
        s = self.KILL_SHARD
        tr.note_timeout(s)
        tr.note_timeout(s)
        ing.search(sp, q, K)                 # warm the masked-view read
        ing.search(sp, moved, K)
        with obs.collecting():
            c0 = obs.registry().counter("xla.compiles").value
            ing.write(NEW_IDS[:16] + 100, data[2][:16])   # re-routed
            _, _i = ing.search(sp, q, K)
            _, i_moved = ing.search(sp, moved, K)
            c1 = obs.registry().counter("xla.compiles").value
        assert c1 == c0, f"{c1 - c0} recompiles across write failover"
        np.testing.assert_array_equal(np.asarray(i_moved)[:, 0],
                                      MOVED_IDS)
        ing.close()


class TestTornTail:
    def test_torn_tail_repair_at_every_record_boundary(
            self, rhandle, built, data, tmp_path):
        """Per-shard WALs inherit the PR 13 torn-tail classes: cut one
        shard's log mid-record at EVERY record boundary — recover()
        repairs the tail, replays the intact prefix, and the memtable
        matches an independent replay of the same prefix."""
        ing = _mk(rhandle, built, tmp_path / "w")
        _write_stream(ing, data)
        s = 0
        path = ing.wal_path(s)
        ing.close()
        with open(path, "rb") as f:
            blob = f.read()
        records, good_end = scan_wal(blob)
        assert good_end == len(blob) and records
        offsets = _record_offsets(blob)
        assert len(offsets) == len(records)
        bounds = offsets + [len(blob)]
        for j, start in enumerate(offsets):
            # tear record j roughly mid-frame: records[:j] stay intact
            cut = start + max(1, (bounds[j + 1] - start) // 2)
            with open(path, "wb") as f:
                f.write(blob[:cut])
            ing2 = _mk(rhandle, built, tmp_path / "w")
            ref = _delta.Memtable(DIM, capacity=1024,
                                  tomb_capacity=1024,
                                  metric=ing2.metric)
            for rec in records[:j]:
                ref.apply(rec)
            assert ing2.memtables[s].digest() == ref.digest(), j
            # the repaired log is clean: exactly the intact prefix
            with open(path, "rb") as f:
                repaired = f.read()
            recs2, end2 = scan_wal(repaired)
            assert end2 == len(repaired) and len(recs2) == j
            ing2.close()
        with open(path, "wb") as f:
            f.write(blob)       # restore the intact log


class TestFoldAndRecover:
    def test_fold_one_placement_generation_bump(self, rhandle, built,
                                                data, tmp_path):
        _, q, _, moved = data
        ing = _mk(rhandle, built, tmp_path / "w")
        _write_stream(ing, data)
        g_idx = mutate.generation(ing.index)
        g_pl = ing.index.placement.generation
        with obs.collecting():
            out = ing.fold()
            assert obs.registry().counter(
                "serving.ingest.dist.folds").value == 1
        assert out is not None
        assert ing.index.placement.generation == g_pl + 1
        assert mutate.generation(ing.index) == g_idx + 1
        # every shard WAL truncated, every memtable drained
        assert ing.stats()["wal_bytes"] == [0] * 8
        assert all(m.live_rows == 0 and m.n_tombstones == 0
                   for m in ing.memtables)
        sp = ivf_pq.SearchParams(n_probes=NL)
        _, ids = ing.search(sp, moved, K)
        np.testing.assert_array_equal(np.asarray(ids)[:, 0], MOVED_IDS)
        _, ids_q = ing.search(sp, q, K)
        assert not np.isin(np.asarray(ids_q), DEL_BASE).any()
        ev = flight.events("serving.ingest.dist.fold")
        assert ev and ev[-1]["attrs"]["placement_generation"] == g_pl + 1
        ing.close()

    def test_recover_rolls_forward_after_commit_marker(
            self, rhandle, built, data, tmp_path):
        """A kill between the commit marker and the truncations rolls
        FORWARD: the checkpointed candidate serves, truncations
        finish."""
        _, q, _, moved = data
        ing = _mk(rhandle, built, tmp_path / "w")
        _write_stream(ing, data)
        # the fold dies on the FIRST per-shard truncation — after the
        # commit marker and the publish
        with FaultPlan(seed=SEED).at("ingest.truncate",
                                     times=1).active():
            with pytest.raises(FaultInjected):
                ing.fold()
        ing.close()
        ing2 = _mk(rhandle, built, tmp_path / "w")
        ev = flight.events("serving.ingest.dist.replay")
        assert ev and ev[-1]["attrs"]["rolled_forward"] is True
        assert ing2.stats()["wal_bytes"] == [0] * 8
        sp = ivf_pq.SearchParams(n_probes=NL)
        _, ids = ing2.search(sp, moved, K)
        np.testing.assert_array_equal(np.asarray(ids)[:, 0], MOVED_IDS)
        _, ids_q = ing2.search(sp, q, K)
        assert not np.isin(np.asarray(ids_q), DEL_BASE).any()
        ing2.close()

    def test_recover_rolls_back_before_commit_marker(
            self, rhandle, built, data, tmp_path):
        """A kill at the fold boundary (before the marker) rolls BACK:
        the base index is untouched and the per-shard replay reproduces
        every logged record bit-identically."""
        _, _, _, moved = data
        ing = _mk(rhandle, built, tmp_path / "w")
        _write_stream(ing, data)
        digests = [m.digest() for m in ing.memtables]
        last = ing.stats()["last_lsn"]
        with FaultPlan(seed=SEED).at("ingest.dist.fold",
                                     times=1).active():
            with pytest.raises(FaultInjected):
                ing.fold()
        ing.close()
        ing2 = _mk(rhandle, built, tmp_path / "w")
        assert [m.digest() for m in ing2.memtables] == digests
        assert ing2.stats()["last_lsn"] == last
        sp = ivf_pq.SearchParams(n_probes=NL)
        _, ids = ing2.search(sp, moved, K)
        np.testing.assert_array_equal(np.asarray(ids)[:, 0], MOVED_IDS)
        ing2.close()

    def test_catch_up_filters_to_owned_lists(self, rhandle, built,
                                             data, tmp_path):
        from raft_tpu.distributed import ann
        _, _, new_rows, _ = data
        ing = _mk(rhandle, built, tmp_path / "w")
        ing.write(NEW_IDS[:16], new_rows[:16])
        s = 1
        before = ing.memtables[s].digest()
        kept = ing.catch_up_shard(s)
        assert kept > 0
        # a catch-up of an up-to-date shard is a no-op on its state:
        # the rebuilt WAL + memtable reproduce what it already held
        assert ing.memtables[s].digest() == before
        homes = ann.route_vectors(ing.index, new_rows[:16])
        owned = set(int(g) for g in
                    ing.index.placement.shard_lists(s))
        for j, i in enumerate(NEW_IDS[:16]):
            should = int(homes[j]) in owned
            assert (int(i) in ing.memtables[s]._slot_of) == should
        ing.close()
