"""Durable streaming ingest: WAL framing/corruption classes, memtable
semantics (incl. the same-id churn regression), the fsync-before-ack
write path, the kill-at-every-boundary recovery matrix (bit-identical
replay, no acked write lost), write-path backpressure/quota/brownout
shedding, the checkpointed fold lifecycle, and the zero-steady-state-
recompile contract with the delta tier attached."""

import io
import os
import struct
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import DeviceResources, serving
from raft_tpu import observability as obs
from raft_tpu.core.error import RaftError
from raft_tpu.core.serialize import CorruptIndexError
from raft_tpu.distance.types import DistanceType
from raft_tpu.neighbors import delta, ivf_flat, mutate
from raft_tpu.observability import flight, trace
from raft_tpu.resilience import FaultInjected, FaultPlan
from raft_tpu.serving import ingest
from raft_tpu.serving.brownout import BrownoutState

# the CI chaos job pins this so a red matrix cell replays the identical
# kill schedule locally
SEED = int(os.environ.get("RAFT_TPU_FAULT_SEED", "20260805"))

KILL_SITES = ("ingest.append", "ingest.apply", "ingest.fsync",
              "ingest.fold", "ingest.truncate")


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
def res():
    return DeviceResources(seed=42)


DIM = 16


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(3)
    db = rng.normal(size=(2000, DIM)).astype(np.float32)
    q = rng.normal(size=(8, DIM)).astype(np.float32)
    return db, q


@pytest.fixture(scope="module")
def flat_index(res, dataset):
    db, _ = dataset
    return ivf_flat.build(
        res, ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4),
        jnp.asarray(db))


def _ingest(tmp_path, res=None, **cfg):
    cfg.setdefault("wal_dir", str(tmp_path / "wal"))
    cfg.setdefault("memtable_capacity", 32)
    cfg.setdefault("tomb_capacity", 32)
    srv = ingest.IngestServer(res, ingest.IngestConfig(**cfg), dim=DIM)
    return srv


def _rows(rng, n):
    return rng.normal(size=(n, DIM)).astype(np.float32)


# ---------------------------------------------------------------------------
# WAL framing + the corruption classes


class TestWalFraming:
    def test_encode_scan_round_trip(self):
        rng = np.random.default_rng(0)
        recs = [
            ingest.encode_record(1, delta.OP_UPSERT, np.array([4, 7]),
                                 _rows(rng, 2)),
            ingest.encode_record(2, delta.OP_DELETE, np.array([4]), None),
            ingest.encode_record(3, delta.OP_UPSERT, np.array([9]),
                                 _rows(rng, 1)),
        ]
        out, end = ingest.scan_wal(b"".join(recs))
        assert [r.lsn for r in out] == [1, 2, 3]
        assert [r.op for r in out] == [delta.OP_UPSERT, delta.OP_DELETE,
                                       delta.OP_UPSERT]
        assert end == sum(len(r) for r in recs)
        np.testing.assert_array_equal(out[0].ids, [4, 7])
        assert out[1].vectors is None
        assert out[2].vectors.shape == (1, DIM)

    def test_torn_tail_truncated_not_raised(self):
        rng = np.random.default_rng(1)
        good = ingest.encode_record(1, delta.OP_UPSERT, np.array([1]),
                                    _rows(rng, 1))
        torn = ingest.encode_record(2, delta.OP_UPSERT, np.array([2]),
                                    _rows(rng, 1))[:-7]
        out, end = ingest.scan_wal(good + torn)
        assert [r.lsn for r in out] == [1]
        assert end == len(good)

    def test_short_header_at_eof_is_torn(self):
        rng = np.random.default_rng(1)
        good = ingest.encode_record(1, delta.OP_UPSERT, np.array([1]),
                                    _rows(rng, 1))
        out, end = ingest.scan_wal(good + b"RT")
        assert len(out) == 1 and end == len(good)

    def test_garbage_tail_without_magic_is_torn(self):
        rng = np.random.default_rng(1)
        good = ingest.encode_record(1, delta.OP_UPSERT, np.array([1]),
                                    _rows(rng, 1))
        out, end = ingest.scan_wal(good + b"\x00" * 40)
        assert len(out) == 1 and end == len(good)

    def test_crc_flip_on_final_record_is_torn(self):
        rng = np.random.default_rng(2)
        a = ingest.encode_record(1, delta.OP_UPSERT, np.array([1]),
                                 _rows(rng, 1))
        b = bytearray(ingest.encode_record(2, delta.OP_UPSERT,
                                           np.array([2]), _rows(rng, 1)))
        b[-1] ^= 0xFF              # payload bit flip -> CRC mismatch
        out, end = ingest.scan_wal(a + bytes(b))
        assert [r.lsn for r in out] == [1]
        assert end == len(a)

    def test_crc_flip_mid_log_raises_with_offset(self):
        rng = np.random.default_rng(2)
        a = ingest.encode_record(1, delta.OP_UPSERT, np.array([1]),
                                 _rows(rng, 1))
        b = bytearray(ingest.encode_record(2, delta.OP_UPSERT,
                                           np.array([2]), _rows(rng, 1)))
        b[-1] ^= 0xFF
        c = ingest.encode_record(3, delta.OP_DELETE, np.array([9]), None)
        with pytest.raises(CorruptIndexError, match=f"offset {len(a)}"):
            ingest.scan_wal(a + bytes(b) + c)

    def test_frame_garbage_mid_log_raises(self):
        rng = np.random.default_rng(2)
        a = ingest.encode_record(1, delta.OP_UPSERT, np.array([1]),
                                 _rows(rng, 1))
        c = ingest.encode_record(2, delta.OP_DELETE, np.array([9]), None)
        # junk between two otherwise-intact records: real corruption
        with pytest.raises(CorruptIndexError, match=f"offset {len(a)}"):
            ingest.scan_wal(a + b"\xde\xad\xbe\xef" * 4 + c)

    def test_valid_crc_bad_op_raises(self):
        from raft_tpu.core import serialize as ser
        payload = struct.pack("<QBII", 1, 99, 1, 0) + np.int64([4]).tobytes()
        buf = io.BytesIO()
        ser.write_envelope(buf, payload)
        with pytest.raises(CorruptIndexError, match="unknown op"):
            ingest.scan_wal(buf.getvalue())

    def test_repair_tail_truncates_file(self, tmp_path):
        rng = np.random.default_rng(4)
        srv = _ingest(tmp_path)
        srv.recover()
        srv.write(np.array([1]), _rows(rng, 1))
        srv.write(np.array([2]), _rows(rng, 1))
        srv.close()
        path = srv.wal_path
        size = os.path.getsize(path)
        with open(path, "ab") as f:
            f.write(b"RTIE\x01\x00partialgarbage")
        srv2 = _ingest(tmp_path)
        srv2.recover()
        assert os.path.getsize(path) == size
        assert srv2.memtable.live_rows == 2
        evs = flight.events("serving.ingest.replay")
        assert evs and evs[0]["attrs"]["truncated_bytes"] > 0
        srv2.close()


# ---------------------------------------------------------------------------
# memtable semantics


class TestMemtable:
    def test_upsert_search_delete(self):
        mt = delta.Memtable(DIM, capacity=8, tomb_capacity=8)
        v = np.full((1, DIM), 2.0, np.float32)
        mt.apply(delta.Record(lsn=1, op=delta.OP_UPSERT,
                              ids=np.array([11]), vectors=v))
        d, i = mt.search(v, 3)
        assert int(np.asarray(i)[0, 0]) == 11
        assert float(np.asarray(d)[0, 0]) == pytest.approx(0.0, abs=1e-5)
        mt.apply(delta.Record(lsn=2, op=delta.OP_DELETE,
                              ids=np.array([11])))
        _, i2 = mt.search(v, 3)
        assert (np.asarray(i2) == -1).all()
        assert mt.live_rows == 0 and mt.n_tombstones == 1

    def test_duplicate_lsn_is_noop(self):
        mt = delta.Memtable(DIM, capacity=8, tomb_capacity=8)
        rec = delta.Record(lsn=1, op=delta.OP_UPSERT, ids=np.array([1]),
                           vectors=np.ones((1, DIM), np.float32))
        assert mt.apply(rec) is True
        d0 = mt.digest()
        assert mt.apply(rec) is False
        assert mt.digest() == d0

    def test_regrow_preserves_rows_and_bumps_generation(self):
        rng = np.random.default_rng(5)
        mt = delta.Memtable(DIM, capacity=2, tomb_capacity=64)
        g0 = mt.generation
        rows = _rows(rng, 5)
        for j in range(5):
            mt.apply(delta.Record(lsn=j + 1, op=delta.OP_UPSERT,
                                  ids=np.array([j]), vectors=rows[j:j + 1]))
        assert mt.capacity == 8 and mt.generation > g0
        assert mt.live_rows == 5
        d, i = mt.search(rows[3:4], 1)
        assert int(np.asarray(i)[0, 0]) == 3

    def test_same_id_churn_one_slot_one_tombstone(self):
        """The upsert double-work regression: N overwrites of one id
        must cost ONE memtable slot and ONE main-index tombstone."""
        rng = np.random.default_rng(6)
        mt = delta.Memtable(DIM, capacity=4, tomb_capacity=4)
        last = None
        for j in range(50):
            last = _rows(rng, 1)
            mt.apply(delta.Record(lsn=j + 1, op=delta.OP_UPSERT,
                                  ids=np.array([7]), vectors=last))
        assert mt.live_rows == 1
        assert mt.n_tombstones == 1
        assert mt.capacity == 4          # no regrow: one slot reused
        d, _ = mt.search(last, 1)
        assert float(np.asarray(d)[0, 0]) == pytest.approx(0.0, abs=1e-5)
        live_ids, live_rows, tomb_ids = mt.fold_payload()
        np.testing.assert_array_equal(live_ids, [7])
        np.testing.assert_array_equal(tomb_ids, [7])
        np.testing.assert_allclose(live_rows, last, rtol=1e-6)

    def test_delete_then_reinsert_keeps_single_tombstone(self):
        rng = np.random.default_rng(7)
        mt = delta.Memtable(DIM, capacity=8, tomb_capacity=8)
        v = _rows(rng, 1)
        mt.apply(delta.Record(lsn=1, op=delta.OP_UPSERT,
                              ids=np.array([3]), vectors=v))
        mt.apply(delta.Record(lsn=2, op=delta.OP_DELETE, ids=np.array([3])))
        v2 = _rows(rng, 1)
        mt.apply(delta.Record(lsn=3, op=delta.OP_UPSERT,
                              ids=np.array([3]), vectors=v2))
        assert mt.live_rows == 1 and mt.n_tombstones == 1
        d, i = mt.search(v2, 1)
        assert int(np.asarray(i)[0, 0]) == 3

    def test_search_parity_vs_numpy_l2(self):
        rng = np.random.default_rng(8)
        mt = delta.Memtable(DIM, capacity=32, tomb_capacity=8)
        rows = _rows(rng, 20)
        for j in range(20):
            mt.apply(delta.Record(lsn=j + 1, op=delta.OP_UPSERT,
                                  ids=np.array([100 + j]),
                                  vectors=rows[j:j + 1]))
        q = _rows(rng, 4)
        d, i = mt.search(q, 5)
        ref = np.linalg.norm(q[:, None, :] - rows[None], axis=-1) ** 2
        order = np.argsort(ref, axis=1)[:, :5] + 100
        np.testing.assert_array_equal(np.asarray(i), order)

    def test_inner_product_metric(self):
        rng = np.random.default_rng(9)
        mt = delta.Memtable(DIM, capacity=8, tomb_capacity=8,
                            metric=DistanceType.InnerProduct)
        rows = _rows(rng, 4)
        for j in range(4):
            mt.apply(delta.Record(lsn=j + 1, op=delta.OP_UPSERT,
                                  ids=np.array([j]), vectors=rows[j:j + 1]))
        q = _rows(rng, 2)
        _, i = mt.search(q, 2)
        ref = np.argsort(-(q @ rows.T), axis=1)[:, :2]
        np.testing.assert_array_equal(np.asarray(i), ref)
        assert mt.select_min is False

    def test_reset_keeps_shapes(self):
        rng = np.random.default_rng(10)
        mt = delta.Memtable(DIM, capacity=8, tomb_capacity=8)
        mt.apply(delta.Record(lsn=1, op=delta.OP_UPSERT,
                              ids=np.array([1]), vectors=_rows(rng, 1)))
        cap = mt.capacity
        mt.reset()
        assert mt.live_rows == 0 and mt.n_tombstones == 0
        assert mt.capacity == cap and mt.applied_lsn == 0
        data, ids, tombs = mt.device_view()
        assert data.shape == (cap, DIM)
        assert (np.asarray(ids) == -1).all()


# ---------------------------------------------------------------------------
# the write path: ack semantics + observability


class TestWritePath:
    def test_lsn_monotonic_and_counters(self, tmp_path):
        rng = np.random.default_rng(11)
        with obs.collecting():
            srv = _ingest(tmp_path)
            srv.recover()
            lsns = [srv.write(np.array([j]), _rows(rng, 1))
                    for j in range(3)]
            assert lsns == [1, 2, 3]
            srv.write(np.array([0]), op="delete")
            snap = obs.snapshot()["counters"]
            assert snap["serving.ingest.appended"] == 4
            assert snap["serving.ingest.acked"] == 4
            h = obs.registry().histogram(
                "serving.ingest.visibility").windowed_dict()
            assert h["count"] == 4
            srv.close()

    def test_write_before_recover_refused(self, tmp_path):
        srv = _ingest(tmp_path)
        with pytest.raises(RaftError, match="recover"):
            srv.write(np.array([1]), np.ones((1, DIM), np.float32))
        srv.close()

    def test_bad_args_refused(self, tmp_path):
        rng = np.random.default_rng(12)
        srv = _ingest(tmp_path)
        srv.recover()
        with pytest.raises(RaftError, match="op"):
            srv.write(np.array([1]), _rows(rng, 1), op="replace")
        with pytest.raises(RaftError, match="no vectors"):
            srv.write(np.array([1]), _rows(rng, 1), op="delete")
        with pytest.raises(RaftError, match=">= 0"):
            srv.write(np.array([-4]), _rows(rng, 1))
        with pytest.raises(RaftError):
            srv.write(np.array([1]), _rows(rng, 1)[:, :4])
        assert srv.stats()["last_lsn"] == 0
        srv.close()

    def test_concurrent_writers_all_acked_and_replayable(self, tmp_path):
        srv = _ingest(tmp_path, max_memtable_rows=4096,
                      memtable_capacity=256)
        srv.recover()
        errs = []

        def worker(base):
            rng = np.random.default_rng(base)
            try:
                for j in range(20):
                    srv.write(np.array([base * 1000 + j]), _rows(rng, 1))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        assert srv.memtable.live_rows == 80
        assert srv.stats()["last_lsn"] == 80
        dig = srv.memtable.digest()
        srv.close()
        srv2 = _ingest(tmp_path, max_memtable_rows=4096,
                       memtable_capacity=256)
        srv2.recover()
        # lock-ordered apply: replay reproduces the interleaving exactly
        assert srv2.memtable.digest() == dig
        srv2.close()


# ---------------------------------------------------------------------------
# crash recovery: the kill matrix


def _acked_writes(srv, rng, n=4, start=0):
    """n acked single-row upserts; returns {id: row} for loss checks."""
    acked = {}
    for j in range(start, start + n):
        row = _rows(rng, 1)
        srv.write(np.array([j]), row)
        acked[j] = row[0]
    return acked


class TestKillMatrix:
    @pytest.mark.parametrize("site", KILL_SITES)
    def test_kill_then_recover_no_acked_loss(self, tmp_path, res,
                                             flat_index, site):
        rng = np.random.default_rng(SEED % 2**31)
        srv = _ingest(tmp_path, res=res)
        srv.recover(base_index=flat_index)
        acked = _acked_writes(srv, rng, n=4)
        with FaultPlan(seed=SEED).at(site, times=1).active():
            with pytest.raises(FaultInjected):
                if site in ("ingest.fold", "ingest.truncate"):
                    srv.fold()
                else:
                    srv.write(np.array([99]), _rows(rng, 1))
        srv.close()

        r1 = _ingest(tmp_path, res=res)
        idx1 = r1.recover(base_index=flat_index)

        # no acknowledged write is lost: every acked id is live in the
        # memtable, or (post-fold roll-forward) folded into the index
        if mutate.generation(idx1) > mutate.generation(flat_index):
            # the commit marker landed before the kill: recovery rolls
            # the fold FORWARD (candidate index, fresh memtable) and
            # consumes the marker — the fold finished, nothing replays
            sp = ivf_flat.SearchParams(n_probes=16)
            for i, row in acked.items():
                _, got = ivf_flat.search(res, sp, idx1, row[None, :], 1)
                assert int(np.asarray(got)[0, 0]) == i, site
            assert r1.memtable.live_rows == 0
            r1.close()
        else:
            # replay path: two INDEPENDENT recoveries of the same WAL
            # must agree bit for bit
            d1 = r1.memtable.digest()
            r1.close()
            r2 = _ingest(tmp_path, res=res)
            idx2 = r2.recover(base_index=flat_index)
            assert r2.memtable.digest() == d1
            assert mutate.generation(idx1) == mutate.generation(idx2)
            for i, row in acked.items():
                d, got = r2.memtable.search(row[None, :], 1)
                assert int(np.asarray(got)[0, 0]) == i, site
                assert float(np.asarray(d)[0, 0]) == pytest.approx(
                    0.0, abs=1e-5)
            r2.close()

    def test_truncate_kill_rolls_forward(self, tmp_path, res, flat_index):
        """A kill between the durable commit marker and the WAL
        truncation must finish the fold on recover, not replay it."""
        rng = np.random.default_rng(13)
        srv = _ingest(tmp_path, res=res)
        srv.recover(base_index=flat_index)
        acked = _acked_writes(srv, rng, n=3, start=5000)
        with FaultPlan(seed=SEED).at("ingest.truncate", times=1).active():
            with pytest.raises(FaultInjected):
                srv.fold()
        srv.close()

        r = _ingest(tmp_path, res=res)
        idx = r.recover(base_index=flat_index)
        assert mutate.generation(idx) == mutate.generation(flat_index) + 1
        assert r.memtable.live_rows == 0          # folded, not replayed
        assert r.stats()["wal_bytes"] == 0        # truncation completed
        sp = ivf_flat.SearchParams(n_probes=16)
        for i, row in acked.items():
            _, got = ivf_flat.search(res, sp, idx, row[None, :], 1)
            assert int(np.asarray(got)[0, 0]) == i
        r.close()

    def test_fold_kill_rolls_back_to_full_replay(self, tmp_path, res,
                                                 flat_index):
        rng = np.random.default_rng(14)
        srv = _ingest(tmp_path, res=res)
        srv.recover(base_index=flat_index)
        _acked_writes(srv, rng, n=3)
        pre = srv.memtable.digest()
        with FaultPlan(seed=SEED).at("ingest.fold", times=1).active():
            with pytest.raises(FaultInjected):
                srv.fold()
        srv.close()
        r = _ingest(tmp_path, res=res)
        idx = r.recover(base_index=flat_index)
        assert idx is flat_index
        assert r.memtable.digest() == pre
        r.close()

    def test_duplicate_replay_idempotent(self, tmp_path):
        rng = np.random.default_rng(15)
        srv = _ingest(tmp_path)
        srv.recover()
        _acked_writes(srv, rng, n=5)
        dig = srv.memtable.digest()
        srv.close()
        # recover, write nothing, recover again: same WAL replayed twice
        # into fresh memtables lands on the identical digest every time
        for _ in range(2):
            r = _ingest(tmp_path)
            r.recover()
            assert r.memtable.digest() == dig
            r.close()

    def test_recover_continues_lsn_sequence(self, tmp_path):
        rng = np.random.default_rng(16)
        srv = _ingest(tmp_path)
        srv.recover()
        _acked_writes(srv, rng, n=3)
        srv.close()
        r = _ingest(tmp_path)
        r.recover()
        assert r.write(np.array([50]), _rows(rng, 1)) == 4
        r.close()

    def test_replay_under_injected_fsync_failure(self, tmp_path):
        """A torn tail forces a truncation fsync during replay; an
        injected fsync failure there must propagate (never a silent
        half-repair) and the NEXT recover must succeed."""
        rng = np.random.default_rng(17)
        srv = _ingest(tmp_path)
        srv.recover()
        _acked_writes(srv, rng, n=3)
        dig = srv.memtable.digest()
        srv.close()
        with open(srv.wal_path, "ab") as f:
            f.write(b"tornrecordtail")
        with FaultPlan(seed=SEED).at("ingest.fsync", times=1).active():
            r = _ingest(tmp_path)
            with pytest.raises(FaultInjected):
                r.recover()
            r.close()
        r2 = _ingest(tmp_path)
        r2.recover()
        assert r2.memtable.digest() == dig
        r2.close()

    def test_midlog_corruption_refuses_recovery(self, tmp_path):
        rng = np.random.default_rng(18)
        srv = _ingest(tmp_path)
        srv.recover()
        _acked_writes(srv, rng, n=3)
        srv.close()
        # flip a byte INSIDE the first record's payload (not the tail)
        with open(srv.wal_path, "r+b") as f:
            f.seek(20)
            b = f.read(1)
            f.seek(20)
            f.write(bytes([b[0] ^ 0xFF]))
        r = _ingest(tmp_path)
        with pytest.raises(CorruptIndexError, match="offset 0"):
            r.recover()
        r.close()

    def test_delay_at_injects_write_latency(self, tmp_path):
        import time
        rng = np.random.default_rng(19)
        srv = _ingest(tmp_path)
        srv.recover()
        plan = FaultPlan(seed=SEED).delay_at("ingest.fsync", delay=0.05)
        with plan.active():
            t0 = time.monotonic()
            srv.write(np.array([1]), _rows(rng, 1))
            assert time.monotonic() - t0 >= 0.05
        srv.close()


# ---------------------------------------------------------------------------
# write-path admission: backpressure, quotas, brownout


class TestBackpressure:
    def test_memtable_rows_bound_sheds(self, tmp_path):
        rng = np.random.default_rng(20)
        with obs.collecting():
            srv = _ingest(tmp_path, max_memtable_rows=3)
            srv.recover()
            _acked_writes(srv, rng, n=3)
            with pytest.raises(serving.Overloaded, match="backpressure"):
                srv.write(np.array([99]), _rows(rng, 1))
            snap = obs.snapshot()["counters"]
            assert snap["serving.ingest.shed.backpressure"] == 1
            evs = flight.events("serving.ingest.backpressure")
            assert evs and evs[0]["attrs"]["state"] == "enter"
            assert srv.stats()["backpressured"] is True
            # a delete drains a row; the next write records the exit
            srv.write(np.array([0]), op="delete")
            srv.write(np.array([99]), _rows(rng, 1))
            states = [e["attrs"]["state"]
                      for e in flight.events("serving.ingest.backpressure")]
            assert states == ["enter", "exit"]
            srv.close()

    def test_wal_bytes_bound_sheds(self, tmp_path):
        rng = np.random.default_rng(21)
        srv = _ingest(tmp_path, max_wal_bytes=64)
        srv.recover()
        srv.write(np.array([1]), _rows(rng, 1))   # pushes past 64 bytes
        with pytest.raises(serving.Overloaded, match="WAL"):
            srv.write(np.array([2]), _rows(rng, 1))
        srv.close()

    def test_tenant_write_quota(self, tmp_path):
        rng = np.random.default_rng(22)
        clock = [0.0]
        srv = ingest.IngestServer(
            None,
            ingest.IngestConfig(wal_dir=str(tmp_path / "wal"),
                                write_quotas={"batch": (10.0, 2.0)}),
            dim=DIM, clock=lambda: clock[0])
        srv.recover()
        with obs.collecting():
            srv.write(np.array([1]), _rows(rng, 1), tenant="batch")
            srv.write(np.array([2]), _rows(rng, 1), tenant="batch")
            with pytest.raises(serving.QuotaExceeded):
                srv.write(np.array([3]), _rows(rng, 1), tenant="batch")
            # unquota'd tenants are unaffected
            srv.write(np.array([4]), _rows(rng, 1))
            assert obs.snapshot()["counters"][
                "serving.ingest.shed.quota"] == 1
        clock[0] += 1.0          # refill
        srv.write(np.array([5]), _rows(rng, 1), tenant="batch")
        srv.close()

    def test_brownout_write_shed(self, tmp_path):
        rng = np.random.default_rng(23)
        srv = _ingest(tmp_path)
        srv.recover()
        bo = BrownoutState(best_effort_tenants={"batch"})
        bo.shed_best_effort_writes = True
        bo.level = 2
        srv._brownout = bo
        with obs.collecting():
            with pytest.raises(serving.BrownedOut):
                srv.write(np.array([1]), _rows(rng, 1), tenant="batch")
            assert obs.snapshot()["counters"][
                "serving.ingest.shed.brownout"] == 1
        # interactive tenants write through; clearing the rung re-admits
        srv.write(np.array([2]), _rows(rng, 1))
        bo.shed_best_effort_writes = False
        srv.write(np.array([3]), _rows(rng, 1), tenant="batch")
        srv.close()

    def test_rung_flag_propagates_through_controller(self, res,
                                                     flat_index):
        ex = serving.Executor(res, "ivf_flat", flat_index, ks=(5,),
                              max_batch=4,
                              search_params=ivf_flat.SearchParams(
                                  n_probes=4), warm="jit")
        srv = serving.Server(ex, serving.ServerConfig(max_batch=4))
        ladder = [serving.Rung("full"),
                  serving.Rung("shed-writes",
                               shed_best_effort_writes=True)]
        ctl = serving.BrownoutController(
            srv, ladder, best_effort_tenants={"batch"})
        now = ctl._clock()
        with ctl._lock:
            ctl._apply(1, "step_down", now, p99=None, queue_rows=0,
                       sheds=0)
        assert srv.brownout.shed_best_effort_writes is True
        with ctl._lock:
            ctl._apply(0, "step_up", now, p99=None, queue_rows=0, sheds=0)
        assert srv.brownout.shed_best_effort_writes is False

    def test_rung0_must_not_shed_writes(self, res, flat_index):
        ex = serving.Executor(res, "ivf_flat", flat_index, ks=(5,),
                              max_batch=4,
                              search_params=ivf_flat.SearchParams(
                                  n_probes=4), warm="jit")
        srv = serving.Server(ex, serving.ServerConfig(max_batch=4))
        bad = [serving.Rung("full", shed_best_effort_writes=True),
               serving.Rung("degraded")]
        with pytest.raises(RaftError, match="rung 0"):
            serving.BrownoutController(srv, bad)


# ---------------------------------------------------------------------------
# the fold lifecycle


class TestFold:
    def test_empty_fold_is_noop(self, tmp_path, res, flat_index):
        srv = _ingest(tmp_path, res=res)
        srv.recover(base_index=flat_index)
        assert srv.fold() is None

    def test_fold_publishes_and_truncates(self, tmp_path, res,
                                          flat_index, dataset):
        db, _ = dataset
        rng = np.random.default_rng(24)
        with obs.collecting():
            srv = _ingest(tmp_path, res=res)
            srv.recover(base_index=flat_index)
            acked = _acked_writes(srv, rng, n=3, start=7000)
            srv.write(np.array([0]), op="delete")     # tombstone a db row
            cand = srv.fold()
            assert mutate.generation(cand) == mutate.generation(
                flat_index) + 1
            assert srv.stats()["wal_bytes"] == 0
            assert srv.memtable.live_rows == 0
            snap = obs.snapshot()["counters"]
            assert snap["serving.ingest.folds"] == 1
            assert snap["serving.ingest.truncations"] == 1
            evs = flight.events("serving.ingest.fold")
            assert evs and evs[0]["attrs"]["rows"] == 3
            assert evs[0]["attrs"]["tombstones"] == 4
        sp = ivf_flat.SearchParams(n_probes=16)
        for i, row in acked.items():
            _, got = ivf_flat.search(res, sp, cand, row[None, :], 1)
            assert int(np.asarray(got)[0, 0]) == i
        _, got = ivf_flat.search(res, sp, cand, db[0][None, :], 2)
        assert 0 not in np.asarray(got)[0]
        srv.close()

    def test_maybe_fold_thresholds(self, tmp_path, res, flat_index):
        rng = np.random.default_rng(25)
        srv = _ingest(tmp_path, res=res, fold_rows=2)
        srv.recover(base_index=flat_index)
        srv.write(np.array([8000]), _rows(rng, 1))
        assert srv.maybe_fold() is None
        srv.write(np.array([8001]), _rows(rng, 1))
        assert srv.maybe_fold() is not None
        srv.close()

    def test_rebalancer_fold_hook(self, tmp_path, res, flat_index):
        rng = np.random.default_rng(26)
        srv = _ingest(tmp_path, res=res, fold_rows=1)
        srv.recover(base_index=flat_index)
        rb = serving.Rebalancer(res, flat_index, ingest=srv)
        assert rb.maybe_fold_ingest() is None        # nothing buffered
        srv.write(np.array([8100]), _rows(rng, 1))
        folded = rb.maybe_fold_ingest()
        assert folded is not None
        assert rb.last_good is folded                # base moved forward
        srv.close()


# ---------------------------------------------------------------------------
# serving integration: merged visibility + zero-recompile steady state


@pytest.fixture()
def served(tmp_path, res, flat_index):
    ex = serving.Executor(res, "ivf_flat", flat_index, ks=(5,),
                          max_batch=4,
                          search_params=ivf_flat.SearchParams(n_probes=16),
                          warm="jit")
    srv = serving.Server(ex, serving.ServerConfig(max_batch=4,
                                                  max_wait_us=500))
    ig = _ingest(tmp_path, res=res, memtable_capacity=64)
    ig.recover(base_index=flat_index)
    srv.attach_ingest(ig)
    srv.start()
    yield srv, ig
    srv.stop()
    ig.close()


class TestServingIntegration:
    def test_write_visible_before_fold(self, served):
        srv, _ = served
        v = np.full((1, DIM), 7.0, np.float32)
        srv.write(np.array([9000]), v)
        _, i = srv.search(v, k=5)
        assert int(np.asarray(i)[0, 0]) == 9000

    def test_delete_masks_main_index(self, served, dataset):
        srv, _ = served
        db, _ = dataset
        q = db[5][None, :]
        _, i0 = srv.search(q, k=5)
        victim = int(np.asarray(i0)[0, 0])
        srv.write(np.array([victim]), op="delete")
        _, i1 = srv.search(q, k=5)
        assert victim not in np.asarray(i1)[0]

    def test_overwrite_wins_over_main_copy(self, served, dataset):
        srv, _ = served
        db, _ = dataset
        new_row = np.full((1, DIM), -6.0, np.float32)
        srv.write(np.array([5]), new_row)          # id 5 exists in main
        _, i = srv.search(new_row, k=5)
        assert int(np.asarray(i)[0, 0]) == 5
        d0, i0 = srv.search(db[5][None, :], k=5)
        # the main-index row for id 5 is tombstoned: if id 5 surfaces,
        # it is the NEW row's (far) distance, not the old exact match
        row0 = np.asarray(i0)[0]
        if 5 in row0:
            at = float(np.asarray(d0)[0][list(row0).index(5)])
            assert at > 1.0

    def test_fold_then_search_consistent(self, served):
        srv, ig = served
        v = np.full((1, DIM), 7.5, np.float32)
        srv.write(np.array([9100]), v)
        ig.fold()
        _, i = srv.search(v, k=5)
        assert int(np.asarray(i)[0, 0]) == 9100

    def test_server_write_requires_ingest(self, res, flat_index):
        ex = serving.Executor(res, "ivf_flat", flat_index, ks=(5,),
                              max_batch=4,
                              search_params=ivf_flat.SearchParams(
                                  n_probes=4), warm="jit")
        srv = serving.Server(ex, serving.ServerConfig(max_batch=4))
        with pytest.raises(RaftError, match="attach_ingest"):
            srv.write(np.array([1]), np.ones((1, DIM), np.float32))

    def test_attach_after_start_refused(self, tmp_path, res, flat_index):
        ex = serving.Executor(res, "ivf_flat", flat_index, ks=(5,),
                              max_batch=4,
                              search_params=ivf_flat.SearchParams(
                                  n_probes=4), warm="jit")
        srv = serving.Server(ex, serving.ServerConfig(max_batch=4)).start()
        ig = _ingest(tmp_path, res=res)
        ig.recover(base_index=flat_index)
        try:
            with pytest.raises(RaftError, match="attach"):
                srv.attach_ingest(ig)
        finally:
            srv.stop()
            ig.close()

    def test_zero_steady_state_recompiles_write_search_fold_search(
            self, tmp_path, res, flat_index):
        """The acceptance bar: with the delta tier attached, steady
        state — writes, searches, a fold, more searches — compiles
        nothing outside the fold's own swap warm (which happens before
        the new generation is published, off the request path)."""
        ex = serving.Executor(res, "ivf_flat", flat_index, ks=(5,),
                              max_batch=4,
                              search_params=ivf_flat.SearchParams(
                                  n_probes=16), warm="jit")
        srv = serving.Server(ex, serving.ServerConfig(max_batch=4,
                                                      max_wait_us=500))
        ig = _ingest(tmp_path, res=res, memtable_capacity=64)
        ig.recover(base_index=flat_index)
        srv.attach_ingest(ig)
        rng = np.random.default_rng(27)
        with obs.collecting():
            srv.start()
            try:
                # absorb warmup + one shape round
                for m in (1, 2, 4, 3):
                    srv.search(_rows(rng, m), k=5)
                reg = obs.registry()
                c0 = reg.counter("xla.compiles").value
                # steady state: write -> search (memtable dirty -> fresh
                # device view, same shapes)
                for j in range(4):
                    srv.write(np.array([9500 + j]), _rows(rng, 1))
                    for m in (1, 3, 4):
                        srv.search(_rows(rng, m), k=5)
                srv.write(np.array([3]), op="delete")
                srv.search(_rows(rng, 2), k=5)
                c1 = reg.counter("xla.compiles").value
                assert c1 == c0, f"{c1 - c0} recompiles on the write path"
                ig.fold()            # swap warm may compile — off path
                c2 = reg.counter("xla.compiles").value
                for m in (1, 2, 4, 3):
                    srv.search(_rows(rng, m), k=5)
                srv.write(np.array([9600]), _rows(rng, 1))
                srv.search(_rows(rng, 1), k=5)
                c3 = reg.counter("xla.compiles").value
                assert c3 == c2, f"{c3 - c2} recompiles after the fold"
            finally:
                srv.stop()
        ig.close()

    def test_memtable_regrow_is_one_generation_bump(self, tmp_path, res,
                                                    flat_index):
        """Filling past capacity regrows once (one new compiled shape),
        then steady state is flat again."""
        ex = serving.Executor(res, "ivf_flat", flat_index, ks=(5,),
                              max_batch=4,
                              search_params=ivf_flat.SearchParams(
                                  n_probes=16), warm="jit")
        srv = serving.Server(ex, serving.ServerConfig(max_batch=4,
                                                      max_wait_us=500))
        ig = _ingest(tmp_path, res=res, memtable_capacity=4,
                     max_memtable_rows=64)
        ig.recover(base_index=flat_index)
        srv.attach_ingest(ig)
        rng = np.random.default_rng(28)
        srv.start()
        try:
            g0 = ig.memtable.generation
            for j in range(6):                  # 4 -> regrow -> 8
                srv.write(np.array([9700 + j]), _rows(rng, 1))
            assert ig.memtable.capacity == 8
            assert ig.memtable.generation == g0 + 1
            v = np.full((1, DIM), 3.3, np.float32)
            srv.write(np.array([9750]), v)
            _, i = srv.search(v, k=5)
            assert int(np.asarray(i)[0, 0]) == 9750
        finally:
            srv.stop()
        ig.close()


# ---------------------------------------------------------------------------
# write-path tracing (PR 16): serving.ingest.* spans on the durable path


class TestIngestTracing:
    def test_write_mints_trace_with_spans(self, tmp_path):
        rng = np.random.default_rng(31)
        srv = _ingest(tmp_path)
        srv.recover()
        with obs.collecting(), trace.tracing_scope():
            srv.write(np.arange(4, dtype=np.int64), _rows(rng, 4))
        mine = [r for r in flight.traces()
                if r.name == "serving.ingest.request"]
        assert len(mine) == 1
        rt = mine[0]
        assert [s.name for s in rt.spans] == [
            "serving.ingest.append", "serving.ingest.apply",
            "serving.ingest.fsync"]
        assert all(s.duration >= 0.0 for s in rt.spans)
        assert rt.attrs["op"] == "upsert"
        assert rt.attrs["rows"] == 4
        assert rt.attrs["lsn"] == 1
        srv.close()

    def test_write_adopts_ambient_trace(self, tmp_path):
        rng = np.random.default_rng(32)
        srv = _ingest(tmp_path)
        srv.recover()
        rec = trace.SpanRecorder("serving.request")
        with obs.collecting(), trace.tracing_scope(), trace.activating(rec):
            srv.write(np.arange(4, dtype=np.int64), _rows(rng, 4))
        # adopted the caller's recorder: nothing minted into the ring
        assert flight.traces() == []
        assert "serving.ingest.fsync" in [s.name for s in rec.spans]
        assert rec.attrs["op"] == "upsert"
        srv.close()

    def test_write_without_tracing_records_nothing(self, tmp_path):
        rng = np.random.default_rng(33)
        srv = _ingest(tmp_path)
        srv.recover()
        with obs.collecting():
            srv.write(np.arange(4, dtype=np.int64), _rows(rng, 4))
        assert flight.traces() == []
        srv.close()

    def test_fold_trace_lands_with_stage_span(self, tmp_path, res,
                                              flat_index):
        rng = np.random.default_rng(34)
        srv = _ingest(tmp_path, res=res)
        srv.recover(base_index=flat_index)
        srv.write(np.arange(2000, 2008, dtype=np.int64), _rows(rng, 8))
        with obs.collecting(), trace.tracing_scope():
            assert srv.fold() is not None
        folds = [r for r in flight.traces()
                 if r.attrs.get("op") == "fold"]
        assert len(folds) == 1
        frt = folds[0]
        # the stage hook mirrors the fold timer onto the minted trace
        assert "serving.ingest.fold" in [s.name for s in frt.spans]
        assert frt.attrs["rows"] == 8
        assert "generation" in frt.attrs
        srv.close()


# ---------------------------------------------------------------------------
# round 19 satellites: the group-commit failure fence, the WAL-lag /
# visibility fold triggers, and replay racing live readers


class TestRound19Satellites:
    def test_fsync_failure_fails_whole_group_commit(self, tmp_path,
                                                    monkeypatch):
        """A failed group fsync fails the ack for EVERY rider of that
        group — the performer raises, and a waiter whose record was
        covered re-raises the same exception through the epoch fence —
        and the tail stays repairable: the records were appended, so
        the next good fsync (or a recover) makes them durable."""
        rng = np.random.default_rng(40)
        srv = _ingest(tmp_path, memtable_capacity=64)
        srv.recover()
        in_sync = threading.Event()
        release = threading.Event()
        calls = []
        orig = ingest.WriteAheadLog.sync

        def patched(wal):
            if not calls:
                calls.append(1)
                in_sync.set()
                assert release.wait(10.0)
                raise OSError("injected fsync failure")
            return orig(wal)

        monkeypatch.setattr(ingest.WriteAheadLog, "sync", patched)
        errs = {}

        def writer(name, i):
            try:
                srv.write(np.array([i]), _rows(rng, 1))
            except BaseException as e:  # noqa: BLE001
                errs[name] = e

        t1 = threading.Thread(target=writer, args=("performer", 9001))
        t1.start()
        assert in_sync.wait(10.0)        # performer is inside fsync
        t2 = threading.Thread(target=writer, args=("rider", 9002))
        t2.start()
        # the rider appends its record, then parks on the busy group
        deadline = 50
        while srv.stats()["last_lsn"] < 2 and deadline:
            threading.Event().wait(0.1)
            deadline -= 1
        threading.Event().wait(0.3)      # let the rider reach the fence
        release.set()
        t1.join(10.0)
        t2.join(10.0)
        assert isinstance(errs.get("performer"), OSError)
        assert isinstance(errs.get("rider"), OSError)
        assert errs["rider"] is errs["performer"]   # the fence re-raises
        # both records were appended; the NEXT write's good fsync (and
        # any recover) sees them — no acked state was lost, only acks
        assert srv.write(np.array([9003]), _rows(rng, 1)) == 3
        dig = srv.memtable.digest()
        srv.close()
        srv2 = _ingest(tmp_path, memtable_capacity=64)
        srv2.recover()
        assert srv2.memtable.digest() == dig
        assert srv2.stats()["last_lsn"] == 3
        srv2.close()

    def test_fold_trigger_replay_debt_rows(self, tmp_path, res,
                                           flat_index):
        rng = np.random.default_rng(41)
        srv = _ingest(tmp_path, res=res, fold_replay_debt_rows=3)
        srv.recover(base_index=flat_index)
        with obs.collecting():
            srv.write(np.array([8200, 8201]), _rows(rng, 2))
            assert srv.maybe_fold() is None          # debt 2 < 3
            assert srv.stats()["replay_debt_rows"] == 2
            srv.write(np.array([8202]), _rows(rng, 1))
            assert srv.maybe_fold() is not None      # debt 3 fires
            snap = obs.snapshot()["counters"]
            assert snap["serving.ingest.fold_trigger.rows"] == 1
            assert "serving.ingest.fold_trigger.lag" not in snap
        assert srv.stats()["replay_debt_rows"] == 0  # fold clears debt
        srv.close()

    def test_fold_trigger_visibility_lag(self, tmp_path, res,
                                         flat_index):
        rng = np.random.default_rng(42)
        t = [100.0]
        srv = ingest.IngestServer(
            res, ingest.IngestConfig(wal_dir=str(tmp_path / "wal"),
                                     memtable_capacity=32,
                                     tomb_capacity=32,
                                     fold_visibility_lag_s=5.0),
            dim=DIM, clock=lambda: t[0])
        srv.recover(base_index=flat_index)
        srv.write(np.array([8300]), _rows(rng, 1))
        with obs.collecting():
            assert srv.maybe_fold() is None          # age 0 < 5s
            t[0] += 10.0                             # oldest row ages out
            assert srv.maybe_fold() is not None
            snap = obs.snapshot()["counters"]
            assert snap["serving.ingest.fold_trigger.lag"] == 1
        # a fresh write restarts the visibility clock
        srv.write(np.array([8301]), _rows(rng, 1))
        assert srv.maybe_fold() is None
        srv.close()

    def test_recover_replay_races_concurrent_reads(self, tmp_path):
        """recover() replays under the append lock while a closed-loop
        reader hammers the memtable search path — no exception, no torn
        view, and the final state is the full bit-identical replay."""
        rng = np.random.default_rng(43)
        srv = _ingest(tmp_path, memtable_capacity=256)
        srv.recover()
        for j in range(40):
            srv.write(np.array([j]), _rows(rng, 1))
        dig = srv.memtable.digest()
        srv.close()
        srv2 = _ingest(tmp_path, memtable_capacity=256)
        stop = threading.Event()
        errs = []
        seen = []

        def reader():
            q = np.zeros((1, DIM), np.float32)
            while not stop.is_set():
                try:
                    _, i = srv2.memtable.search(q, 5)
                    seen.append(int((np.asarray(i) >= 0).sum()))
                except Exception as e:  # noqa: BLE001
                    errs.append(e)
                    return

        rt = threading.Thread(target=reader)
        rt.start()
        srv2.recover()
        stop.set()
        rt.join(10.0)
        assert not errs
        assert seen                                   # the loop really ran
        assert srv2.memtable.digest() == dig
        srv2.close()
