"""Mutation-layer tests (ISSUE PR 7, robustness archetype): tombstone
deletes, generation-snapshotted readers, compaction, the CAGRA delete-mask
shim, and mutation x scan-mode parity.

The contract under test: ``delete``/``compact``/``extend`` return a NEW
index generation sharing unchanged arrays with the parent; deleted ids
vanish from every scan formulation (recon / codes / fused) via
the existing ``id < 0`` mask with zero kernel changes; ``integrity.verify``
accepts tombstones inside the occupied prefix and rejects them outside it;
the recall canary excludes deleted rows from its ground truth.
"""

import dataclasses
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import integrity
from raft_tpu.distance.types import DistanceType
from raft_tpu.integrity import IntegrityError
from raft_tpu.integrity import canary as _canary
from raft_tpu.neighbors import cagra, grouped, ivf_flat, ivf_pq
from raft_tpu.neighbors import mutate
from raft_tpu.random import make_blobs


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches():
    # this module compiles many one-off mutated-shape variants; release
    # the executables at teardown so later modules in a full-suite run
    # don't inherit the accumulated JIT code mappings
    yield
    jax.clear_caches()


def naive_knn(db, q, k):
    d = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=1), idx


def recall(found, truth):
    hits = sum(len(set(f) & set(t)) for f, t in zip(found, truth))
    return hits / truth.size


@pytest.fixture(scope="module")
def res():
    # module-scoped override of conftest's function-scoped fixture so the
    # class-scoped built-index fixtures (building dominates runtime here)
    # can depend on it
    from raft_tpu import DeviceResources
    return DeviceResources(seed=42)


@pytest.fixture(scope="module")
def dataset():
    X, _ = make_blobs(1000, 16, n_clusters=16, cluster_std=1.0, seed=11)
    return np.asarray(X[:950]), np.asarray(X[950:966])


@pytest.fixture(scope="module")
def pq_dataset():
    X, _ = make_blobs(1200, 32, n_clusters=16, cluster_std=1.0, seed=12)
    return np.asarray(X[:1100]), np.asarray(X[1100:1132])


class TestMutateHelpers:
    def test_encode_decode_roundtrip(self):
        ids = jnp.asarray([0, 1, 7, 1 << 20], jnp.int32)
        enc = mutate.encode_tombstones(ids)
        assert bool(jnp.all(enc <= -2))
        np.testing.assert_array_equal(
            np.sort(mutate.decode_tombstones(np.asarray(enc))),
            np.sort(np.asarray(ids)))

    def test_tombstone_hits_only_live_slots(self):
        li = jnp.asarray([[0, 3, -1], [5, -(3 + 2), -1]], jnp.int32)
        out, hit = mutate.tombstone(li, [3, 99])
        # live id 3 is rewritten; the pre-existing tombstone of 3 and the
        # pad slots are untouched; id 99 matches nothing
        np.testing.assert_array_equal(
            np.asarray(out), [[0, -(3 + 2), -1], [5, -(3 + 2), -1]])
        assert int(hit.sum()) == 1

    def test_deleted_ids_subtracts_reinserted(self):
        # id 3 tombstoned in one slot but live in another (the rebalancer's
        # delete -> re-insert pattern): NOT deleted.  id 5 stays deleted.
        li = jnp.asarray([[0, -(3 + 2), -(5 + 2)], [3, 1, -1]], jnp.int32)

        class Stub:
            list_indices = li

        assert mutate.deleted_ids(Stub()) == frozenset({5})

    def test_deleted_ids_prefers_explicit_attr(self):
        class Stub:
            deleted_ids = {4, 9}

        assert mutate.deleted_ids(Stub()) == frozenset({4, 9})

    def test_live_sizes_and_dead_fraction(self):
        li = jnp.asarray([[0, 1, -(2 + 2), -1], [-1, -1, -1, -1]], jnp.int32)

        class Stub:
            list_indices = li

        np.testing.assert_array_equal(np.asarray(mutate.live_sizes(li)),
                                      [2, 0])
        assert mutate.live_count(Stub()) == 2
        assert mutate.dead_fraction(Stub()) == pytest.approx(1 / 3)

    def test_dead_fraction_empty_index(self):
        class Stub:
            list_indices = jnp.full((2, 4), -1, jnp.int32)

        assert mutate.dead_fraction(Stub()) == 0.0

    def test_compaction_order_stable(self):
        li = jnp.asarray([[7, -(1 + 2), 9, -1]], jnp.int32)
        order, live = mutate.compaction_order(li)
        # live rows first, original relative order preserved
        np.testing.assert_array_equal(np.asarray(li[0][order[0]]),
                                      [7, 9, -(1 + 2), -1])
        np.testing.assert_array_equal(np.asarray(live), [2])


class TestTombstoneSerializeRoundTrip:
    """The tombstone decode helpers (``deleted_ids`` / ``live_sizes``)
    must survive serialize -> deserialize: both decode from
    ``list_indices``, which hardened serialization stores verbatim, so a
    checkpointed-and-restored index must report the same delete state
    (the rebalancer's resume path depends on it)."""

    DOOMED = [0, 5, 17, 400]

    def _roundtrip(self, res, module, index):
        buf = io.BytesIO()
        module.serialize(res, buf, index)
        buf.seek(0)
        return module.deserialize(res, buf)

    def _check(self, res, module, index):
        deleted = module.delete(res, index, self.DOOMED)
        back = self._roundtrip(res, module, deleted)
        assert (mutate.deleted_ids(back) == mutate.deleted_ids(deleted)
                == frozenset(self.DOOMED))
        np.testing.assert_array_equal(
            np.asarray(mutate.live_sizes(back.list_indices)),
            np.asarray(mutate.live_sizes(deleted.list_indices)))
        assert mutate.live_count(back) == mutate.live_count(index) - len(
            self.DOOMED)

    def test_flat_roundtrip(self, res, dataset):
        db, _ = dataset
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=5)
        self._check(res, ivf_flat, ivf_flat.build(res, params, db))

    def test_pq_roundtrip(self, res, pq_dataset):
        db, _ = pq_dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=8,
                                    kmeans_n_iters=5)
        self._check(res, ivf_pq, ivf_pq.build(res, params, db))


class TestFlatMutation:
    @pytest.fixture(scope="class")
    def built(self, res, dataset):
        db, _ = dataset
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=5)
        return ivf_flat.build(res, params, db)

    def test_delete_excludes_ids(self, res, dataset, built):
        db, q = dataset
        sp = ivf_flat.SearchParams(n_probes=8)
        _, ti = naive_knn(db, q, 10)
        doomed = set(ti[:, 0].tolist())  # every query's true nearest
        idx2 = ivf_flat.delete(res, built, sorted(doomed))
        _, i2 = ivf_flat.search(res, sp, idx2, q, 10)
        found = set(np.asarray(i2).reshape(-1).tolist())
        assert not (found & doomed)
        # survivors still searchable at good recall
        keep = np.asarray([r for r in range(db.shape[0]) if r not in doomed])
        _, ti2 = naive_knn(db[keep], q, 10)
        assert recall(np.asarray(i2), keep[ti2]) > 0.85

    def test_delete_is_a_new_generation(self, res, built):
        idx2 = ivf_flat.delete(res, built, [0])
        assert mutate.generation(idx2) == mutate.generation(built) + 1
        # the parent snapshot is untouched: id 0 still live there
        assert 0 in np.asarray(built.list_indices)
        assert 0 not in np.asarray(idx2.list_indices)[
            np.asarray(idx2.list_indices) >= 0]

    def test_delete_nonexistent_is_noop(self, res, built):
        idx2 = ivf_flat.delete(res, built, [10 ** 7])
        np.testing.assert_array_equal(np.asarray(idx2.list_indices),
                                      np.asarray(built.list_indices))

    def test_compact_reclaims_and_preserves_results(self, res, dataset,
                                                    built):
        db, q = dataset
        sp = ivf_flat.SearchParams(n_probes=8)
        idx2 = ivf_flat.delete(res, built, list(range(0, 200)))
        assert mutate.dead_fraction(idx2) > 0.0
        idx3 = ivf_flat.compact(res, idx2)
        assert mutate.dead_fraction(idx3) == 0.0
        assert mutate.live_count(idx3) == mutate.live_count(idx2)
        assert mutate.generation(idx3) == mutate.generation(idx2) + 1
        _, i2 = ivf_flat.search(res, sp, idx2, q, 10)
        _, i3 = ivf_flat.search(res, sp, idx3, q, 10)
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i3))
        # post-compact ids are sparse (survivors keep their source ids):
        # verify needs the explicit id span, then passes clean
        integrity.verify(idx3, level="statistical", res=res,
                         n_rows=db.shape[0])

    def test_reinsert_after_delete(self, res, dataset, built):
        db, q = dataset
        sp = ivf_flat.SearchParams(n_probes=8)
        _, ti = naive_knn(db, q, 1)
        rid = int(ti[0, 0])
        idx2 = ivf_flat.delete(res, built, [rid])
        idx3 = ivf_flat.extend(res, idx2, db[rid:rid + 1],
                               np.asarray([rid], np.int64))
        _, i3 = ivf_flat.search(res, sp, idx3, q[:1], 5)
        assert rid in np.asarray(i3)[0].tolist()
        # live copy answers searches -> the id is no longer "deleted"
        assert rid not in mutate.deleted_ids(idx3)
        integrity.verify(idx3, level="statistical", res=res,
                         n_rows=db.shape[0])

    def test_delete_everything_searches_empty(self, res, dataset, built):
        db, q = dataset
        idx2 = ivf_flat.delete(res, built, list(range(db.shape[0])))
        assert mutate.live_count(idx2) == 0
        d, i = ivf_flat.search(res, ivf_flat.SearchParams(n_probes=8),
                               idx2, q, 5)
        np.testing.assert_array_equal(np.asarray(i),
                                      np.full((q.shape[0], 5), -1))


class TestVerifyTombstones:
    @pytest.fixture(scope="class")
    def deleted(self, res, dataset):
        db, _ = dataset
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=5)
        index = ivf_flat.build(res, params, db)
        return ivf_flat.delete(res, index, list(range(0, 50)))

    def test_verify_accepts_tombstones(self, res, deleted):
        integrity.verify(deleted, level="statistical", res=res)

    def test_tombstone_outside_prefix_fails(self, deleted):
        # a tombstone encoding in the padding region (beyond list_sizes)
        # is corruption, not a delete
        sizes = np.asarray(deleted.list_sizes)
        li = int(np.argmin(sizes))
        assert sizes[li] < deleted.capacity
        bad_li = deleted.list_indices.at[li, deleted.capacity - 1].set(-7)
        bad = dataclasses.replace(deleted, list_indices=bad_li)
        with pytest.raises(IntegrityError) as ei:
            integrity.verify(bad)
        assert ei.value.invariant == "ivf_flat.ids.range" or \
            ei.value.invariant == "ivf_flat.list_sizes.slots"

    def test_live_duplicate_still_fails(self, deleted):
        a = np.asarray(deleted.list_indices)
        # a list holding at least two LIVE slots
        li = int(np.argmax((a >= 0).sum(axis=1)))
        s0, s1 = [int(v) for v in np.flatnonzero(a[li] >= 0)[:2]]
        dup = int(a[li, s1])
        bad_li = deleted.list_indices.at[li, s0].set(dup)
        bad = dataclasses.replace(deleted, list_indices=bad_li)
        with pytest.raises(IntegrityError) as ei:
            integrity.verify(bad)
        assert ei.value.invariant == "ivf_flat.ids.unique"

    def test_live_plus_tombstone_same_id_passes(self, res, deleted):
        # the delete -> re-insert pattern: a live slot sharing its id with
        # a tombstone is legitimate, not a duplicate
        a = np.asarray(deleted.list_indices)
        lives = np.argwhere(a >= 0)
        li, sl = (int(lives[0][0]), int(lives[0][1]))
        live_id = int(a[li, sl])
        tombs = np.argwhere(a <= -2)
        tli, tsl = (int(tombs[0][0]), int(tombs[0][1]))
        patched = deleted.list_indices.at[tli, tsl].set(-(live_id + 2))
        idx = dataclasses.replace(deleted, list_indices=patched)
        integrity.verify(idx, level="structural")

    def test_decoded_tombstone_out_of_range_fails(self, deleted):
        total = int(np.asarray(deleted.list_sizes).sum())
        a = np.asarray(deleted.list_indices)
        tli, tsl = [int(v) for v in np.argwhere(a <= -2)[0]]
        bad_li = deleted.list_indices.at[tli, tsl].set(
            -(total + 100 + 2))
        bad = dataclasses.replace(deleted, list_indices=bad_li)
        with pytest.raises(IntegrityError) as ei:
            integrity.verify(bad)
        assert ei.value.invariant == "ivf_flat.ids.range"


class TestPqMutationParity:
    """Satellite 3: interleaved extend/delete/search must agree across
    every scan formulation, and deleted ids must never surface in ANY
    mode's top-k (fused included — on CPU its Pallas kernels run the
    portable path, same contract)."""

    MODES = ("recon", "codes", "fused")

    @pytest.fixture(scope="class")
    def built(self, res, pq_dataset):
        db, _ = pq_dataset
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=8,
                                    kmeans_n_iters=5)
        return ivf_pq.build(res, params, db)

    def _search_all_modes(self, res, index, q, k, kt=0):
        out = {}
        for mode in self.MODES:
            sp = ivf_pq.SearchParams(n_probes=16, scan_mode=mode,
                                     per_probe_topk=kt)
            _, i = ivf_pq.search(res, sp, index, q, k)
            out[mode] = np.asarray(i)
        return out

    def test_interleaved_mutations_agree_across_modes(self, res,
                                                      pq_dataset, built):
        db, q = pq_dataset
        rng = np.random.default_rng(20260805)
        index, n = built, db.shape[0]
        deleted = set()
        for rnd in range(3):
            doom = rng.choice([r for r in range(n) if r not in deleted],
                              size=40, replace=False)
            index = ivf_pq.delete(res, index, np.sort(doom))
            deleted.update(int(v) for v in doom)
            extra = rng.normal(size=(16, db.shape[1])).astype(np.float32)
            index = ivf_pq.extend(res, index, extra,
                                  np.arange(n, n + 16, dtype=np.int64))
            n += 16
            # matched kt across modes, both the exact-merge default and a
            # narrowed per-probe keep-set: deleted ids surface in NEITHER
            for kt in (0, 4):
                by_mode = self._search_all_modes(res, index, q, 10, kt=kt)
                for mode, ids in by_mode.items():
                    hit = set(ids.reshape(-1).tolist()) & deleted
                    assert not hit, (rnd, kt, mode, hit)
                if kt:
                    continue
                # at the exact merge the quantized modes keep essentially
                # the recon reference's candidates (LUT noise only)
                for mode in ("codes", "fused"):
                    ov = np.mean([len(set(a) & set(b)) / 10 for a, b in
                                  zip(by_mode[mode], by_mode["recon"])])
                    assert ov > 0.9, (rnd, mode, ov)
        # 3 x (delete + extend) on top of wherever build started
        assert mutate.generation(index) == mutate.generation(built) + 6

    def test_deleted_never_in_topk_property(self, res, pq_dataset, built):
        db, q = pq_dataset
        _, ti = naive_knn(db, q, 5)
        doomed = sorted(set(ti.reshape(-1).tolist()))  # the whole true top-5
        index = ivf_pq.delete(res, built, doomed)
        by_mode = self._search_all_modes(res, index, q, 10)
        for mode, ids in by_mode.items():
            assert not (set(ids.reshape(-1).tolist()) & set(doomed)), mode

    def test_compact_preserves_mode_results(self, res, pq_dataset, built):
        db, q = pq_dataset
        index = ivf_pq.delete(res, built, list(range(0, 300)))
        compacted = ivf_pq.compact(res, index)
        assert mutate.dead_fraction(compacted) == 0.0
        before = self._search_all_modes(res, index, q, 10)
        after = self._search_all_modes(res, compacted, q, 10)
        for mode in self.MODES:
            ov = np.mean([len(set(a) & set(b)) / 10 for a, b in
                          zip(before[mode], after[mode])])
            assert ov > 0.9, mode
        integrity.verify(compacted, level="statistical", res=res,
                         n_rows=db.shape[0])

    def test_all_deleted_returns_sentinels_every_mode(self, res,
                                                      pq_dataset, built):
        db, q = pq_dataset
        index = ivf_pq.delete(res, built, list(range(db.shape[0])))
        for mode, ids in self._search_all_modes(res, index, q, 5).items():
            np.testing.assert_array_equal(
                ids, np.full((q.shape[0], 5), -1), err_msg=mode)


class TestGroupedDegenerate:
    """Satellite 2: the grouped machinery must tolerate lists emptied by
    delete/compaction — empty pair groups, zero probes, zero capacity."""

    def test_probe_overlap_order_zero_probes(self):
        probes = jnp.zeros((5, 0), jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(grouped.probe_overlap_order(probes, 8)),
            np.arange(5))

    def test_block_size_zero_groups(self):
        assert grouped.block_size(0, 1024) >= 1
        assert grouped.block_size(0, 0) >= 1

    def test_scan_and_scatter_zero_groups(self):
        gl = jnp.zeros((0,), jnp.int32)
        sp = jnp.zeros((0, grouped.GROUP), jnp.int32)
        d, i = grouped.scan_and_scatter(gl, sp, 8, 64, 5, True,
                                        grouped.block_size(0, 1024),
                                        None, None)
        assert d.shape == (8, 5) and i.shape == (8, 5)
        assert bool(jnp.all(jnp.isinf(d))) and bool(jnp.all(i == -1))

    def test_scan_and_scatter_zero_cap(self):
        gl = jnp.zeros((4,), jnp.int32)
        sp = jnp.zeros((4, grouped.GROUP), jnp.int32)
        d, i = grouped.scan_and_scatter(gl, sp, 8, 0, 5, False, 4,
                                        None, None, kt=3)
        # cap == 0: kt falls back to the requested kt, ids all sentinel
        assert d.shape == (8, 3) and i.shape == (8, 3)
        assert bool(jnp.all(jnp.isneginf(d))) and bool(jnp.all(i == -1))

    def test_finalize_topk_clamps_encoded_ids(self):
        # k exceeding the candidate count must never leak a tombstone
        # encoding (<= -2) into public results
        from raft_tpu.matrix.select_k import select_k
        outd = jnp.asarray([[0.5, jnp.inf, jnp.inf]], jnp.float32)
        outi = jnp.asarray([[3, -7, -9]], jnp.int32)
        d, i = grouped.finalize_topk(outd, outi, 1, 3, True, False,
                                     select_k)
        assert bool(jnp.all(i >= -1)), np.asarray(i)


class TestCagraShim:
    @pytest.fixture(scope="class")
    def built(self, res, dataset):
        # The delete shim only masks at search time, so an exact brute-force
        # kNN graph stands in for the (much slower) cagra.build pipeline.
        db, _ = dataset
        _, nbrs = naive_knn(db, db, 17)
        graph = jnp.asarray(nbrs[:, 1:].astype(np.int32))
        return cagra.Index(dataset=jnp.asarray(db), graph=graph), db

    def test_delete_masks_results(self, res, dataset, built):
        index, db = built
        _, q = dataset
        _, ti = naive_knn(db, q, 1)
        doomed = sorted(set(ti[:, 0].tolist()))
        idx2 = cagra.delete(res, index, doomed)
        assert mutate.deleted_ids(idx2) == frozenset(doomed)
        assert mutate.generation(idx2) == mutate.generation(index) + 1
        sp = cagra.SearchParams(itopk_size=32)
        _, i2 = cagra.search(res, sp, idx2, q, 10)
        assert not (set(np.asarray(i2).reshape(-1).tolist()) & set(doomed))
        # parent snapshot still serves the deleted rows
        _, i1 = cagra.search(res, sp, index, q, 1)
        assert set(np.asarray(i1).reshape(-1).tolist()) & set(doomed)

    def test_delete_accumulates(self, res, built):
        index, _ = built
        idx2 = cagra.delete(res, index, [1, 2])
        idx3 = cagra.delete(res, idx2, [3])
        assert mutate.deleted_ids(idx3) == frozenset({1, 2, 3})

    def test_results_stay_sorted_after_mask(self, res, dataset, built):
        index, db = built
        _, q = dataset
        idx2 = cagra.delete(res, index, list(range(0, 100)))
        d2, _ = cagra.search(res, cagra.SearchParams(itopk_size=32),
                             idx2, q, 10)
        d2 = np.asarray(d2)
        # masked slots carry +inf; cap them so inf-inf row tails don't
        # turn the monotonicity diff into NaN
        capped = np.where(np.isfinite(d2), d2, np.finfo(np.float32).max)
        assert np.all(np.diff(capped, axis=1) >= -1e-6)


class TestCanaryExclusion:
    @pytest.fixture(scope="class")
    def canaried(self, res, dataset):
        db, _ = dataset
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=5,
                                      canary_queries=16, canary_k=5,
                                      canary_floor=0.3)
        return ivf_flat.build(res, params, db)

    def test_health_check_survives_deleting_ground_truth(self, res,
                                                         canaried):
        # delete rows that ARE canary ground truth: recall would crater if
        # measure() kept counting them; the exclusion keeps it honest
        gt_ids = sorted(set(
            int(v) for v in np.asarray(canaried.canaries.gt_ids)
            .reshape(-1) if int(v) >= 0))
        doomed = gt_ids[:len(gt_ids) // 2]
        idx2 = ivf_flat.delete(res, canaried, doomed)
        report = _canary.health_check(res, idx2, raise_on_fail=True)
        assert report.ok

    def test_measure_all_ground_truth_deleted(self, res, canaried):
        gt_ids = sorted(set(
            int(v) for v in np.asarray(canaried.canaries.gt_ids)
            .reshape(-1) if int(v) >= 0))
        idx2 = ivf_flat.delete(res, canaried, gt_ids)
        # zero live ground truth -> vacuous 1.0, not a 0/0 crash
        assert _canary.measure(res, idx2, idx2.canaries) == 1.0


@pytest.mark.slow
class TestDistributedDelete:
    @pytest.fixture
    def session(self, mesh8):
        from raft_tpu.comms import CommsSession
        s = CommsSession(mesh=mesh8, axis_name="data").init()
        yield s
        s.destroy()

    @pytest.fixture
    def handle(self, session):
        return session.worker_handle(seed=0)

    def test_delete_excludes_global_ids(self, handle):
        from raft_tpu.distributed import ann
        rng = np.random.default_rng(3)
        db = rng.normal(size=(1024, 16)).astype(np.float32)
        q = rng.normal(size=(16, 16)).astype(np.float32)
        params = ivf_pq.IndexParams(n_lists=4, pq_dim=4, kmeans_n_iters=3)
        index = ann.build(handle, params, db)
        sp = ivf_pq.SearchParams(n_probes=4)
        _, i1 = ann.search(handle, sp, index, q, 10)
        doomed = sorted(set(np.asarray(i1)[:, 0].tolist()) - {-1})
        assert doomed
        idx2 = ann.delete(handle, index, doomed)
        assert mutate.generation(idx2) == mutate.generation(index) + 1
        _, i2 = ann.search(handle, sp, idx2, q, 10)
        assert not (set(np.asarray(i2).reshape(-1).tolist()) & set(doomed))
        # parent snapshot untouched
        _, i1b = ann.search(handle, sp, index, q, 10)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i1b))


class TestUpsert:
    """Satellite (PR 8): ``upsert`` = delete + extend under one id with
    ONE generation bump, so a churn loop advances generation-keyed
    caches once per batch instead of twice."""

    @pytest.fixture(scope="class")
    def flat_built(self, res, dataset):
        db, _ = dataset
        return ivf_flat.build(res, ivf_flat.IndexParams(
            n_lists=8, kmeans_n_iters=5), db)

    @pytest.fixture(scope="class")
    def pq_built(self, res, pq_dataset):
        db, _ = pq_dataset
        return ivf_pq.build(res, ivf_pq.IndexParams(
            n_lists=16, pq_dim=8, kmeans_n_iters=5), db)

    def test_flat_upsert_replaces_under_same_id(self, res, dataset,
                                                flat_built):
        db, _ = dataset
        rng = np.random.default_rng(81)
        ids = np.asarray([3, 40, 77], np.int32)
        vecs = rng.normal(size=(3, db.shape[1])).astype(np.float32) * 0.01
        out = ivf_flat.upsert(res, flat_built, ids, vecs)
        sp = ivf_flat.SearchParams(n_probes=8)
        _, i = ivf_flat.search(res, sp, out, vecs, 1)
        np.testing.assert_array_equal(np.sort(np.asarray(i).ravel()), ids)
        # the old rows under those ids no longer resolve: searching the
        # ORIGINAL vectors must not return the upserted ids at rank 0
        # from their old location (each id now lives at the new vector)
        d2, i2 = ivf_flat.search(res, sp, out, db[ids], 1)
        old_self_dist = np.asarray(d2)[np.asarray(i2).ravel() == ids]
        assert not np.any(np.isclose(old_self_dist, 0.0))

    def test_flat_one_generation_bump(self, res, dataset, flat_built):
        db, _ = dataset
        out = ivf_flat.upsert(res, flat_built, np.asarray([5], np.int32),
                              db[5:6] + 0.5)
        assert mutate.generation(out) == mutate.generation(flat_built) + 1

    def test_pq_upsert_replaces_under_same_id(self, res, pq_dataset,
                                              pq_built):
        db, _ = pq_dataset
        rng = np.random.default_rng(82)
        ids = np.asarray([10, 200, 999], np.int32)
        vecs = rng.normal(size=(3, db.shape[1])).astype(np.float32)
        out = ivf_pq.upsert(res, pq_built, ids, vecs)
        assert mutate.generation(out) == mutate.generation(pq_built) + 1
        sp = ivf_pq.SearchParams(n_probes=16)
        _, i = ivf_pq.search(res, sp, out, vecs, 1)
        np.testing.assert_array_equal(np.sort(np.asarray(i).ravel()), ids)
        # each id is live exactly once (the delete half removed the old
        # copy before the extend half appended the new one)
        li = np.asarray(out.list_indices)
        for v in ids:
            assert int((li == v).sum()) == 1

    def test_pq_upsert_inserts_fresh_ids(self, res, pq_dataset, pq_built):
        db, _ = pq_dataset
        fresh = np.asarray([db.shape[0] + 7], np.int32)  # not in index
        out = ivf_pq.upsert(res, pq_built, fresh, db[:1] * 1.001)
        li = np.asarray(out.list_indices)
        assert int((li == fresh[0]).sum()) == 1

    def test_pq_churn_loop(self, res, pq_dataset, pq_built):
        """Sustained upsert churn: repeatedly rewrite a rotating window
        of ids; generation advances by exactly one per batch, every id
        stays live exactly once, and recall against the evolving ground
        truth holds."""
        db, q = pq_dataset
        rng = np.random.default_rng(83)
        cur = np.array(db, copy=True)
        index = pq_built
        n = db.shape[0]
        sp = ivf_pq.SearchParams(n_probes=16)
        _, f0 = ivf_pq.search(res, sp, index, q, 10)
        _, t0 = naive_knn(db, np.asarray(q), 10)
        base_recall = recall(np.asarray(f0), t0)
        for rnd in range(4):
            ids = rng.choice(n, size=64, replace=False).astype(np.int32)
            # perturbed copies of other dataset rows: stays inside the
            # codebook's support so PQ recall is meaningful
            src = rng.choice(n, size=64).astype(np.int32)
            vecs = (db[src] + 0.05 * rng.normal(
                size=(64, db.shape[1]))).astype(np.float32)
            g = mutate.generation(index)
            index = ivf_pq.upsert(res, index, ids, vecs)
            assert mutate.generation(index) == g + 1
            cur[ids] = vecs
        li = np.asarray(index.list_indices)
        live = li[li >= 0]
        assert live.size == n and np.unique(live).size == n
        _, found = ivf_pq.search(res, sp, index, q, 10)
        _, truth = naive_knn(cur, np.asarray(q), 10)
        # churn must not degrade recall materially below the index's own
        # pre-churn recall (PQ quantization bounds both the same way)
        assert recall(np.asarray(found), truth) > base_recall - 0.08
