"""Native C++ host components: parity with the pure-Python fallbacks."""

import os
import subprocess

import numpy as np
import pytest

from raft_tpu import native


requires_native = pytest.mark.skipif(not native.available(),
                                     reason="no C++ toolchain")


@requires_native
class TestBuildDendrogram:
    def test_matches_python_fallback(self):
        rng = np.random.default_rng(0)
        n, n_edges = 200, 400
        src = rng.integers(0, n, n_edges).astype(np.int32)
        dst = rng.integers(0, n, n_edges).astype(np.int32)
        w = rng.random(n_edges).astype(np.float32)

        labels_n, dendro_n, h_n = native.build_dendrogram(src, dst, w, n, 5)

        os.environ["RAFT_TPU_DISABLE_NATIVE"] = "1"
        try:
            from raft_tpu.cluster.single_linkage import (
                _host_union_find_labels)
            # force the fallback by reloading the guard
            native._lib = None
            native._tried = False
            labels_p, dendro_p, h_p = _host_union_find_labels(
                src, dst, w, n, 5)
        finally:
            del os.environ["RAFT_TPU_DISABLE_NATIVE"]
            native._lib = None
            native._tried = False

        np.testing.assert_array_equal(labels_n, labels_p)
        np.testing.assert_array_equal(dendro_n, dendro_p)
        np.testing.assert_allclose(h_n, h_p)

    def test_connected_components(self):
        # two components: a chain 0-1-2 and a pair 3-4; node 5 isolated
        src = np.asarray([0, 1, 3], np.int32)
        dst = np.asarray([1, 2, 4], np.int32)
        labels, n_comp = native.connected_components(src, dst, 6)
        assert n_comp == 3
        assert labels[0] == labels[1] == labels[2]
        assert labels[3] == labels[4]
        assert len({labels[0], labels[3], labels[5]}) == 3

    def test_sentinel_edges_skipped(self):
        src = np.asarray([0, -1, 2], np.int32)
        dst = np.asarray([1, 5, 3], np.int32)
        labels, n_comp = native.connected_components(src, dst, 6)
        assert n_comp == 4      # {0,1}, {2,3}, {4}, {5}


@requires_native
def test_stale_library_is_rebuilt(monkeypatch, tmp_path):
    """A library older than its source is rebuilt before it is loaded."""
    import shutil

    src = tmp_path / "agglomerative.cpp"
    shutil.copy(native._SRC, src)
    so = tmp_path / "libagglomerative.so"
    so.write_bytes(b"not a library")
    os.utime(so, (0, 0))
    monkeypatch.setattr(native, "_SRC", src)
    monkeypatch.setattr(native, "_SO", so)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert native._stale()
    assert native._load() is not None
    assert not native._stale()
    labels, n_comp = native.connected_components(
        np.asarray([0], np.int32), np.asarray([1], np.int32), 3)
    assert n_comp == 2


def test_single_linkage_end_to_end_uses_whatever_is_available(res):
    """single_linkage must give identical results whichever backend the
    union-find runs on."""
    from raft_tpu.cluster.single_linkage import single_linkage
    from raft_tpu.random import make_blobs
    X, y = make_blobs(300, 8, n_clusters=3, cluster_std=0.4, seed=11)
    out = single_linkage(res, np.asarray(X), n_clusters=3)
    assert out.n_clusters == 3
    # blobs are well separated: labels must match ground truth up to
    # permutation
    y = np.asarray(y)
    for cl in range(3):
        assert len(set(out.labels[y == cl].tolist())) == 1
