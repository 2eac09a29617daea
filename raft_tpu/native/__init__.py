"""Native (C++) host-side runtime components, loaded via ctypes.

The analogue of the reference's compiled host layer: the pieces that are
inherently sequential host work (union-find dendrogram labeling,
agglomerative/detail/agglomerative.cuh's ``build_dendrogram_host``) run as
C++ with a plain C ABI.  The shared library is compiled on first use with
the system toolchain (g++); every entry point has a pure-Python fallback so
the package works without a compiler.
"""

from __future__ import annotations

import ctypes
import os
import pathlib
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_HERE = pathlib.Path(__file__).parent
_SO = _HERE / "libagglomerative.so"
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


_SRC = _HERE / "agglomerative.cpp"


def _stale() -> bool:
    """True when the library is missing or older than its source."""
    return (not _SO.exists()
            or _SO.stat().st_mtime < _SRC.stat().st_mtime)


def _compile() -> bool:
    # build beside the target, then rename: a process that loads the
    # library concurrently never sees a half-written file
    tmp = _SO.with_name(f"{_SO.name}.{os.getpid()}.tmp")
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", str(_SRC), "-o", str(tmp)],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError):
        tmp.unlink(missing_ok=True)
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("RAFT_TPU_DISABLE_NATIVE"):
            return None
        if _stale() and not _compile():
            return None
        try:
            lib = ctypes.CDLL(str(_SO))
        except OSError:
            return None
        i32p = ctypes.POINTER(ctypes.c_int32)
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.raft_tpu_build_dendrogram.restype = ctypes.c_int64
        lib.raft_tpu_build_dendrogram.argtypes = [
            i32p, i32p, f32p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, i32p, i32p, f32p]
        lib.raft_tpu_connected_components.restype = ctypes.c_int64
        lib.raft_tpu_connected_components.argtypes = [
            i32p, i32p, ctypes.c_int64, ctypes.c_int64, i32p]
        _lib = lib
        return _lib


def available() -> bool:
    """True when the native library is loaded (or compilable)."""
    return _load() is not None


def _as_i32(a) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.int32)


def build_dendrogram(src, dst, w, n: int, n_clusters: int
                     ) -> Optional[Tuple[np.ndarray, np.ndarray,
                                         np.ndarray]]:
    """Native union-find dendrogram (reference:
    detail/agglomerative.cuh ``build_dendrogram_host``).  Returns
    (labels (n,), dendrogram (merges, 2), heights (merges,)) or None when
    the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    src = _as_i32(src)
    dst = _as_i32(dst)
    w = np.ascontiguousarray(w, dtype=np.float32)
    n_edges = src.shape[0]
    max_merges = max(n - n_clusters, 0)
    labels = np.empty(n, np.int32)
    dendro = np.empty(2 * max_merges, np.int32)
    heights = np.empty(max_merges, np.float32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    merges = lib.raft_tpu_build_dendrogram(
        src.ctypes.data_as(i32p), dst.ctypes.data_as(i32p),
        w.ctypes.data_as(f32p), n_edges, n, n_clusters,
        labels.ctypes.data_as(i32p), dendro.ctypes.data_as(i32p),
        heights.ctypes.data_as(f32p))
    return (labels, dendro[:2 * merges].reshape(-1, 2),
            heights[:merges])


def connected_components(src, dst, n: int
                         ) -> Optional[Tuple[np.ndarray, int]]:
    """Native connected components over an edge list; returns
    (labels (n,), n_components) or None when unavailable."""
    lib = _load()
    if lib is None:
        return None
    src = _as_i32(src)
    dst = _as_i32(dst)
    labels = np.empty(n, np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    n_comp = lib.raft_tpu_connected_components(
        src.ctypes.data_as(i32p), dst.ctypes.data_as(i32p),
        src.shape[0], n, labels.ctypes.data_as(i32p))
    return labels, int(n_comp)
