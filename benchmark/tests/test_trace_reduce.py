"""The reduction from a profiler trace to device time."""

from pathlib import Path

import pytest

from benchmark import kernels, trace_reduce

SMALL = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]


def test_gaps_take_the_innermost_annotation_at_their_midpoint():
    label = trace_reduce._GapLabels([("bench.batch", 0, 100),
                                     ("bench.search", 10, 20),
                                     ("bench.readback", 60, 90)])
    assert label(12, 18) == "bench.search"
    assert label(30, 40) == "bench.batch"
    assert label(70, 80) == "bench.readback"
    assert label(120, 130) == "host.other"


@pytest.fixture(scope="module")
def small():
    return trace_reduce.reduce(str(SMALL))


def test_recorded_chip_trace_reduces(small):
    # recorded on one v5e by record_trace.py: two steps of a fused
    # IVF-PQ search + refine and a fused CAGRA walk
    assert small.n_devices == 1
    assert 0 < small.busy_s < small.window_s
    idle = small.window_s - small.busy_s
    assert sum(small.gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert sum(small.ops.values()) >= small.busy_s * (1 - 1e-9)


def test_recorded_chip_trace_finds_each_kernel(small):
    scan = small.seconds(kernels.SCAN)
    hop = small.seconds(kernels.HOP)
    refine = small.seconds(kernels.REFINE, table="modules")
    assert scan and hop and refine
    # kernels are ops on the busy timeline; a program's span can hold
    # short idle stretches of its own, so it is bounded by the window
    assert scan + hop <= small.busy_s * (1 + 1e-9)
    assert refine <= small.window_s
