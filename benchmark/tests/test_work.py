"""The roofline's work counts against a count made by hand."""

import numpy as np

from benchmark import work

# four lists on a line; identity rotation
CENTERS = np.array([[0.0, 0.0], [1.0, 0.0], [2.0, 0.0], [10.0, 0.0]])
ROTATION = np.eye(2)
SIZES = np.array([3, 1, 2, 5])
POOL = np.array([[0.1, 0.0],     # nearest lists 0, 1
                 [1.9, 0.0],     # nearest lists 2, 1
                 [9.0, 0.0]])    # nearest lists 3, 2


def test_coarse_probes_are_the_exact_nearest_lists():
    p = work.coarse_probes(POOL, CENTERS, ROTATION, 2)
    assert [sorted(r) for r in p.tolist()] == [[0, 1], [1, 2], [2, 3]]


def test_scan_work_by_hand():
    p = work.coarse_probes(POOL, CENTERS, ROTATION, 2)
    w = work.scan_work([np.array([0, 1]), np.array([2])], p, SIZES,
                       dim=2, code_bytes=8)
    # batch 1: rows 0 and 1 probe {0,1} and {1,2}: pairs 3+1 + 1+2 = 7,
    # distinct lists {0,1,2} hold 6 rows; batch 2: row 2 probes {2,3}:
    # pairs 2+5 = 7, 7 rows read
    assert w["pairs"] == 14
    assert w["flops"] == 2 * 2 * 14
    assert w["bytes"] == (6 + 7) * (8 + work.ID_BYTES)


def test_least_seconds_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds({"flops": 200.0, "bytes": 10.0}, peak) == (
        2.0, "compute")
    assert work.least_seconds({"flops": 100.0, "bytes": 30.0}, peak) == (
        3.0, "memory")
