"""The control (the reference in the program's place, one precision
lower) and the planted faults come out not correct under every cell's
limits, at a size a test run holds; ``benchmark/control.py`` reads the
same at the cells' own size on the chip."""

import pytest

from benchmark import compare, control, run
from benchmark.tests.small import CELLS

SIZE = {"n_db": 100000, "n_queries": 1000}


@pytest.mark.parametrize("seed", [11, 12, 2**31 + 13])
def test_control_and_faults_are_refused(seed):
    cells = [run.cell_spec(n) for n in CELLS]
    cfg = cells[0]["config"]
    cfg["dataset"].update(SIZE)
    for planted, numbers in control.readings(cfg, seed, 64).items():
        for c in cells:
            ok, checks = compare.judge(numbers, c["config"]["limits"])
            assert not ok, (planted, c["name"], checks)
