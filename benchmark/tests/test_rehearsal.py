"""A small CPU rehearsal of each cell: the same functions as a chip run,
at a size a test can hold.  Only the checks that the timed path reached
its Pallas kernels and never fell back off them are stood down (the CPU
runs the XLA twins)."""

import time

import pytest

from benchmark import run
from benchmark.tests.small import CELLS, PEAK, SEED, small


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name, trace, on_cpu):
    cell = small(run.cell_spec(name))
    out = run.run_cell(cell, SEED, 1.0, bool(trace), PEAK,
                       time.perf_counter())
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    want = cell["per_layer" if trace else "end_to_end"]
    if trace:
        # no device plane on the CPU: only the program's own counters and
        # spans can be read, and each of them is
        assert set(out["metrics"]) <= {m["name"] for m in want}
        assert set(out["metrics"]) >= {m["name"] for m in want
                                       if m["source"] != "device_trace"}
        assert "breakdown" in out
    else:
        assert set(out["metrics"]) == {m["name"] for m in want}
        assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("config,traffic", [("sift1m-ivfpq", "online"),
                                            ("sift1m-cagra", "batch5k")])
def test_each_index_kind_runs_under_each_loop(config, traffic, on_cpu):
    # the pairings no cell of BENCHMARK.json holds yet: a later cell that
    # adds one brings only its entry
    root = run.ROOT / "benchmark"
    cell = small({"name": f"{config}.{traffic}", "chips": 1,
                  "config": run.load_json(root / "configs" / f"{config}.json"),
                  "traffic": run.load_json(root / "traffic"
                                           / f"{traffic}.json"),
                  "end_to_end": [], "per_layer": []})
    out = run.run_cell(cell, SEED, 1.0, False, PEAK, time.perf_counter())
    # served IVF-PQ answers carry PQ-approximate distances (the server
    # has no refine stage), so only validity and recall are held here
    checks = out["checks"]
    assert checks["invalid"]["value"] == 0
    assert checks["recall_shortfall"]["value"] < 0.2
    assert out["attempted"] > 0 and out["failed"] == 0
