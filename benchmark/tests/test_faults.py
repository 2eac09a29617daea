"""A run with its timed path broken underneath must come out not correct:
each fault a search cell can have, planted where the answer is produced,
on the same small cells as the rehearsal."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run, systems
from benchmark.tests.small import CELLS, PEAK, SEED, small


def answer_altered(d, i):
    # one answer of the batch names another row, its distance kept
    return d, i.at[0, 0].set((i[0, 0] + 1) % 3000)


def half_batch(d, i):
    # the second half of the batch gets the first half's answers
    h = i.shape[0] // 2
    return (d.at[h:2 * h].set(d[:h]), i.at[h:2 * h].set(i[:h]))


FAULTS = {"answer_altered": answer_altered, "half_batch": half_batch}


def _plant(monkeypatch, kind, fault):
    mod = systems.load(kind)
    batch_fn, executor = mod.batch_fn, mod.executor

    def broken_batch_fn(*a, **kw):
        fn = batch_fn(*a, **kw)
        return lambda q: fault(*fn(q))

    def broken_executor(*a, **kw):
        ex = executor(*a, **kw)
        search = ex.search_bucket

        def broken(queries, n, k, **kw2):
            d, i = search(queries, n, k, **kw2)
            return fault(jnp.asarray(d), jnp.asarray(i))
        ex.search_bucket = broken
        return ex
    monkeypatch.setattr(mod, "batch_fn", broken_batch_fn)
    monkeypatch.setattr(mod, "executor", broken_executor)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(name, fault, on_cpu,
                                          monkeypatch):
    cell = small(run.cell_spec(name))
    _plant(monkeypatch, cell["config"]["index"]["kind"], FAULTS[fault])
    out = run.run_cell(cell, SEED + 1, 1.0, False, PEAK,
                       time.perf_counter())
    assert not out["correct"], out["checks"]
    failed = [n for n, c in out["checks"].items()
              if not c["value"] <= c["limit"]]
    assert failed and np.isfinite(out["checks"]["dist_gap"]["value"])
