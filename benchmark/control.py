"""Readings of the comparison's control and of the planted faults, at a
cell's own size; the upper readings that each limit is set below.

    python3 benchmark/control.py --workload <cell>[,<cell>...] \\
        --seeds 11,12,13

For each seed: the cell's data and the reference's answers for the whole
query pool, then, in the program's place,

- ``control``: the reference computed in the next precision below the
  configuration's float32 at ``highest``: three bf16 passes (``high``);
- ``answer_altered``: the reference's answers with one answer in each
  batch altered where it is produced (another row's id in first place,
  its distance kept);
- ``half_batch``: each batch's second half answered with its first half's
  answers.

Each prints its compared numbers and whether the cell's limits call it
correct (they must not).  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    sys.path[0] = str(Path(__file__).resolve().parents[1])

from benchmark import compare, data, reference  # noqa: E402

FAULT_BATCH = 64


def answer_altered(ids, dists, rng, batch: int, n_db: int):
    ids = ids.copy()
    for s in range(0, ids.shape[0], batch):
        r = s + int(rng.integers(min(batch, ids.shape[0] - s)))
        ids[r, 0] = (ids[r, 0] + 1 + int(rng.integers(n_db - 1))) % n_db
    return ids, dists


def half_batch(ids, dists, batch: int):
    ids, dists = ids.copy(), dists.copy()
    for s in range(0, ids.shape[0], batch):
        n = min(batch, ids.shape[0] - s)
        h = n // 2
        ids[s + h:s + 2 * h] = ids[s:s + h]
        dists[s + h:s + 2 * h] = dists[s:s + h]
    return ids, dists


def readings(cfg: dict, seed: int, batch: int) -> dict:
    """The compared numbers of the control and each fault for one seed."""
    k = int(cfg["index"]["k"])
    db, pool = data.make(seed, cfg["dataset"])
    ref_d, ref_i = reference.knn(pool, db, k)
    rows = np.arange(pool.shape[0])
    n_db = db.shape[0]
    rng = np.random.default_rng(seed)
    out = {}
    ctrl_d, ctrl_i = reference.knn(pool, db, k, precision="high")
    for name, (i, d) in {"control": (ctrl_i, ctrl_d),
                         "answer_altered": answer_altered(ref_i, ref_d, rng,
                                                         batch, n_db),
                         "half_batch": half_batch(ref_i, ref_d, batch)
                         }.items():
        v = compare.numbers(pool, db, rows, i, d, ref_i)
        v["lost"] = 0
        out[name] = v
    return out


def main(argv=None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="cells sharing one configuration, comma-separated")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cells = [run.cell_spec(w) for w in args.workload.split(",")]
    try:
        run.check_device(max(c["chips"] for c in cells))
    except run.Refused as e:
        print(f"control: refused: {e}", file=sys.stderr)
        return 2
    run.setup_compile_cache()
    cfg = cells[0]["config"]
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, v in readings(cfg, seed, FAULT_BATCH).items():
            judged = {c["name"]: compare.judge(v, c["config"]["limits"])[0]
                      for c in cells}
            print(json.dumps({"seed": seed, "planted": name, "numbers": v,
                              "correct": judged}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
