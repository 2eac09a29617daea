"""Find the knee of an open-loop cell: the highest offered rate at which
completed rows/s keeps up with offered rows/s and the queue does not grow.

    python3 benchmark/sweep.py --workload sift1m-cagra.online --seed <n> \\
        --seconds 5 --rates 2000,4000,8000

One set-up, then one window per rate (the cell's own mix at that rate),
in the order given; one JSON line per rate.  A rate keeps up when
completed rows/s is at least ``KEEP_UP`` of offered rows/s and the last
answer arrives within ``DRAIN_LIMIT_S`` of the window's close.  The cell's
rate in its traffic file is then 0.8 x the highest rate that keeps up.
Runs on a TPU only, like the benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path[0] = str(Path(__file__).resolve().parents[1])

from benchmark import run  # noqa: E402

KEEP_UP = 0.98
DRAIN_LIMIT_S = 0.1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True,
                    help="offered rows/s, comma-separated")
    args = ap.parse_args(argv)
    cell = run.cell_spec(args.workload)
    try:
        run.check_device(cell["chips"])
    except run.Refused as e:
        print(f"sweep: refused: {e}", file=sys.stderr)
        return 2
    run.setup_compile_cache()
    p = run.prepare(cell, args.seed)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            win = p.loop.window(args.seconds, rate_rows_per_s=rate)
            n = win.notes
            late = n["last_answer_after_close_s"]
            keeps_up = (n["completed_rows_per_s"]
                        >= KEEP_UP * n["offered_rows_per_s"]
                        and late is not None and late <= DRAIN_LIMIT_S
                        and win.failed == 0)
            print(json.dumps(dict(n, **win.metrics, rate_rows_per_s=rate,
                                  failed=win.failed, keeps_up=keeps_up),
                             default=float), flush=True)
    finally:
        p.loop.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
