"""Device idle time inside the library's own host spans.

The library labels its host phases with profiler annotations named
``raft_tpu:<name>`` (``raft_tpu.core.tracing.annotation``): its stages,
the serving dispatcher's phases and its host syncs.  ``reduce(path)``
reads one ``.xplane.pb`` and returns, over the window that the
benchmark's ``bench.window`` annotation spans (:mod:`benchmark.trace_reduce`
takes the window, the devices and their busy time the same way):

- ``spans``: name -> ``[count, seconds]``, the events of that name on any
  host thread that overlap the window, and their summed length within it;
- ``idle_in``: name -> the device-idle seconds inside the union of that
  name's intervals (any thread), per device and averaged over devices.
  There is no innermost rule: a gap inside two spans counts for both, so
  only disjoint phases add up;
- ``longest``: the longest idle gaps, each with the spans it overlaps.

Run as a script, it runs one cell as ``benchmark/run.py`` does, with the
same arguments, and logs the reduction of a ``--trace 1`` run's trace on a
``library spans`` line before the trace is deleted:

    python3 benchmark/spans.py --workload <cell> --seed <n> --seconds <s> \\
        --trace 1
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

if __package__ in (None, ""):
    sys.path[0] = str(Path(__file__).resolve().parents[1])

from benchmark import trace_reduce  # noqa: E402

PREFIX = "raft_tpu:"
Interval = Tuple[float, float]


@dataclasses.dataclass
class SpanSummary:
    window_s: float
    idle_s: float                       # averaged over devices
    n_devices: int
    spans: Dict[str, List[float]]       # name -> [count, seconds]
    idle_in: Dict[str, float]           # name -> idle seconds inside it
    longest: List[dict]                 # the longest gaps, spans overlapped

    def share(self, names: Sequence[str]) -> float:
        """100 x the idle seconds inside ``names``, summed, / the window."""
        return 100.0 * sum(self.idle_in.get(n, 0.0)
                           for n in names) / self.window_s


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that no busy interval covers."""
    merged = trace_reduce.union([(max(s, lo), min(e, hi)) for s, e in busy
                                 if min(e, hi) > max(s, lo)])
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def summarize(window: Interval, busy: List[List[Interval]],
              spans: List[Tuple[str, float, float]], scale: float = 1.0,
              n_longest: int = 5) -> SpanSummary:
    """The reduction on plain intervals: ``busy`` per device, ``spans`` as
    ``(name, start, end)`` from any thread; times multiply by ``scale``."""
    lo, hi = window
    by_name: Dict[str, List[Interval]] = {}
    counts: Dict[str, List[float]] = {}
    for name, s, e in spans:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        by_name.setdefault(name, []).append((s, e))
        c = counts.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * scale
    unions = {n: trace_reduce.union(iv) for n, iv in by_name.items()}
    nd = max(len(busy), 1)
    idle_in = {n: 0.0 for n in unions}
    idle = 0.0
    gaps_all = []
    for dev in busy:
        gaps = idle_gaps(dev, lo, hi)
        idle += sum(e - s for s, e in gaps)
        gaps_all += gaps
        for n, u in unions.items():
            idle_in[n] += overlap(gaps, u)
    longest = []
    for s, e in sorted(gaps_all, key=lambda g: g[0] - g[1])[:n_longest]:
        over = sorted(n for n, u in unions.items() if overlap([(s, e)], u))
        longest.append({"at_s": (s - lo) * scale, "idle_s": (e - s) * scale,
                        "in": over})
    return SpanSummary(window_s=(hi - lo) * scale, idle_s=idle * scale / nd,
                       n_devices=len(busy), spans=counts,
                       idle_in={n: v * scale / nd for n, v in idle_in.items()},
                       longest=longest)


def reduce(path: str, window: str = trace_reduce.WINDOW) -> SpanSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    win, spans, busy = [], [], []
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            busy.append([(ev.start_ns, ev.start_ns + ev.duration_ns)
                         for line in plane.lines
                         if line.name == trace_reduce.OP_LINE
                         for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == window:
                        win.append(iv)
                    elif ev.name.startswith(PREFIX):
                        spans.append((ev.name,) + iv)
    if not win:
        raise ValueError(f"trace has no {window!r} annotation")
    return summarize(win[0], busy, spans, scale=1e-9)


def _logging_reduce(log):
    """``trace_reduce.reduce`` that also logs the library spans."""
    orig = trace_reduce.reduce

    def wrapped(path, *a, **kw):
        s = reduce(path)
        log("library spans", window_s=s.window_s, idle_s=s.idle_s,
            idle_in=s.idle_in, spans=s.spans, longest=s.longest)
        return orig(path, *a, **kw)
    return wrapped


def main(argv=None) -> int:
    from benchmark import run

    trace_reduce.reduce = _logging_reduce(run.log)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
