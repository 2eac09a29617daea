"""The single reduction from a profiler trace to device time.

``reduce(path)`` reads one ``.xplane.pb`` written by ``jax.profiler``
and returns, over the window that the benchmark's ``bench.window``
annotation spans:

- ``window_s``: the window's length;
- ``busy_s``: the union of the intervals in which an operation ran on a
  device (events of each TPU plane's ``XLA Ops`` line), averaged over the
  devices;
- ``ops``: device seconds per operation name, summed over devices and
  divided by their number;
- ``modules``: the same per XLA program (``XLA Modules`` line);
- ``gaps``: device idle seconds within the window, by what the host was
  doing: the innermost ``bench.*`` annotation (other than the window)
  that holds each idle gap's midpoint, or ``host.other`` where none does.

Kernels and programs are matched by regular expressions on the names the
trace gives them (:func:`Summary.seconds`); ``benchmark/kernels.py`` holds
the patterns.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    n_devices: int
    ops: Dict[str, float]
    modules: Dict[str, float]
    gaps: Dict[str, float]

    def seconds(self, patterns: Sequence[str],
                table: str = "ops") -> Optional[float]:
        """Device seconds of every op (or module, ``table="modules"``)
        whose name matches one of ``patterns``; None when none does."""
        rx = re.compile("|".join(f"(?:{p})" for p in patterns))
        hit = [s for name, s in getattr(self, table).items()
               if rx.search(name)]
        return sum(hit) if hit else None

    def top_ops(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.ops.items(),
                                           key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.gaps.items(),
                                           key=lambda kv: -kv[1])[:n]]


def find_xplane(directory: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace dir."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


class _GapLabels:
    """Labels idle gaps, taken in time order, by the innermost host
    annotation that holds the gap's midpoint."""

    def __init__(self, spans) -> None:
        self.spans = sorted(spans, key=lambda sp: sp[1])
        self.j, self.active = 0, []

    def __call__(self, gs, ge) -> str:
        m = 0.5 * (gs + ge)
        while self.j < len(self.spans) and self.spans[self.j][1] <= m:
            self.active.append(self.spans[self.j])
            self.j += 1
        self.active = [sp for sp in self.active if sp[2] >= m]
        if not self.active:
            return "host.other"
        return max(self.active, key=lambda sp: sp[1])[0]


def reduce(path: str, window: str = WINDOW) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(n, s, e) for n, s, e in _events(line)
                          if n.startswith(HOST_PREFIX)]
    win = [(s, e) for n, s, e in spans if n == window]
    if not win:
        raise ValueError(f"trace has no {window!r} annotation")
    lo, hi = win[0]
    spans = [sp for sp in spans if sp[0] != window]
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    busy = 0.0
    for plane in devices:
        label = _GapLabels(spans)
        busy_iv = []
        for line in plane.lines:
            if line.name not in (OP_LINE, MODULE_LINE):
                continue
            table = ops if line.name == OP_LINE else modules
            for name, s, e in _events(line):
                s, e = _clip(s, e, lo, hi)
                if e <= s:
                    continue
                table[name] = table.get(name, 0.0) + (e - s)
                if line.name == OP_LINE:
                    busy_iv.append((s, e))
        merged = union(busy_iv)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                lab = label(gs, ge)
                gaps[lab] = gaps.get(lab, 0.0) + (ge - gs)
    nd = max(len(devices), 1)
    scale = 1e-9 / nd
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * scale,
                   n_devices=len(devices),
                   ops={k: v * scale for k, v in ops.items()},
                   modules={k: v * scale for k, v in modules.items()},
                   gaps={k: v * scale for k, v in gaps.items()})
