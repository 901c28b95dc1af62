"""Profiler trace capture and its reduction to the numbers the per-layer
metrics read.

``capture`` runs a function under ``jax.profiler`` and reads the
``.xplane.pb`` it writes with ``jax.profiler.ProfileData``.  ``events``
keeps what the reduction needs, which is also the form of the small
recorded trace the tests read: per device the operations that ran on it
(name, start, duration in ns), and the host spans of the benchmark's own
(names starting with ``bench.``), all on the profiler's one clock.

Busy time is the union of the intervals in which an operation runs on a
device; idle share is one minus busy over the window.  A kernel's time
is the sum of its events' durations.  A collective is exposed where it
runs and no other operation does.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "send", "recv")

Interval = Tuple[int, int]


def capture(fn, directory: str):
    """Run ``fn()`` under the profiler, writing into ``directory``;
    returns (fn's result, the trace's events)."""
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    jax.profiler.start_trace(directory)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {directory}")
    return out, events(max(files, key=os.path.getmtime))


def events(path: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "spans": [[name, start_ns, end_ns], ...]} of one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    devices.setdefault(plane.name, []).extend(
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        spans.append([e.name, s, s + int(e.duration_ns)])
    return {"devices": devices, "spans": spans}


def load(path: str) -> dict:
    """Events kept as gzipped JSON, as the tests' recorded trace is."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------ reduction

def union(intervals: List[Interval]) -> List[Interval]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: List[Interval], w: Interval) -> List[Interval]:
    return [(max(a, w[0]), min(b, w[1])) for a, b in intervals
            if b > w[0] and a < w[1]]


def length(intervals: List[Interval]) -> int:
    return sum(b - a for a, b in intervals)


class Reduced:
    """A trace's events, with the window the benchmark marked."""

    def __init__(self, ev: dict):
        self.ops = {d: [(n, s, s + t) for n, s, t in evs]
                    for d, evs in ev["devices"].items() if evs}
        self.spans = [tuple(s) for s in ev["spans"]]
        win = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if win:
            self.window = (min(s[1] for s in win), max(s[2] for s in win))
        else:
            allops = [x for ops in self.ops.values() for x in ops]
            self.window = (min(x[1] for x in allops),
                           max(x[2] for x in allops))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self, device: str) -> List[Interval]:
        return clip(union([(s, e) for _, s, e in self.ops[device]]),
                    self.window)

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(length(self.busy(d)) for d in self.ops) / len(
            self.ops) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, match) -> float:
        """Device seconds, summed over devices, of the window's
        operations whose name ``match`` accepts."""
        return sum(e - s for ops in self.ops.values()
                   for n, s, e in clip_ops(ops, self.window)
                   if match(n)) / 1e9

    def exposed_collective_s(self) -> float:
        """Seconds, averaged over devices, in which a collective runs
        and no other operation does."""
        total = 0
        for ops in self.ops.values():
            ops = clip_ops(ops, self.window)
            coll = union([(s, e) for n, s, e in ops if is_collective(n)])
            comp = union([(s, e) for n, s, e in ops
                          if not is_collective(n)])
            total += length(coll) - length(intersect(coll, comp))
        return total / max(len(self.ops), 1) / 1e9

    def top_ops(self, n: int = 10) -> List[list]:
        """The operations that took most device time, [name, seconds],
        each named by its HLO instruction name."""
        acc: Dict[str, int] = {}
        for ops in self.ops.values():
            for name, s, e in clip_ops(ops, self.window):
                name = short_name(name)
                acc[name] = acc.get(name, 0) + e - s
        top = sorted(acc.items(), key=lambda x: -x[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The longest idle gaps of the first device, each named by the
        innermost benchmark span open across most of it."""
        if not self.ops:
            return []
        dev = sorted(self.ops)[0]
        busy = self.busy(dev)
        edges = [self.window[0]] + [x for ab in busy for x in ab] + \
            [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
        return [[self.span_at(g), (g[1] - g[0]) / 1e9] for g in gaps]

    def span_at(self, gap: Interval) -> str:
        """The span that covers most of ``gap``; of equal covers, the
        shortest (the innermost).  "none" where no span covers it."""
        best, key = "none", (0, 0)
        for name, s, e in self.spans:
            over = min(e, gap[1]) - max(s, gap[0])
            if name != WINDOW_SPAN and over > 0 and \
                    (over, s - e) > key:
                best, key = name, (over, s - e)
        return best


def clip_ops(ops, w: Interval):
    return [(n, max(s, w[0]), min(e, w[1])) for n, s, e in ops
            if e > w[0] and s < w[1]]


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_collective(name: str) -> bool:
    low = short_name(name).lower()
    return any(c in low for c in COLLECTIVES)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out
