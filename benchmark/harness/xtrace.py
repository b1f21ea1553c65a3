"""Reduction of one traced sub-window to numbers: the profiler's device
trace (``.xplane.pb``) and the program's chunk spans, on one clock.

The traced interval is the benchmark's own host annotation (``MARKER``)
in the profiler trace. The program's spans are host ``perf_counter``
times; the annotation was entered at a known ``perf_counter`` time, which
gives the offset that puts them on the trace's clock.

Device time is the union of the op intervals on each TPU plane's
``XLA Ops`` line, clipped to the traced interval. Ops nest there (a
``while`` holds the ops of its body), so an op's own time is its
duration less that of the ops inside it. An op is named by its program
(the ``XLA Modules`` event around it) and its HLO name:
``jit_step(<fingerprint>)/%fusion.12``.
"""
from __future__ import annotations

import bisect
import collections
import re

MARKER = "siddhi_bench/traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def merge(intervals) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged) -> int:
    return sum(e - s for s, e in merged)


def gaps(merged, lo: int, hi: int) -> list:
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


class DeviceTrace:
    """Per TPU device: op intervals inside the traced interval."""

    def __init__(self, lo: int, hi: int, ops: dict):
        self.lo, self.hi = lo, hi
        self.ops = ops          # device id -> [(name, start, end)]

    @classmethod
    def from_file(cls, path: str) -> "DeviceTrace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        marks = [(ev.start_ns, ev.duration_ns) for plane in pd.planes
                 if not DEVICE_PLANE.match(plane.name)
                 for line in plane.lines for ev in line.events
                 if ev.name == MARKER]
        if len(marks) != 1:
            raise ValueError(f"{len(marks)} '{MARKER}' annotations in "
                             f"{path}, expected 1")
        lo = int(marks[0][0])
        hi = lo + int(marks[0][1])
        ops = {}
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if not m:
                continue
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((int(ev.start_ns), int(ev.duration_ns), ev.name)
                          for ev in lines.get(MODULES_LINE, []))
            starts = [mod[0] for mod in mods]
            evs = ops.setdefault(int(m.group(1)), [])
            for ev in lines.get(OPS_LINE, []):
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if e > lo and s < hi:
                    i = bisect.bisect_right(starts, s) - 1
                    mod = mods[i][2] if i >= 0 and \
                        s < mods[i][0] + mods[i][1] else "?"
                    name = f"{mod}/{ev.name.split(' = ', 1)[0]}"
                    evs.append((name, max(s, lo), min(e, hi)))
        return cls(lo, hi, ops)

    @property
    def window_ns(self) -> int:
        return self.hi - self.lo

    def devices(self) -> list:
        return sorted(d for d, evs in self.ops.items() if evs)

    def busy_total_ns(self) -> int:
        """Device-busy time summed over the devices."""
        return sum(self.busy_ns(d) for d in self.devices())

    def busy_ns(self, dev: int) -> int:
        return covered(merge((s, e) for _, s, e in self.ops[dev]))

    def mean_busy_ns(self) -> float:
        devs = self.devices()
        return sum(self.busy_ns(d) for d in devs) / len(devs) if devs \
            else 0.0

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` ops with the most own time, summed over devices."""
        tot = collections.Counter()
        for evs in self.ops.values():
            spans = [(name, s, e, 0) for name, s, e in evs]
            for name, ns in self_ns(spans).items():
                tot[name] += ns
        return [[name, ns / 1e9] for name, ns in tot.most_common(n)]

    def idle_gaps(self, spans, n: int = 10) -> list:
        """The ``n`` longest idle gaps over all devices, each named by
        the innermost host span open at its midpoint."""
        out = []
        for d in self.devices():
            merged = merge((s, e) for _, s, e in self.ops[d])
            for s, e in gaps(merged, self.lo, self.hi):
                out.append((e - s, open_span((s + e) // 2, spans)))
        out.sort(reverse=True)
        return [[name, ns / 1e9] for ns, name in out[:n]]


def open_span(t: int, spans) -> str:
    """Name of the shortest span (name, start, end, thread) holding t."""
    best = None
    for name, s, e, _ in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "none"


def self_ns(spans) -> dict:
    """Self time per span name: its duration less the union of the other
    spans of its thread that lie inside it."""
    by_tid = collections.defaultdict(list)
    for sp in spans:
        by_tid[sp[3]].append(sp)
    out = collections.Counter()
    for group in by_tid.values():
        group.sort(key=lambda sp: (sp[1], -sp[2]))
        for i, (name, s, e, _) in enumerate(group):
            inner = []
            for _, s2, e2, _ in group[i + 1:]:
                if s2 > e:
                    break
                if e2 <= e and (s2, e2) != (s, e):
                    inner.append((s2, e2))
            out[name] += (e - s) - covered(merge(inner))
    return dict(out)
