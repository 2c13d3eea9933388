"""Reduction of a profiler trace to device busy time, kernel and program
time, and idle gaps labelled by what the host was doing.

A trace is first flattened to ``Event`` rows (plane, line, name, start,
duration in ns); everything after that is plain arithmetic on rows, so the
tests can feed it a small recorded trace.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass

# the profiler's names on a TPU host
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans that label idle gaps: the harness's and the program's own
HOST_PREFIXES = ("chipbench.", "repro.")
WINDOW_SPAN = "chipbench.traced"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> list[Event]:
    """Flatten the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return []
    pd = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def union(intervals, lo: float, hi: float) -> tuple[float, list]:
    """Covered length of ``intervals`` clipped to [lo, hi], and the gaps
    between them as (start, length)."""
    busy, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s - cur))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if hi > cur:
        gaps.append((cur, hi - cur))
    return busy, gaps


def window(events: list[Event]) -> tuple[float, float] | None:
    """The traced window: the harness's span around the traced steps."""
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        return None
    return min(e.start_ns for e in spans), max(e.end_ns for e in spans)


def device_planes(events: list[Event]) -> list[str]:
    return sorted({e.plane for e in events if e.plane.startswith(DEVICE_PLANE)
                   and e.line == OPS_LINE})


def _clip(e: Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))


def op_name(name: str) -> str:
    """An XLA op event carries its whole HLO text; keep the instruction's
    name (``%paged_attention.9 = ...`` -> ``%paged_attention.9``)."""
    return name.split(" = ", 1)[0]


def self_times(rows: list[Event], lo: float, hi: float) -> dict:
    """Time each op ran inside [lo, hi] less the time of the ops nested in
    it (a ``while`` op spans its body's ops on the same line)."""
    out: dict = defaultdict(float)
    stack: list = []                      # [event, child time]
    for e in sorted(rows, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            done, kids = stack.pop()
            out[op_name(done.name)] += _clip(done, lo, hi) - kids
        if stack:
            stack[-1][1] += _clip(e, lo, hi)
        stack.append([e, 0.0])
    for done, kids in stack:
        out[op_name(done.name)] += _clip(done, lo, hi) - kids
    return out


@dataclass
class Reduced:
    window_ns: float
    busy_ns: float                       # mean over the device planes
    ops: dict                            # op name -> self ns, summed over planes
    modules: dict                        # program name -> [count, ns]
    gaps: list                           # (label, ns) idle pieces, plane 0
    n_planes: int

    def op_ns(self, needle: str) -> float:
        return sum(ns for name, ns in self.ops.items() if needle in name)

    def module(self, needle: str) -> tuple[int, float]:
        n = t = 0
        for name, (c, ns) in self.modules.items():
            if needle in name:
                n, t = n + c, t + ns
        return n, t


def _label(host: list[Event], t: float) -> str:
    """Innermost host span covering time ``t``."""
    best = None
    for e in host:
        if e.start_ns <= t <= e.end_ns and (best is None or e.dur_ns < best.dur_ns):
            best = e
    return best.name if best is not None else "no host span"


def _split(host: list[Event], start: float, length: float) -> list:
    """An idle gap cut at the host spans' edges, each piece labelled by the
    innermost span over it: (label, ns)."""
    end = start + length
    cuts = sorted({start, end} | {t for e in host for t in (e.start_ns, e.end_ns)
                                  if start < t < end})
    return [(_label(host, (a + b) / 2), b - a) for a, b in zip(cuts, cuts[1:])]


def reduce(events: list[Event]) -> Reduced | None:
    """Busy time, op and program totals and labelled gaps inside the
    traced window; None where the trace holds no window or no device."""
    win = window(events)
    planes = device_planes(events)
    if win is None or not planes:
        return None
    lo, hi = win
    ops: dict = defaultdict(float)
    modules: dict = defaultdict(lambda: [0, 0.0])
    busy_total, gaps0 = 0.0, []
    for i, plane in enumerate(planes):
        rows = [e for e in events if e.plane == plane]
        op_rows = [e for e in rows if e.line == OPS_LINE]
        busy, gaps = union(((e.start_ns, e.end_ns) for e in op_rows), lo, hi)
        busy_total += busy
        if i == 0:
            gaps0 = gaps
        for name, ns in self_times(op_rows, lo, hi).items():
            ops[name] += ns
        for e in rows:
            if e.line == MODULES_LINE and lo <= e.start_ns < hi:
                modules[e.name][0] += 1
                modules[e.name][1] += e.dur_ns
    host = [e for e in events if not e.plane.startswith(DEVICE_PLANE)
            and e.name.startswith(HOST_PREFIXES) and e.name != WINDOW_SPAN]
    near = [e for e in host if e.end_ns >= lo and e.start_ns <= hi]
    labelled = [piece for s, n in gaps0 for piece in _split(near, s, n)]
    return Reduced(hi - lo, busy_total / len(planes), dict(ops),
                   {k: tuple(v) for k, v in modules.items()}, labelled,
                   len(planes))


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The device ops that took most time and the idle time by host span,
    in seconds."""
    ops = sorted(r.ops.items(), key=lambda kv: -kv[1])[:top]
    by_label: dict = defaultdict(float)
    for label, ns in r.gaps:
        by_label[label] += ns
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, ns / r.n_planes * 1e-9] for n, ns in ops],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in gaps]}
