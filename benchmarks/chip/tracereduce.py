"""Reduction of a profiler trace to device busy time, idle gaps and the
device programs that took the most time.

The trace is the `.xplane.pb` that `jax.profiler` writes, read with
`jax.profiler.ProfileData`. On each device plane (`/device:TPU:<n>`) the
"XLA Modules" line holds one event per program run (`jit_scan(...)`,
`jit_while(...)`, ...); its per-op line is not read: the AM handler's
scan alone puts millions of op events there in one batch. The window and
the host spans are the benchmark's own `jax.profiler.TraceAnnotation`s,
whose names start with `SPAN_PREFIX`, on the host plane. All carry
nanoseconds on the profiler's one clock.

Busy time is the union of the program intervals inside the window; the
idle share is 1 minus busy over the window. Each idle gap is labelled by
the innermost host span open at its midpoint. Where the device's trace
buffer overflowed (a "Trace Buffers Dropped" event), the traced window
ends where the drop begins and only the batches completed before it
count.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DROPPED = "Trace Buffers Dropped"
CALL_SPAN = "bench.front_end_call"
TOP = 10

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval of `busy` (disjoint, sorted)
    covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label(gap: Interval, spans: Sequence[Tuple[float, float, str]]) -> str:
    """Name of the innermost span that holds the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2][len(SPAN_PREFIX):] if best else "outside_spans"


def self_times(events: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Per name, the time of its events less that of events nested in
    them (one line's events nest or follow each other)."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[List] = []          # [end, name, child time]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm, child = stack.pop()
            out[nm] -= child
        if stack:
            stack[-1][2] += e - s
        out[name] += e - s
        stack.append([e, name, 0.0])
    while stack:
        _, nm, child = stack.pop()
        out[nm] -= child
    return dict(out)


@dataclasses.dataclass
class Summary:
    window_s: float             # traced: up to a buffer drop, if any
    busy_s: float               # averaged over the device planes
    devices: int
    batches: int                # front-end calls completed in window_s
    device_ops: List[Tuple[str, float]]     # programs, by self time
    idle_gaps: List[Tuple[str, float]]
    dropped_s: float = 0.0      # window seconds lost to a buffer drop

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def op_name(name: str) -> str:
    """An op's name without the HLO text that TPU traces append:
    "%fusion.10 = s32[...] fusion(...)" -> "fusion.10"."""
    return name.split(" = ", 1)[0].lstrip("%")


def events_of(pd) -> Tuple[Dict[str, List], List]:
    """(device events per device plane, host spans), each event
    (start_s, end_s, name); a buffer drop is named `DROPPED`."""
    dev: Dict[str, List] = {}
    spans = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    continue
                evs = [(e.start_ns, e.duration_ns, e.name)
                       for e in line.events
                       if line.name == MODULES_LINE or e.name == DROPPED]
                dev.setdefault(plane.name, []).extend(
                    (s * 1e-9, (s + d) * 1e-9, n) for s, d, n in evs)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9,
                                      e.name))
    return dev, spans


def reduce(dev: Dict[str, List], spans: List) -> Optional[Summary]:
    """Summary of the window span; None where the trace has no window or
    no device op in it."""
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not win or not dev:
        return None
    lo, end = win[0]
    drops = [s for evs in dev.values() for s, _, n in evs
             if n == DROPPED and s < end]
    hi = max(lo, min(drops)) if drops else end
    busy, progs, all_gaps = 0.0, collections.defaultdict(float), []
    for evs in dev.values():
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in evs
                  if e > lo and s < hi and n != DROPPED]
        merged = union([(s, e) for s, e, _ in inside])
        busy += total(merged)
        for n, t in self_times(inside).items():
            progs[n] += t
        all_gaps += gaps(merged, lo, hi)
    batches = sum(1 for s, e, n in spans
                  if n == CALL_SPAN and lo <= s and e <= hi)
    if busy <= 0:
        return None
    inner = [x for x in spans if x[2] != WINDOW_SPAN]
    top_gaps = sorted(all_gaps, key=lambda g: g[1] - g[0], reverse=True)[:TOP]
    return Summary(
        window_s=hi - lo, busy_s=busy / len(dev), devices=len(dev),
        batches=batches,
        device_ops=sorted(progs.items(), key=lambda kv: -kv[1])[:TOP],
        idle_gaps=[(label(g, inner), g[1] - g[0]) for g in top_gaps],
        dropped_s=end - hi)


def read_dir(log_dir: str) -> Optional[Summary]:
    """Summary of the one trace that `jax.profiler` wrote under log_dir."""
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    pd = jax.profiler.ProfileData.from_file(paths[0])
    return reduce(*events_of(pd))
