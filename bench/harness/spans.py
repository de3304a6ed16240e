"""The program's own spans in a profiler trace, and the device's idle time
named by them.

The program opens `repro.<what>` spans (`repro.obs.span`) on the host
planes, beside the benchmark's `bench.<what>`. Clipped to the window
`bench.window`:

  reduce  per `repro.` span name:
            inclusive_s  time under spans of that name
            self_s       the same, less the part their child spans cover:
                         the `repro.` spans nested in them on one thread
            count        spans of that name that overlap the window
          Spans on different threads never nest in one another.
  gaps    the device's idle stretches (no `XLA Ops` event on a device), as
          `trace.reduce` finds them: the longest, each named by the
          innermost `bench.` or `repro.` span open at its middle, and the
          idle time inside at least one `repro.` span, averaged over the
          devices.
"""
from __future__ import annotations

import dataclasses

from . import trace as tr

PREFIX = "repro."
PREFIXES = ("bench.", PREFIX)


@dataclasses.dataclass
class Total:
    inclusive_s: float = 0.0
    self_s: float = 0.0
    count: int = 0


@dataclasses.dataclass
class Gaps:
    window_s: float
    idle_s: float                       # averaged over devices
    in_program_s: float                 # idle inside a `repro.` span
    longest: list[tuple[str, float]]    # (innermost span, s)

    @property
    def in_program_pct(self) -> float:
        return 100.0 * self.in_program_s / self.idle_s if self.idle_s else 0.0


def host_spans(data) -> list[tuple[int, int, str, int]]:
    """Every `bench.` and `repro.` event on the trace's host planes, as
    (start ns, end ns, name, thread); `thread` numbers the planes' lines,
    so two threads' spans never share one."""
    out = []
    thread = 0
    for plane in data.planes:
        if tr.DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            thread += 1
            out += [(int(ev.start_ns), int(ev.end_ns), ev.name, thread)
                    for ev in line.events if ev.name.startswith(PREFIXES)]
    return out


def window(spans) -> tuple[int, int]:
    """The first `bench.window` among `host_spans`, as (start, end) ns."""
    windows = [(s, e) for s, e, n, _t in spans if n == tr.WINDOW]
    if not windows:
        raise ValueError(f"no {tr.WINDOW} span in the trace")
    return windows[0]


def reduce(data) -> dict[str, Total]:
    """Per span name, its totals inside the window of a `ProfileData`."""
    spans = host_spans(data)
    lo, hi = window(spans)
    threads: dict[int, list[tuple[int, int, str]]] = {}
    for s, e, name, thread in spans:
        if name.startswith(PREFIX):
            threads.setdefault(thread, []).append((s, e, name))
    totals: dict[str, Total] = {}

    def inside(s: int, e: int) -> int:
        return max(0, min(e, hi) - max(s, lo))

    def close(span: list) -> None:
        s, e, name, children = span
        ns = inside(s, e)
        if ns > 0:
            t = totals.setdefault(name, Total())
            t.inclusive_s += ns / 1e9
            t.self_s += (ns - children) / 1e9
            t.count += 1

    for events in threads.values():
        stack: list[list] = []      # open: [start, end, name, child ns]
        for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
            while stack and stack[-1][1] <= s:
                close(stack.pop())
            if stack:
                stack[-1][3] += inside(s, min(e, stack[-1][1]))
            stack.append([s, e, name, 0])
        while stack:
            close(stack.pop())
    return totals


def _overlap(intervals: list[tuple[int, int]],
             merged: list[tuple[int, int]]) -> int:
    """Nanoseconds of `intervals` that `merged` (sorted, disjoint)
    covers."""
    total = j = 0
    for s, e in sorted(intervals):
        while j < len(merged) and merged[j][1] <= s:
            j += 1
        for ms, me in merged[j:]:
            if ms >= e:
                break
            total += min(e, me) - max(s, ms)
    return total


def gaps(data, n: int = 10) -> Gaps | None:
    """The window's device idle time, named by program spans; None when
    the trace has no TPU device plane."""
    spans = host_spans(data)
    lo, hi = window(spans)
    devices = [p for p in data.planes if tr.DEVICE.match(p.name)]
    if not devices:
        return None
    idle: list[tuple[int, int]] = []
    for plane in devices:
        ops = [iv for line in plane.lines if line.name == tr.OPS_LINE
               for ev in line.events
               for iv in [tr._clip(int(ev.start_ns), int(ev.end_ns), lo, hi)]
               if iv]
        edges = [lo] + [t for iv in tr._union(ops) for t in iv] + [hi]
        idle += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    def doing(s: int, e: int) -> str:
        mid = (s + e) // 2
        inner = [(se - ss, name) for ss, se, name, _t in spans
                 if ss <= mid <= se and name != tr.WINDOW]
        return min(inner)[1] if inner else tr.WINDOW

    program = tr._union([iv for s, e, name, _t in spans
                         if name.startswith(PREFIX)
                         for iv in [tr._clip(s, e, lo, hi)] if iv])
    longest = sorted(idle, key=lambda iv: iv[0] - iv[1])[:n]
    per = 1e9 * len(devices)
    return Gaps(window_s=(hi - lo) / 1e9,
                idle_s=sum(e - s for s, e in idle) / per,
                in_program_s=_overlap(idle, program) / per,
                longest=[(doing(s, e), (e - s) / 1e9) for s, e in longest])
