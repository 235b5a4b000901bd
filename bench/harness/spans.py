"""The engine's own host spans in a profiler trace, and the device's idle
time put down to them.

The engine opens a `jax.profiler` annotation around each phase of
`Engine.step()` (`repro.serving.engine.SPANS`, all named `engine.*`); the
profiler puts them on the host plane, on the clock of the device's ops.
An idle gap of the device is named by the innermost engine span that
covers its middle. Idle under `engine.retire.fence` is the host waiting
on the device, not host work, so it is kept apart from the engine's host
idle; idle that no engine span covers is outside the engine (harness,
client, waiting for arrivals).
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench.harness import trace as T

PREFIX = "engine."
FENCE = "engine.retire.fence"
OUTSIDE = "outside"          # idle with the host in no engine span


@dataclass
class Span:
    start: int               # ns on the trace's clock
    end: int
    name: str
    args: Dict[str, float] = field(default_factory=dict)


@dataclass
class Spans:
    """What a trace holds for the attribution: the device's busy
    intervals (the union over devices), the window they span, and the
    engine's spans in order of start."""
    busy: List[Tuple[int, int]]
    window: Tuple[int, int]
    spans: List[Span]


@dataclass
class Idle:
    """Idle seconds of the device in a window, by what the host was in."""
    window_s: float
    total_s: float
    by_span: Dict[str, float]          # innermost engine span, or OUTSIDE

    @property
    def fence_s(self) -> float:
        return self.by_span.get(FENCE, 0.0)

    @property
    def outside_s(self) -> float:
        return self.by_span.get(OUTSIDE, 0.0)

    @property
    def engine_s(self) -> float:
        """Idle while the host works inside the engine: under an engine
        span other than the fence."""
        return self.total_s - self.fence_s - self.outside_s

    def share(self, seconds: float) -> Optional[float]:
        return 100.0 * seconds / self.window_s if self.window_s else None


def read(path: str) -> Optional[Spans]:
    """Device busy intervals and engine spans of the trace at `path`, or
    None where no operation ran on a TPU in it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    busy: List[Tuple[int, int]] = []
    spans: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith(T.DEVICE_PLANE):
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    busy.extend((int(ev.start_ns), int(ev.end_ns))
                                for ev in line.events)
        elif plane.name == T.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        args = {k: v for k, v in ev.stats
                                if not k.startswith("_")}
                        spans.append(Span(int(ev.start_ns), int(ev.end_ns),
                                          ev.name, args))
    if not busy:
        return None
    busy = T.union(busy)
    spans.sort(key=lambda s: (s.start, -s.end))
    return Spans(busy, (busy[0][0], busy[-1][1]), spans)


def gaps(busy: List[Tuple[int, int]], w0: int,
         w1: int) -> List[Tuple[int, int]]:
    """The device's idle intervals in [w0, w1] of at least
    `trace.MIN_GAP_NS`, as `trace` counts them when it names idle gaps."""
    out = []
    prev = w0
    for s, e in busy:
        if s - prev >= T.MIN_GAP_NS:
            out.append((prev, min(s, w1)))
        prev = max(prev, e)
    if w1 - prev >= T.MIN_GAP_NS:
        out.append((prev, w1))
    return out


def attribute(busy: List[Tuple[int, int]], spans: List[Span],
              window: Tuple[int, int]) -> Idle:
    """Put each idle gap of the device down to the innermost engine span
    covering its middle. `busy` is merged and sorted; `spans` nest (one
    host thread) and are sorted by start, outer first on a tie."""
    w0, w1 = window
    by: Dict[str, float] = defaultdict(float)
    total = 0.0
    stack: List[Span] = []
    i = 0
    for s, e in gaps(busy, w0, w1):
        mid = (s + e) // 2
        while i < len(spans) and spans[i].start <= mid:
            while stack and stack[-1].end <= spans[i].start:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1].end < mid:
            stack.pop()
        sec = (e - s) / 1e9
        by[stack[-1].name if stack else OUTSIDE] += sec
        total += sec
    return Idle((w1 - w0) / 1e9, total, dict(by))


def arg_mean(spans: List[Span], name: str, arg: str) -> Optional[float]:
    """Mean of one argument over the spans called `name`."""
    vals = [s.args[arg] for s in spans if s.name == name and arg in s.args]
    return sum(vals) / len(vals) if vals else None
