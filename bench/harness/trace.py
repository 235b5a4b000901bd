"""From a profiler trace (`.xplane.pb`) to device busy time, per-program
and per-kernel device time, and the host's activity in the device's idle
gaps.

The program names nothing for the profiler yet, so its steps and kernels
are found by the names JAX and Pallas give them today. They are listed
here, and only here:

- a jitted step shows on the device's "XLA Modules" line as
  `jit_<function name>(<fingerprint>)`;
- a Pallas kernel shows on the "XLA Ops" line as an instruction named
  after its kernel function: `%<name>.<n> = <shape> custom-call(...)`.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# what the program calls its steps and kernels today
PROGRAMS = {
    "decode": ("jit__decode_paged_fn",),
    "prefill": ("jit__prefill_paged_fn",),
}
KERNELS = {
    "paged_decode": ("paged_decode_attention",),
}

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MIN_GAP_NS = 10_000          # idle gaps shorter than this are not named

_OP_NAME = re.compile(r"^%?([^ ]+?)(?:\.\d+)?(?: |$)")


def program_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """`%fusion.12 = bf16[...] ...` -> `fusion`."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def op_label(event_name: str) -> str:
    """The instruction and its result shape, for a breakdown."""
    return event_name.lstrip("%")[:72]


@dataclass
class Reduced:
    window_s: float
    busy_s: float                                  # mean over devices
    devices: int
    programs: Dict[str, float] = field(default_factory=dict)
    kernels: Dict[str, float] = field(default_factory=dict)
    kernel_calls: Dict[str, int] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def program_seconds(self, kind: str) -> float:
        return sum(v for k, v in self.programs.items()
                   if k in PROGRAMS[kind])

    def kernel_seconds(self, kind: str) -> float:
        return sum(v for k, v in self.kernels.items() if k in KERNELS[kind])

    def kernel_count(self, kind: str) -> int:
        return sum(v for k, v in self.kernel_calls.items()
                   if k in KERNELS[kind])


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: List[Tuple[int, int, str]]) -> Dict[str, float]:
    """Seconds each op label ran with no op nested inside it: a `while`
    that holds a whole step's ops keeps only its own time."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []               # [end, label, self_ns]
    for s, e, label in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, lab, own = stack.pop()
            out[lab] += own / 1e9
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, label, e - s])
    while stack:
        end, lab, own = stack.pop()
        out[lab] += own / 1e9
    return out


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def reduce(path: str, window: Optional[Tuple[int, int]] = None
           ) -> Optional[Reduced]:
    """Reduce the trace at `path`, or None where no operation ran on a
    TPU in it. `window` (start, end in ns on the trace's clock) bounds the
    busy share; by default it runs from the first to the last device
    event."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    per_dev_busy = []
    programs: Dict[str, float] = defaultdict(float)
    kernels: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    op_self: Dict[str, float] = defaultdict(float)
    busy_all: List[Tuple[int, int]] = []
    host: List[Tuple[int, int, str]] = []
    lo, hi = None, None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        programs[program_name(ev.name)] += \
                            ev.duration_ns / 1e9
                elif line.name == OPS_LINE:
                    for ev in line.events:
                        ops.append((int(ev.start_ns), int(ev.end_ns),
                                    ev.name))
            if not ops:
                continue
            for s, e, name in ops:
                n = op_name(name)
                for kind, names in KERNELS.items():
                    if n in names:
                        kernels[n] += (e - s) / 1e9
                        calls[n] += 1
            for lab, sec in self_times(
                    [(s, e, op_label(nm)) for s, e, nm in ops]).items():
                op_self[lab] += sec
            u = union([(s, e) for s, e, _ in ops])
            busy_all.extend(u)
            lo = u[0][0] if lo is None else min(lo, u[0][0])
            hi = u[-1][1] if hi is None else max(hi, u[-1][1])
            per_dev_busy.append(u)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    host.append((int(ev.start_ns), int(ev.end_ns), ev.name))
    if not per_dev_busy:
        return None
    w0, w1 = window if window else (lo, hi)
    busy = []
    for u in per_dev_busy:
        busy.append(sum(max(0, min(e, w1) - max(s, w0)) for s, e in u) / 1e9)
    red = Reduced(window_s=(w1 - w0) / 1e9,
                  busy_s=sum(busy) / len(busy), devices=len(busy),
                  programs=dict(programs), kernels=dict(kernels),
                  kernel_calls=dict(calls))
    red.top_ops = sorted(op_self.items(), key=lambda kv: -kv[1])[:10]
    red.idle_gaps = _name_gaps(union(busy_all), host, w0, w1)
    return red


def _name_gaps(busy: List[Tuple[int, int]], host, w0: int, w1: int):
    """Total idle seconds of the device, by the innermost host event that
    covers each gap's middle (`idle` where none does)."""
    gaps = []
    prev = w0
    for s, e in busy:
        if s - prev >= MIN_GAP_NS:
            gaps.append((prev, min(s, w1)))
        prev = max(prev, e)
    if w1 - prev >= MIN_GAP_NS:
        gaps.append((prev, w1))
    host.sort()
    starts = [h[0] for h in host]
    import bisect
    totals: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) // 2
        best = None
        i = bisect.bisect_right(starts, mid)
        # host events start at or before the middle; the innermost
        # covering one is the shortest
        for hs, he, name in host[max(0, i - 4000):i]:
            if he >= mid and (best is None or he - hs < best[1] - best[0]):
                best = (hs, he, name)
        totals[best[2] if best else "idle"] += (e - s) / 1e9
    return sorted(totals.items(), key=lambda kv: -kv[1])[:10]
