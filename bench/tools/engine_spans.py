#!/usr/bin/env python3
"""One traced run of a cell, with the device's idle time put down to the
engine's host spans, and the cost of those spans.

    python3 bench/tools/engine_spans.py --workload nemo-longprompt \
        --seed 7 --seconds 51
    python3 bench/tools/engine_spans.py --span-cost 100000

The first form runs the cell as `bench/run.py --trace 1` does and reads
its trace before the run deletes it. It prints one JSON line: the run's
own line under `run`, the cell's end-to-end metrics read from the same
record (so that a traced and an untraced run of one seed compare), the
idle seconds under each engine span (`engine_idle`), the idle shares
(engine host work, the retirement fence, outside the engine), and the
means of the `engine.prefill` and `engine.decode` span arguments. Gaps
under `trace.MIN_GAP_NS` are named by no span and counted apart. The
second form times the span set of one interval with no profiler
running, and prints microseconds per interval."""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def span_cost(loops: int, repeats: int = 5) -> dict:
    """Microseconds per interval of entering and leaving every span one
    `Engine.step()` opens (the release span twice), with their arguments,
    while no trace runs: the median of `repeats` timings."""
    import jax
    from repro.serving import engine as E
    ann, step = jax.profiler.TraceAnnotation, jax.profiler.StepTraceAnnotation
    per = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for i in range(loops):
            with step(E.SPAN_STEP, step_num=i):
                with ann(E.SPAN_SCHEDULE):
                    pass
                with ann(E.SPAN_ADMIT, waiting=64):
                    with ann(E.SPAN_RELEASE, blocks=3):
                        pass
                with ann(E.SPAN_PREEMPT):
                    pass
                with ann(E.SPAN_PREFILL, budget=31, lanes_busy=2):
                    pass
                with ann(E.SPAN_DECODE, rows=1, bucket=1):
                    with ann(E.SPAN_RELEASE, blocks=3):
                        pass
                with ann(E.SPAN_FENCE):
                    pass
                with ann(E.SPAN_READBACK):
                    pass
                with ann(E.SPAN_STAMP):
                    pass
        per.append((time.perf_counter() - t0) / loops * 1e6)
    return {"span_cost_us_per_interval": statistics.median(per),
            "runs_us": per, "loops": loops}


def traced_run(argv, root: Path = ROOT, **kw) -> dict:
    """The traced run; `kw` goes to `run.run_cell` (a CPU rehearsal's
    root, peaks and `require_tpu`)."""
    import jax
    from bench import run
    from bench.harness import spans as S
    from bench.harness import trace as T
    from bench.harness.spec import Spec
    from repro.serving.engine import SPAN_DECODE, SPAN_PREFILL

    got = {}
    reduce = T.reduce

    def reduce_keeping_spans(path, window=None):
        # the run deletes the trace once it is reduced: read it first
        got["spans"] = S.read(path)
        return reduce(path, window)

    start = jax.profiler.start_trace

    def timed_start(*a, **k):
        # the trace starts inside the window: how long the send loop stops
        t0 = time.perf_counter()
        start(*a, **k)
        got["start_trace_s"] = time.perf_counter() - t0

    T.reduce, jax.profiler.start_trace = reduce_keeping_spans, timed_start
    args = run.parse(argv + ["--trace", "1"])
    try:
        result, rec = run.run_cell(args, root=root,
                                   t_start=time.perf_counter(), **kw)
    finally:
        T.reduce, jax.profiler.start_trace = reduce, start
    if result is None:
        sys.exit(2)
    spec = Spec(root)
    out = {"run": result, "end_to_end": {},
           "start_trace_s": got.get("start_trace_s")}
    for m in spec.metrics_for(args.workload, trace=False):
        out["end_to_end"][m.name] = spec.reader(m.name)(rec)
    from bench.metrics._common import ttft
    from bench.metrics._lifecycle import waits
    w = waits(rec) or []
    if w:
        out["ttft_engine_s_mean"] = sum(sum(p) for p in w) / len(w)
        out["ttft_requests"] = len(w)
        # the client's view of the same requests: due -> first token seen
        firsts = [c for c in rec.judged() if c.r.first_token_time >= 0]
        out["ttft_client_s_mean"] = sum(
            ttft(c, rec.stop) for c in firsts) / len(firsts)
    sp = got.get("spans")
    if sp is not None:
        idle = S.attribute(sp.busy, sp.spans, sp.window)
        # as the device_idle_share metric reads it: gaps of any length
        dev = 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)
        eng = idle.share(idle.engine_s)
        out.update({
            "window_s": idle.window_s,
            "device_idle_share": dev,
            "engine_host_idle_share": eng,
            # the rest of the device's idle share, and its parts
            "rest_idle_share": dev - eng,
            "fence_idle_share": idle.share(idle.fence_s),
            "outside_engine_idle_share": idle.share(idle.outside_s),
            "short_gap_idle_share": dev - idle.share(idle.total_s),
            "engine_idle": sorted(idle.by_span.items(),
                                  key=lambda kv: -kv[1]),
            "spans": len(sp.spans),
        })
        for name, arg in ((SPAN_PREFILL, "budget"),
                          (SPAN_PREFILL, "lanes_busy"),
                          (SPAN_DECODE, "rows"), (SPAN_DECODE, "bucket")):
            out[f"{name}.{arg}_mean"] = S.arg_mean(sp.spans, name, arg)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--span-cost", type=int, default=0,
                    help="time this many intervals of spans instead")
    a, rest = ap.parse_known_args()
    out = span_cost(a.span_cost) if a.span_cost else traced_run(rest)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
