#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: run the cell once at
each offered rate, one process, and print a line per rate.

    python3 bench/tools/sweep.py --workload granite-chat \
        --rates 0.2,0.3,0.4,0.5 --seconds 40 --seed 11

A rate is sustained where every request due in the window finishes within
the drain limit and the queue does not grow through the window. The rate
found is written into the cell's traffic file by hand; the benchmark
never searches for one."""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def summarize(rec, rate: float) -> dict:
    from bench.metrics._common import percentile, ttft
    judged = rec.judged()
    slo = rec.mix["slo"]
    met = 0
    gaps = []
    for c in judged:
        g = [(b - a) * 1e3 for a, b in zip(c.times, c.times[1:])]
        gaps += g
        mean = sum(g) / len(g) if g else 0.0
        if c.complete and ttft(c, rec.stop) <= slo["ttft_s"] \
                and mean <= slo["tbt_ms"]:
            met += 1
    win = rec.window_steps()
    rows = [s.decode_rows for s in win if s.decode_rows]
    t = [ttft(c, rec.stop) for c in judged]
    return {
        "rate": rate, "judged": len(judged),
        "open_at_stop": sum(1 for c in judged if not c.done),
        "drain_s": rec.stop - rec.w1,
        "slo_attainment": met / max(len(judged), 1),
        "ttft_p50_s": percentile(t, 50), "ttft_p90_s": percentile(t, 90),
        "tbt_p50_ms": percentile(gaps, 50) if gaps else None,
        "tbt_p95_ms": percentile(gaps, 95) if gaps else None,
        "decode_rows_mean": sum(rows) / len(rows) if rows else 0,
        "prefill_tok_s": sum(s.prefill_tokens for s in win)
        / max(rec.w1 - rec.w0, 1e-9),
        "step_ms_mean": 1e3 * sum(s.t1 - s.t0 for s in win) / max(len(win), 1),
        "setup_s": rec.w0 - rec.t_start,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--no-serve-flags", action="store_true",
                    help="serve without the mix's own engine flags")
    a = ap.parse_args()
    from bench import run
    from bench.harness.spec import Spec
    base = Spec(ROOT).traffic_file(Spec(ROOT).cell(a.workload)["traffic"])
    for i, r in enumerate(float(x) for x in a.rates.split(",")):
        mix = copy.deepcopy(base)
        mix["arrivals"]["rate"] = r
        if a.no_serve_flags:
            mix["serve_flags"] = []
        args = run.parse(["--workload", a.workload, "--seed",
                          str(a.seed + i), "--seconds", str(a.seconds)])
        result, rec = run.run_cell(args, t_start=time.perf_counter(),
                                   mix=mix)
        if result is None:
            sys.exit(2)
        line = summarize(rec, r)
        line["correct"] = result["correct"]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
