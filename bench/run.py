#!/usr/bin/env python3
"""Run one cell of `BENCHMARK.json` once, on the chips of this machine.

    python3 bench/run.py --workload granite-chat --seed 7 --seconds 51 \
        --trace 0

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`), then `checks`, each compared number beside its limit. The
same checks are the last lines of standard error. With `--trace 0` the
metrics are the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics. `--control 1` puts the float8 control in the program's place in
the comparison, on the same sample, so that `correct` reads the control's
verdict (for setting and proving limits; the benchmark's own runs leave
it off).

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(args, root: Path = ROOT, require_tpu: bool = True,
             peak_table=None, t_start: float = T_START, mix=None):
    """One run of a cell. Returns (result, record), or (None, None) where
    the chips the cell needs are not there. `mix` replaces the cell's
    traffic parameters (the sweep tool's rates)."""
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness.spec import Spec
    spec = Spec(root)
    cell = spec.cell(args.workload)
    metrics = spec.metrics_for(args.workload, bool(args.trace))

    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell["chips"]):
        print(f"bench: needs {cell['chips']} TPU chip(s), JAX sees "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return None, None
    from bench.harness.peaks import peaks
    kind = devs[0].device_kind
    peak = (peak_table or {}).get(kind) or peaks(kind)

    from bench.harness.cell import CellRun
    run = CellRun(spec, args.workload, args.seed, args.seconds,
                  bool(args.trace), t_start, peak)
    if mix is not None:
        run.mix = run.rec.mix = mix
    try:
        run.setup()
        rec = run.serve()
        values = {}
        for m in metrics:
            v = spec.reader(m.name)(rec)
            if v is not None:
                values[m.name] = {"value": v, "unit": m.unit}
        device = {"platform": devs[0].platform, "kind": kind,
                  "count": len(devs), "memory_peak_bytes": run.memory_peak()}
        result = {"correct": False, "attempted": len(rec.judged()),
                  "failed": rec.failures(), "metrics": values,
                  "device": device}
        if rec.trace is not None:
            device["busy_s"] = rec.trace.busy_s
            device["window_s"] = rec.trace.window_s
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in rec.trace.top_ops],
                "idle_gaps": [[n, s] for n, s in rec.trace.idle_gaps]}
        run.free_engine()
        res = run.check(control=bool(args.control))
    finally:
        run.close()
    result["correct"] = res["correct"]
    if args.control:
        result["readings"] = {"program_gap": res["program_gap"],
                              "control_gap": res["checks"]["logit_gap"]
                              ["value"],
                              "served_checked": res["served_checked"]}
    result["checks"] = res["checks"]
    return result, rec


def main(argv=None, **kw) -> int:
    result, _ = run_cell(parse(argv), **kw)
    if result is None:
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} {c['rule']} {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
