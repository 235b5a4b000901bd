#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the program's widest logit gap
and the float8 control's, on many seeds, in one process.

    python3 bench/tools/limits.py --workload granite-chat \
        --seeds 101,102,103 --seconds 20

Prints one line per seed; its `correct` is the control's verdict under
the cell's current limit. The limit is set by hand, from these readings,
into `bench/cells/<cell>.json`."""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    a = ap.parse_args()
    from bench import run
    for seed in (int(s) for s in a.seeds.split(",")):
        args = run.parse(["--workload", a.workload, "--seed", str(seed),
                          "--seconds", str(a.seconds), "--control", "1"])
        result, rec = run.run_cell(args, t_start=time.perf_counter())
        if result is None:
            sys.exit(2)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          **result["readings"],
                          "metrics": result["metrics"],
                          "peak": result["device"]["memory_peak_bytes"]}),
              flush=True)


if __name__ == "__main__":
    main()
