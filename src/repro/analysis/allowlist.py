"""The justified allowlist (DESIGN §13).

Every entry suppresses EXACTLY `count` findings of `rule` in `path`
(optionally narrowed to one qualified `scope`) and must carry a real
justification — the framework rejects reasons under MIN_REASON chars,
entries matching no finding (stale), and entries matching a different
number of findings than declared (count drift: a new un-reviewed site
hiding behind an old excuse).

The host-sync entries below are deliberate. The async dispatch-ahead loop
(DESIGN §14, ROADMAP item 1) drained the per-graph fences the synchronous
loop carried in _advance_prefill (4) and _decode_once (2): dispatch is now
fence-free and exactly one blocking pair remains on the serving path — the
retirement fence + bulk token readback in Engine._retire_step. warmup's
blocks are pre-serving by construction; _swap_out's device_get IS the swap
transfer. Total on-path syncs: 2 (was 6), whole file: 8 (was 12). The
`_i32` entry is no sync: its np.asarray only ever sees host lists.
"""
from __future__ import annotations

from typing import List

from repro.analysis.framework import Allow

ENGINE = "src/repro/serving/engine.py"

ALLOWLIST: List[Allow] = [
    Allow("host-sync", ENGINE, "Engine.warmup", 5,
          "warmup deliberately blocks on each compiled graph so first-token "
          "latency is never paid mid-benchmark; off the serving path"),
    Allow("host-sync", ENGINE, "Engine._retire_step", 2,
          "THE pipeline fence (DESIGN SS14): one block_until_ready per "
          "retired interval — the measured step_device_s — then one bulk "
          "device_get of every sampled/first token; the only blocking "
          "pair the async dispatch-ahead loop retains on the serving path"),
    Allow("host-sync", ENGINE, "Engine._swap_out", 1,
          "device_get of evicted KV rows is the swap transfer itself "
          "(DESIGN SS11); it must complete before the rows are reused"),
    Allow("host-sync", ENGINE, "_i32", 1,
          "the operand is a host list of Python ints the scheduler built "
          "(tokens, positions, slots, block ids); np.asarray converts host "
          "data so that no per-shape conversion program compiles mid-serve"),
    Allow("counter-parity", ENGINE, "Engine.summary", 2,
          "copy_rows/copy_bytes count physical tensor row moves during "
          "defrag; the sim has no tensor storage, so no twin can exist"),
]
