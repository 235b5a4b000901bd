"""Mesh-sharded (tensor-parallel) serving benchmark (DESIGN §12).

Runs the real paged engine on CPU test meshes with a FIXED per-chip KV
pool while the model axis grows (m = 1, 2, 4): params shard per the §5
name rules, the paged K/V pools shard over "model" on kv-heads, and the
chip-aware MemoryModel scales Alg-1's token capacity with the shard
count. The capacity headline is `admitted_peak_tokens` — the peak KV
tokens held live for admitted requests — which scales with the model
axis at constant per-chip HBM, while decoded tokens stay bitwise
identical to the single-device engine.

Each mesh size runs in a child process (XLA's forced host device count is
fixed at first jax init, so meshes cannot be grown inside one process).

The children are pinned to the CPU backend, on the chip machine too: every
record names the platform it ran on (`platform`, `device_kind`,
`device_count`) and every CSV row carries `platform=cpu`. Its timings
(`tbt_ms_mean`, `wall_s`) are CPU wall-clock, never a chip number; the
on-chip mesh path is `chip_smoke.py --chips 4`.

Writes `BENCH_tp.json`.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
MODEL_AXES = (1, 2, 4)
PER_CHIP_POOL_TOKENS = 192     # 12 blocks/chip: tight for the burst below

_CHILD = r"""
import json, sys, time
import jax, jax.numpy as jnp
import numpy as np
from repro.config.base import ServeConfig
from repro.config.registry import get_config
from repro.models.model import build_model
from repro.serving.engine import Engine

m, per_chip_pool = int(sys.argv[1]), int(sys.argv[2])
cfg = get_config("granite-3-8b", "reduced")
model = build_model(cfg, dtype=jnp.float32)
params = model.init(jax.random.PRNGKey(0))

serve = ServeConfig(policy="memory", b_max=8, max_new_tokens=24,
                    kv_pool_tokens=per_chip_pool, block_size=16,
                    chunked_prefill=True, chunk_budget_tokens=32,
                    n_prefill_lanes=2, paged_kv=True,
                    mesh_shape=(1, m) if m > 1 else ())
eng = Engine(model, params, serve, max_context=96, buckets=(1, 2, 4, 8),
             prefill_chunk=16)
eng.warmup()

rng = np.random.RandomState(7)
prompts = [list(map(int, rng.randint(0, cfg.vocab_size,
                                     size=int(rng.randint(28, 44)))))
           for _ in range(10)]
hs = [eng.submit(p, max_new_tokens=24, arrival_time=0.0) for p in prompts]
peak_tokens = peak_reqs = 0
t0 = time.perf_counter()
while eng.step():
    peak_tokens = max(peak_tokens, eng.blocks.physical_used_tokens)
    peak_reqs = max(peak_reqs, len(eng.active) + len(eng.prefilling))
wall_s = time.perf_counter() - t0
s = eng.summary()
dev = jax.devices()[0]
print("RESULT" + json.dumps({
    "platform": dev.platform,
    "device_kind": dev.device_kind,
    "device_count": len(jax.devices()),
    "model_axis": m,
    "model_shards": int(s["model_shards"]),
    "per_chip_pool_tokens": per_chip_pool,
    "pool_tokens_capacity": int(s["pool_tokens"]),
    "admitted_peak_tokens": peak_tokens,
    "admitted_peak_requests": peak_reqs,
    "mean_batch": s["mean_batch"],
    "tbt_ms_mean": s["tbt_ms_mean"],
    "preemptions": int(s["preemptions"]),
    "oom_events": int(s["oom_events"]),
    "finished": int(s["finished"]),
    "copy_rows": int(s["copy_rows"]),
    "wall_s": wall_s,
    "outputs": [h.output_tokens for h in hs],
}))
"""


def _run_child(model_axis: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{max(model_axis, 1)}")
    proc = subprocess.run([sys.executable, "-c", _CHILD, str(model_axis),
                           str(PER_CHIP_POOL_TOKENS)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"tp child (m={model_axis}) failed:\n"
                           f"{proc.stderr[-2000:]}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def run_tp_scaling(out_json: str = "BENCH_tp.json", csv_out=None) -> dict:
    results: dict = {"platform": "cpu",
                     "per_chip_pool_tokens": PER_CHIP_POOL_TOKENS,
                     "meshes": []}
    outputs = {}
    for m in MODEL_AXES:
        r = _run_child(m)
        if r["platform"] != "cpu":
            raise RuntimeError(f"tp child ran on {r['platform']}, not cpu")
        outputs[m] = r.pop("outputs")
        results["meshes"].append(r)
        if csv_out:
            csv_out(f"tp_model_axis_{m}", r["wall_s"] * 1e6,
                    f"platform=cpu "
                    f"capacity={r['pool_tokens_capacity']}tok "
                    f"peak={r['admitted_peak_tokens']}tok "
                    f"preempt={r['preemptions']} oom={r['oom_events']}")
    base = MODEL_AXES[0]
    results["outputs_identical_to_single_device"] = all(
        outputs[m] == outputs[base] for m in MODEL_AXES)
    results["admitted_peak_scaling"] = [
        r["admitted_peak_tokens"] for r in results["meshes"]]
    results["capacity_scaling"] = [
        r["pool_tokens_capacity"] for r in results["meshes"]]
    with open(out_json, "w") as f:
        json.dump(results, f, indent=2)
    if csv_out:
        csv_out("tp_summary", 0.0,
                f"platform=cpu "
                f"peaks={results['admitted_peak_scaling']} "
                f"identical={results['outputs_identical_to_single_device']} "
                f"-> {out_json}")
    return results


def run(csv_out) -> None:
    run_tp_scaling(csv_out=csv_out)


if __name__ == "__main__":
    run(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
