"""A copy of the benchmark at widths a CPU test can hold.

`make_root` writes a checkout-shaped directory: the real `BENCHMARK.json`
and `bench/`, with each configuration cut to the CPU test widths, and each
traffic mix's lengths and each configuration's context and pool divided
by `shrink`; `src/` is linked, not copied.
The cells, metrics and names stay those of the real benchmark."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

# the program's `reduced` widths, and a head_dim != d_model / heads case
SMALL = {
    "granite-3-8b-chip": dict(
        variant="reduced", hidden_size=128, intermediate_size=256,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        num_hidden_layers=2, vocab_size=512),
    "mistral-nemo-12b-chip": dict(
        variant="chip", hidden_size=128, intermediate_size=256,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64,
        num_hidden_layers=2, vocab_size=512),
}
PROGRAM_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                "num_attention_heads": "num_heads",
                "num_key_value_heads": "num_kv_heads",
                "head_dim": "head_dim", "num_hidden_layers": "num_layers",
                "vocab_size": "vocab_size"}
CPU_PEAKS = {"cpu": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11,
                     "hbm_bytes": 2 ** 34}}


def make_root(tmp: Path, shrink: int = 8, rate_scale: float = 1.0,
              limit: float = None) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(REPO / "src")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        small = dict(SMALL[c["name"]])
        cfg["serve"]["variant"] = small.pop("variant")
        cfg.update(small)
        flags = cfg["serve"]["flags"]
        for f in ("--max-context", "--pool-tokens"):
            i = flags.index(f) + 1
            flags[i] = str(max(256, int(flags[i]) // shrink))
        if "register" in cfg["serve"]:
            rep = cfg["serve"]["register"]["replace"]
            rep.update({PROGRAM_KEYS[k]: v for k, v in small.items()})
        path.write_text(json.dumps(cfg))
    for w in spec["workloads"]:
        path = root / "bench" / "traffic" / f"{w['traffic']}.json"
        mix = json.loads(path.read_text())
        for part in ("prompt", "output"):
            for k in ("median", "min", "max"):
                mix[part][k] = max(1, mix[part][k] // shrink)
        if mix["arrivals"]["process"] == "poisson":
            mix["arrivals"]["rate"] *= rate_scale
            mix["ramp_seconds"] = 1
        else:
            mix["ramp"]["max_seconds"] = 5
        path.write_text(json.dumps(mix))
        if limit is not None:
            cell = root / "bench" / "cells" / f"{w['name']}.json"
            data = json.loads(cell.read_text())
            data["limits"]["logit_gap"] = limit
            cell.write_text(json.dumps(data))
    return root
