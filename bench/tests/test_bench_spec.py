"""`BENCHMARK.json` is read as data: a configuration, a traffic mix, a
cell and a metric added as new files with their entries are found by
name, and names or units outside the contract are refused."""
import json
import shutil

import pytest

from bench.harness import arith
from bench.harness.spec import Spec, SpecError, check_name, check_unit
from bench.tests.rehearse import REPO


def copy_root(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_new_files_are_found_by_name(tmp_path):
    root = copy_root(tmp_path)
    b = root / "bench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    base = json.loads((b / "configs" / "granite-3-8b-chip.json").read_text())
    base["name"] = "toy-model"
    (b / "configs" / "toy-model.json").write_text(json.dumps(base))
    spec["configs"].append({"name": "toy-model", "source": "https://x.org",
                            "file": "bench/configs/toy-model.json",
                            "reduced": [], "why": "toy"})
    mix = json.loads((b / "traffic" / "chat-poisson.json").read_text())
    mix["arrivals"]["rate"] = 9.5
    (b / "traffic" / "toy-mix.json").write_text(json.dumps(mix))
    (b / "cells" / "toy-cell.json").write_text(
        json.dumps({"limits": {"logit_gap": 0.5}}))
    spec["workloads"].append({"name": "toy-cell", "config": "toy-model",
                              "traffic": "toy-mix", "chips": 1,
                              "why": "toy"})
    (b / "metrics" / "toy_metric.v2.py").write_text(
        "def read(rec):\n    return rec * 2.0\n")
    spec["per_layer"].append({"name": "toy_metric.v2", "unit": "tokens/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine scheduler",
                              "moves": "ttft_p50_s",
                              "workloads": ["toy-cell"]})
    spec["end_to_end"][0]["workloads"].append("toy-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    s = Spec(root)
    assert s.cell("toy-cell")["config"] == "toy-model"
    assert s.config_file("toy-model")["name"] == "toy-model"
    assert s.traffic_file("toy-mix")["arrivals"]["rate"] == 9.5
    assert s.cell_file("toy-cell")["limits"]["logit_gap"] == 0.5
    assert s.reader("toy_metric.v2")(21) == 42.0
    names = [m.name for m in s.metrics_for("toy-cell", trace=True)]
    assert names == ["toy_metric.v2"]
    e2e = [m.name for m in s.metrics_for("toy-cell", trace=False)]
    assert e2e == ["ttft_p50_s", "setup_s"]
    with pytest.raises(SpecError):
        s.reader("no_such_metric")


def test_every_cell_and_metric_of_the_benchmark_resolves():
    s = Spec(REPO)
    for name, w in s.workloads.items():
        s.config_file(w["config"])
        s.traffic_file(w["traffic"])
        assert "logit_gap" in s.cell_file(name)["limits"]
        for trace in (False, True):
            for m in s.metrics_for(name, trace):
                assert callable(s.reader(m.name))
        e2e = [m.name for m in s.metrics_for(name, False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert s.metrics_for(name, True)
    for m in s.metrics.values():
        if m.kind == "per_layer":
            for cell in m.workloads:
                assert m.moves in [x.name for x in s.metrics_for(cell, False)]


@pytest.mark.parametrize("name", ["ttft_p90_s", "compiles_in_window.batch",
                                  "granite-3-8b-chip", "_x", "9a"])
def test_names_accepted(name):
    assert check_name(name, "t") == name


@pytest.mark.parametrize("name", ["", "a b", "a,b", "a/b", ".x", "-x",
                                  "tokµs", "x" * 65, 7])
def test_names_refused(name):
    with pytest.raises(SpecError):
        check_name(name, "t")


@pytest.mark.parametrize("unit", ["tokens/s", "%", "ms/ktok", "s", "share"])
def test_units_accepted(unit):
    assert check_unit(unit, "t") == unit


@pytest.mark.parametrize("unit", ["", "tokens per second", "µs",
                                  "x" * 17, "a,b"])
def test_units_refused(unit):
    with pytest.raises(SpecError):
        check_unit(unit, "t")


def test_bad_metric_name_in_benchmark_is_refused(tmp_path):
    root = copy_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"][0]["name"] = "bad name"
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(SpecError):
        Spec(root)


# -- operations and bytes, worked by hand ------------------------------------

GRANITE = dict(d=4096, H=32, KV=8, hd=128, f=12800, V=49155, L=20)
NEMO = dict(d=5120, H=32, KV=8, hd=128, f=14336, V=131072, L=10)


def test_layer_weights():
    # q, o: 4096*4096 each; k, v: 4096*1024 each; SwiGLU: 3*4096*12800
    assert arith.matmul_params_per_layer(GRANITE) == 199_229_440
    # q, o: 5120*4096 each; k, v: 5120*1024 each; SwiGLU: 3*5120*14336
    assert arith.matmul_params_per_layer(NEMO) == 272_629_760


def test_token_flops():
    # 20 * (2*199229440 + 4*32*128*100) + 2*4096*49155
    assert arith.token_flops(GRANITE, 100, logits=True) == 8_404_623_360
    # 10 * (2*272629760 + 4*32*128*3000), no LM head
    assert arith.token_flops(NEMO, 3000, logits=False) == 5_944_115_200


def test_paged_decode_kernel_bytes_and_flops():
    # K and V of 100 + 300 live keys: 400 * 2 * 8 * 128 * 2 bytes;
    # q and out of 2 rows: 2 * 2 * 32 * 128 * 2 bytes
    assert arith.decode_kernel_bytes(GRANITE, [100, 300]) == 1_671_168
    assert arith.decode_kernel_flops(GRANITE, [100, 300]) == 6_553_600
    t, bound = arith.roofline_seconds(6_553_600, 1_671_168,
                                      {"bf16_flops": 197e12,
                                       "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and t == pytest.approx(1_671_168 / 819e9)
