"""Without a TPU, or without the program beside it, the command exits
non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

from bench.tests.rehearse import REPO

CMD = [sys.executable, "bench/run.py", "--workload", "granite-chat",
       "--seed", "1", "--seconds", "1"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
