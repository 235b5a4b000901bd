"""kimi-agent-backlog end to end on the CPU at the test widths: the last
line is the contract's object, its metrics are the cell's, it is correct,
and the held-expert counter reaches its reader."""
import pytest

from bench.harness.spec import Spec
from bench.tests.cpu_run import run_cell

CELL = "kimi-agent-backlog"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(tmp_path, capsys, trace):
    last, err, root = run_cell(tmp_path, capsys, CELL, trace=trace,
                               seconds=10)
    spec = Spec(root)
    want = {m.name: m for m in spec.metrics_for(CELL, bool(trace))}
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "checks"
    assert last["correct"] is True, last["checks"]
    assert last["attempted"] > 0 and last["failed"] == 0
    got = last["metrics"]
    assert set(got) <= set(want)
    # a CPU run reads no device trace; every other metric is there
    assert {n for n, m in want.items() if m.source != "device_trace"} \
        <= set(got)
    for n, v in got.items():
        assert v["unit"] == want[n].unit and v["value"] == v["value"]
    if trace:
        assert got["held_expert_imbalance.kimi"]["value"] >= 1.0
    assert last["device"]["platform"] == "cpu"
