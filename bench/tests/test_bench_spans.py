"""The engine-span attribution (`bench/harness/spans.py`) on hand-made
intervals and on the recorded TPU trace, the lifecycle readers on
hand-made requests, and the trace reduction pinned to what it read on
the recorded trace before spans existed."""
import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench.harness import spans as S
from bench.harness import trace as T
from bench.metrics import _lifecycle
from bench.tests.cpu_run import run_cell

DATA = Path(__file__).parent / "data"
FIXTURE = DATA / "granite_4_steps.xplane.pb"
US = 1000                                   # ns


def _span(s, e, name, **args):
    return S.Span(s * US, e * US, name, args)


# a step from 0 to 100 us: admit [10, 30) holding a release [15, 25),
# decode [40, 60), the fence [60, 90); then the host outside the engine
SPANS = [_span(0, 100, "engine.step"), _span(10, 30, "engine.admit"),
         _span(15, 25, "engine.release", blocks=3),
         _span(40, 60, "engine.decode", rows=4),
         _span(60, 90, "engine.retire.fence"),
         _span(200, 300, "engine.step"),
         _span(210, 230, "engine.prefill", budget=30),
         _span(240, 260, "engine.prefill", budget=18)]


def test_innermost_span_wins_and_the_fence_is_apart():
    # idle gaps in: the release (inside admit), decode, the fence, the
    # step outside any phase, no span at all; one of 2 us goes unnamed
    busy = [(0, 10 * US), (12 * US, 13 * US), (23 * US, 42 * US),
            (58 * US, 62 * US), (82 * US, 90 * US), (100 * US, 140 * US),
            (160 * US, 400 * US)]
    idle = S.attribute(busy, SPANS, (0, 400 * US))
    got = {k: round(v * 1e9) for k, v in idle.by_span.items()}
    assert got == {"engine.release": 10 * US, "engine.decode": 16 * US,
                   "engine.retire.fence": 20 * US, "engine.step": 10 * US,
                   S.OUTSIDE: 20 * US}
    assert idle.engine_s == pytest.approx(36e-6)
    assert idle.fence_s == pytest.approx(20e-6)
    assert idle.outside_s == pytest.approx(20e-6)
    # engine idle and the rest add up to the whole idle time
    assert idle.engine_s + idle.fence_s + idle.outside_s \
        == pytest.approx(idle.total_s)
    assert idle.total_s == pytest.approx(sum(
        e - s for s, e in S.gaps(busy, 0, 400 * US)) / 1e9)
    assert idle.window_s == pytest.approx(400e-6)
    assert idle.share(idle.engine_s) == pytest.approx(9.0)


@pytest.mark.parametrize("gap_us,named", [(5, {}),
                                           (10, {"engine.decode": 10e-6})])
def test_gaps_under_the_floor_are_not_named(gap_us, named):
    assert T.MIN_GAP_NS == 10 * US
    busy = [(0, 40 * US), ((40 + gap_us) * US, 100 * US)]
    idle = S.attribute(busy, SPANS, (0, 100 * US))
    assert idle.by_span == pytest.approx(named)


def test_arg_mean():
    assert S.arg_mean(SPANS, "engine.prefill", "budget") == 24
    assert S.arg_mean(SPANS, "engine.decode", "rows") == 4
    assert S.arg_mean(SPANS, "engine.prefill", "rows") is None


def test_recorded_trace_agrees_with_the_reduction():
    """The trace was recorded before the engine named its phases: its
    busy time and window are the reduction's, and every idle gap falls
    outside an engine span."""
    sp = S.read(str(FIXTURE))
    r = T.reduce(str(FIXTURE))
    assert sp.spans == []
    assert (sp.window[1] - sp.window[0]) / 1e9 == pytest.approx(r.window_s)
    assert sum(e - s for s, e in sp.busy) / 1e9 == pytest.approx(r.busy_s)
    idle = S.attribute(sp.busy, sp.spans, sp.window)
    assert set(idle.by_span) == {S.OUTSIDE} and idle.engine_s == 0
    assert idle.total_s <= r.window_s - r.busy_s + 1e-9
    assert idle.total_s == pytest.approx(r.window_s - r.busy_s, rel=0.01)


def test_reduction_reads_as_before():
    """Every field of `trace.reduce` on the recorded trace, as it read
    when the recording was made."""
    want = json.loads((DATA / "granite_4_steps.reduced.json").read_text())
    got = json.loads(json.dumps(dataclasses.asdict(
        T.reduce(str(FIXTURE)))))
    assert got == want


def _client(arrival, admit, start, first, **kw):
    r = SimpleNamespace(arrival_time=arrival, admit_time=admit,
                        prefill_start_time=start, first_token_time=first,
                        **kw)
    return SimpleNamespace(r=r, judged=True)


def _rec(clients):
    return SimpleNamespace(judged=lambda: [c for c in clients if c.judged])


def test_lifecycle_parts():
    cs = [_client(1.0, 1.5, 2.5, 4.5), _client(2.0, 2.0, 3.0, 3.5),
          _client(3.0, 3.25, -1.0, -1.0)]          # no first token yet
    cs.append(_client(0.0, 0.0, 0.0, 0.1))
    cs[-1].judged = False
    rec = _rec(cs)
    parts = [_lifecycle.mean_part(rec, k) for k in range(3)]
    assert parts == pytest.approx([0.25, 1.0, 1.25])
    assert sum(parts) == pytest.approx((3.5 + 1.5) / 2, abs=1e-12)


def test_lifecycle_without_admission_stamps():
    """A program that stamps no admission reads nothing, and raises
    nothing."""
    c = _client(1.0, 1.5, 2.5, 4.5)
    del c.r.admit_time
    assert _lifecycle.waits(_rec([c])) is None
    assert _lifecycle.mean_part(_rec([c]), 0) is None
    assert _lifecycle.mean_part(_rec([]), 0) is None


def test_span_tool_runs_a_cell(tmp_path, capsys):
    """The span tool's traced run on the CPU: the run's own line and the
    cell's end-to-end metrics; a CPU trace has no device ops to split."""
    from bench.tests.rehearse import CPU_PEAKS, make_root
    from bench.tools import engine_spans
    root = make_root(tmp_path, rate_scale=6.0)
    out = engine_spans.traced_run(
        ["--workload", "granite-chat", "--seed", str(2 ** 33 + 7),
         "--seconds", "4"], root=root, require_tpu=False,
        peak_table=CPU_PEAKS)
    assert out["run"]["correct"] is True
    assert set(out["end_to_end"]) == {"ttft_p50_s", "tbt_p95_ms", "setup_s"}
    assert out["ttft_requests"] > 0
    parts = [out["run"]["metrics"][f"ttft_{p}_s_mean"]["value"]
             for p in ("admit_wait", "lane_wait", "prefill")]
    assert all(p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(out["ttft_engine_s_mean"], abs=1e-9)
    assert "engine_host_idle_share" not in out and out["start_trace_s"] > 0
    # put back after the run
    assert T.reduce.__name__ == "reduce"
    assert jax.profiler.start_trace.__name__ == "start_trace"
    assert out["ttft_client_s_mean"] >= out["ttft_engine_s_mean"]


def test_span_cost_loop():
    from bench.tools import engine_spans
    out = engine_spans.span_cost(50, repeats=2)
    assert len(out["runs_us"]) == 2 and out["span_cost_us_per_interval"] > 0


def test_rehearsal_reads_the_lifecycle_metrics(tmp_path, capsys):
    """granite-chat's traced run on the CPU reports the three parts of
    time to first token."""
    last, _, _ = run_cell(tmp_path, capsys, "granite-chat", trace=1,
                          rate_scale=6.0)
    got = last["metrics"]
    for name in ("ttft_admit_wait_s_mean", "ttft_lane_wait_s_mean",
                 "ttft_prefill_s_mean"):
        assert got[name]["unit"] == "s" and got[name]["value"] >= 0
