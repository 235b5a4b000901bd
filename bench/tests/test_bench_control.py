"""The control, the reference computed with float8 inputs to every linear
layer, put in the program's place in a run's comparison, comes out as
not correct, on three seeds, while the program's gap on the same served
requests reads far narrower.

At these widths (d_model 128, two layers) the program runs in float32
and the control's gap is some ten times smaller than at the cell's own
widths, where it reads 0.6-1.0 against the cell's limit of 0.4 (PERF.md).
So the run here is held to a limit read at these widths: the program's
gap reads 0, the control's 0.08-0.17."""
import pytest

from bench.tests.cpu_run import run_cell

LIMIT_AT_TEST_WIDTHS = 0.02


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 2 ** 33 + 13])
def test_control_reads_wider_than_the_program(tmp_path, capsys, seed):
    last, err, _ = run_cell(tmp_path, capsys, "granite-chat", seed=seed,
                            control=1, rate_scale=6.0,
                            limit=LIMIT_AT_TEST_WIDTHS)
    r = last["readings"]
    gap = last["checks"]["logit_gap"]
    assert last["correct"] is False, last["checks"]
    assert gap["value"] == r["control_gap"] > gap["limit"], gap
    assert r["program_gap"] <= gap["limit"] / 3, r
    assert f"check logit_gap {gap['value']!r} <= {gap['limit']!r}" in err
