"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e (four engine steps of granite-3-8b `chip`)."""
from pathlib import Path

import pytest

from bench.harness import trace as T

FIXTURE = Path(__file__).parent / "data" / "granite_4_steps.xplane.pb"


def test_union_merges_overlaps():
    assert T.union([(5, 9), (0, 3), (2, 4), (9, 12)]) == [(0, 4), (5, 12)]


def test_self_times_subtract_nested_ops():
    # a 100 ns loop holding two ops of 30 and 20 ns, then a lone 10 ns op
    ev = [(0, 100, "while"), (10, 40, "a"), (50, 70, "b"),
          (200, 210, "c")]
    got = T.self_times(ev)
    assert got == pytest.approx({"while": 50e-9, "a": 30e-9, "b": 20e-9,
                                 "c": 10e-9})


@pytest.mark.parametrize("name,want", [
    ("%paged_decode_attention.14 = bf16[8,8,4,128] custom-call()",
     "paged_decode_attention"),
    ("%fusion.140 = bf16[8,12800] fusion(x)", "fusion"),
    ("%while.16 = (s32[]) while()", "while"),
    ("copy-start", "copy-start")])
def test_op_names(name, want):
    assert T.op_name(name) == want


def test_program_names():
    assert T.program_name("jit__decode_paged_fn(4456782974305331702)") \
        == "jit__decode_paged_fn"


def test_recorded_trace():
    r = T.reduce(str(FIXTURE))
    assert r is not None and r.devices == 1
    assert 0 < r.busy_s <= r.window_s
    dec = r.program_seconds("decode")
    assert dec > 0 and r.kernel_seconds("paged_decode") > 0
    # the kernel runs once per layer (20) in every decode step
    n = r.kernel_count("paged_decode")
    assert n > 0 and n % 20 == 0
    # kernel time is part of the decode programs' time
    assert r.kernel_seconds("paged_decode") < dec
    assert sum(s for _, s in r.top_ops) <= r.busy_s * 1.0001
    assert r.idle_gaps and all(s > 0 for _, s in r.idle_gaps)
