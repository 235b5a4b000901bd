"""Host spans and lifecycle stamps of the serving engine (DESIGN §16).

Under `jax.profiler` the engine's phases show on the host plane as
`engine.*` spans nested in one `engine.step` per interval, in the order
step() runs them, with their arguments. Each request's admission,
first-chunk and first-token stamps describe its last life, so after a
recompute preemption they still order and sum to the engine-side TTFT.
`summary()` rates run over the serving window, not since construction.
"""
import dataclasses
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import ServeConfig
from repro.config.registry import get_config
from repro.models.model import build_model
from repro.serving import engine as E
from repro.serving.request import Request, RequestState

_MODEL = {}
LENS = [40, 44, 38, 46]


def setup_model():
    if not _MODEL:
        cfg = get_config("granite-3-8b", "reduced")
        m = build_model(cfg, dtype=jnp.float32)
        _MODEL.update(cfg=cfg, m=m, params=m.init(jax.random.PRNGKey(0)))
    return _MODEL["cfg"], _MODEL["m"], _MODEL["params"]


def make_engine(*, pool=160, chunked=True, depth=0, max_new=12):
    _, m, params = setup_model()
    serve = ServeConfig(policy="static", b_max=4, max_new_tokens=max_new,
                        kv_pool_tokens=pool, block_size=16,
                        chunked_prefill=chunked, chunk_budget_tokens=16,
                        n_prefill_lanes=2, paged_kv=True,
                        overlap_depth=depth)
    return E.Engine(m, params, serve, max_context=96, buckets=(1, 2, 4),
                    prefill_chunk=8)


def submit(eng, lens, max_new=12, arrival=None):
    cfg = _MODEL["cfg"]
    rng = np.random.RandomState(0)
    return [eng.submit(list(map(int, rng.randint(0, cfg.vocab_size,
                                                 size=n))),
                       max_new_tokens=max_new, arrival_time=arrival)
            for n in lens]


def test_span_names_are_listed_once():
    assert len(set(E.SPANS)) == len(E.SPANS) == 10
    assert all(n.startswith("engine.") for n in E.SPANS)
    assert E.SPANS[0] == E.SPAN_STEP and E.SPANS[-1] == E.SPAN_STAMP
    assert "tbt_samples" not in {f.name
                                 for f in dataclasses.fields(Request)}


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((int(ev.start_ns), int(ev.end_ns), ev.name,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def test_spans_nest_in_step_in_table_order(tmp_path):
    eng = make_engine(pool=2048, max_new=4)
    submit(eng, LENS, max_new=4)
    eng.step()                                    # compiles, untraced
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(40):
            if not eng.step():
                break
    finally:
        jax.profiler.stop_trace()
    assert eng.total_finished == len(LENS)
    spans = _host_spans(tmp_path)
    steps = [s for s in spans if s[2] == E.SPAN_STEP]
    assert len(steps) >= 3
    nums = [s[3]["step_num"] for s in steps]
    assert nums == sorted(nums) and len(set(nums)) == len(nums)
    order = {n: i for i, n in enumerate(E.SPANS)}
    full = set(E.SPANS) - {E.SPAN_STEP, E.SPAN_RELEASE}
    seen_full = False
    for s0, e0, _, _ in steps:
        inside = [s for s in spans if s0 <= s[0] and s[1] <= e0
                  and s[2] != E.SPAN_STEP]
        # direct children of the step: phases, each once, in table order
        top = [s for s in inside if not any(
            p is not s and p[0] <= s[0] and s[1] <= p[1] for p in inside)]
        names = [s[2] for s in top]
        assert names == sorted(names, key=order.get), names
        assert len(names) == len(set(names))
        assert E.SPAN_RELEASE not in names
        seen_full |= full <= set(names)
        for s in inside:
            if s[2] == E.SPAN_RELEASE:
                parent = [p[2] for p in top if p[0] <= s[0] <= p[1]]
                assert parent in ([E.SPAN_ADMIT], [E.SPAN_DECODE])
                assert s[3]["blocks"] > 0
    # some interval admits, prefills, decodes and retires
    assert seen_full
    pre = [s[3] for s in spans if s[2] == E.SPAN_PREFILL]
    dec = [s[3] for s in spans if s[2] == E.SPAN_DECODE]
    assert pre and all(a["budget"] == 16 and 1 <= a["lanes_busy"] <= 2
                       for a in pre)
    assert dec and all(1 <= a["rows"] <= a["bucket"] <= 4 for a in dec)
    assert any(s[2] == E.SPAN_RELEASE for s in spans)
    adm = [s[3] for s in spans if s[2] == E.SPAN_ADMIT]
    assert all(a["waiting"] >= 0 for a in adm)


@pytest.mark.parametrize("chunked,depth", [(True, 0), (True, 1),
                                           (False, 0)])
def test_lifecycle_stamps_order_and_sum(chunked, depth):
    eng = make_engine(chunked=chunked, depth=depth)
    hs = submit(eng, LENS, arrival=0.0)
    evicted = None
    for _ in range(2000):
        pre = eng.preemptions
        if not eng.step():
            break
        if eng.preemptions > pre and evicted is None:
            evicted = [r for r in eng.waiting if r in hs]
        for r in hs:
            # between steps too: a recompute victim's stamps wait for its
            # next life, even where its first token retires after eviction
            if r.admit_time < 0:
                assert r.prefill_start_time == r.first_token_time == -1.0
            elif r.first_token_time >= 0:
                assert r.admit_time <= r.prefill_start_time \
                    <= r.first_token_time
    if chunked:
        assert evicted, "the pool was meant to force a preemption"
    assert eng.total_finished == len(LENS)
    for r in hs:
        assert 0.0 == r.arrival_time <= r.admit_time \
            <= r.prefill_start_time <= r.first_token_time <= r.finish_time
        parts = (r.admit_time - r.arrival_time,
                 r.prefill_start_time - r.admit_time,
                 r.first_token_time - r.prefill_start_time)
        assert abs(sum(parts) - (r.first_token_time - r.arrival_time)) \
            <= 1e-9
    for r in evicted or ():
        assert r.state == RequestState.FINISHED and r.admit_time > 0


def test_summary_times_the_serving_window():
    eng = make_engine(pool=2048, max_new=4)
    s0 = eng.summary()
    assert s0["duration_s"] == 0.0 and s0["throughput_tok_s"] == 0.0
    time.sleep(0.3)                     # stands for warm-up and compiles
    hs = submit(eng, LENS[:2], max_new=4)
    eng.run()
    s = eng.summary()
    first = min(r.arrival_time for r in hs)
    last = max(r.finish_time for r in hs)
    assert s["duration_s"] == pytest.approx(last - first)
    assert 0 < s["duration_s"] < eng._now() - 0.3
    assert s["throughput_tok_s"] == pytest.approx(
        s["total_tokens"] / s["duration_s"])


def test_first_token_of_an_evicted_life_is_not_stamped():
    """Depth 1: a request promoted in the interval still in flight is
    evicted before that interval retires; the retirement must not stamp
    the cleared life's first token."""
    eng = make_engine(pool=2048, depth=1)
    hs = submit(eng, LENS[:2], arrival=0.0)
    for _ in range(100):
        eng.step()
        fresh = [r for r in eng.active
                 if r.first_token_time < 0 and r.output_tokens[:1] == [None]]
        if fresh:
            break
    r = fresh[0]
    eng._evict(eng.active.index(r), r)
    eng.step()            # retires the promoting interval, readmits r
    assert r.first_token_time == -1.0
    eng.run()
    assert eng.total_finished == 2
    assert r.arrival_time <= r.admit_time <= r.prefill_start_time \
        <= r.first_token_time
    assert all(h.first_token_time > 0 for h in hs)
