"""Physically paged KV cache (DESIGN §9).

Covers the tentpole — paged-vs-contiguous equivalence at the model and
engine level, the paged flash-decode Pallas kernel, zero-copy lifecycle —
and the allocator-drift regression family: state-only (SSM) block leak,
failed-grow preemption, and engine/sim admission parity under
batch_buckets + the free-block watermark.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import ServeConfig
from repro.config.registry import get_config
from repro.models.model import build_model
from repro.serving.engine import Engine
from repro.serving.kv_cache import BlockManager

ARCHS = ["granite-3-8b", "mamba2-2.7b", "recurrentgemma-9b"]


def setup_model(arch):
    cfg = get_config(arch, "reduced")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


# ---------------------------------------------------------------------------
# paged flash-decode kernel vs gather-then-attend oracle


def _paged_case(n_layers):
    """Stacked (n_layers, NB, bs, KV, hd) pools whose layers differ, with
    non-contiguous, non-monotone physical blocks per request, unallocated
    table slots, and stale positions in an unowned block."""
    rng = np.random.RandomState(0)
    B, H, KV, hd, NB, bs, MB = 3, 4, 2, 16, 10, 8, 4
    q = jnp.asarray(rng.randn(B, H, hd), jnp.float32)
    kp = jnp.asarray(rng.randn(n_layers, NB, bs, KV, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(n_layers, NB, bs, KV, hd), jnp.float32)
    owned = [[2, 5, 7], [1], [9, 0]]
    tables = np.full((B, MB), -1, np.int32)
    for b, tbl in enumerate(owned):
        tables[b, :len(tbl)] = tbl
    q_pos = jnp.asarray([20, 5, 11], jnp.int32)
    kpos = np.full((NB, bs), -1, np.int32)
    for b, tbl in enumerate(owned):
        for j, pb in enumerate(tbl):
            for o in range(bs):
                p = j * bs + o
                if p <= int(q_pos[b]):
                    kpos[pb, o] = p
    kpos[3] = 2  # stale positions in an UNOWNED block must stay invisible
    return q, kp, vp, q_pos, jnp.asarray(kpos), jnp.asarray(tables)


def test_paged_kernel_matches_ref():
    from repro.kernels import ops

    q, kp, vp, q_pos, kpos, tables = _paged_case(1)
    for window in (0, 6):
        ref = ops.paged_decode_attention(q, kp, vp, q_pos, kpos, tables, 0,
                                         window=window, use_kernel=False)
        ker = ops.paged_decode_attention(q, kp, vp, q_pos, kpos, tables, 0,
                                         window=window, use_kernel=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("route", ["kernel", "oracle", "paged_view"])
def test_paged_reads_its_layer(route):
    """Layer 2 of a 3-layer stacked pool reads layer 2: each route
    matches the oracle on that layer cut out alone, and differs from
    layer 0 (a reader stuck on layer 0 passes every L = 1 test)."""
    from repro.kernels import ops, ref
    from repro.models.layers import paged_view

    q, kp, vp, q_pos, kpos, tables = _paged_case(3)

    def read(layer):
        if route == "paged_view":
            return paged_view(kp, vp, kpos, tables, layer)
        return (ops.paged_decode_attention(
            q, kp, vp, q_pos, kpos, tables, layer,
            use_kernel=route == "kernel"),)

    if route == "paged_view":
        want = ref.paged_view(kp[2:], vp[2:], kpos, tables, 0)
    else:
        want = (ref.paged_decode_attention_ref(
            q, kp[2:], vp[2:], q_pos, kpos, tables, 0),)
    got, first = read(jnp.int32(2)), read(0)
    for g, w, f in zip(got, want, first):
        if route == "kernel":
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not np.allclose(np.asarray(got[0]), np.asarray(first[0]))


# ---------------------------------------------------------------------------
# model-level equivalence: identical decode logits and final cache contents


@pytest.mark.parametrize("arch", ARCHS)
def test_model_paged_equals_contiguous(arch):
    cfg, m, params = setup_model(arch)
    rng = np.random.RandomState(0)
    max_ctx, bs, n_new = 64, 16, 6
    lens = [12, 9]
    B = len(lens)
    T = max(lens)
    toks = np.zeros((B, T), np.int32)
    pos = np.full((B, T), -1, np.int32)
    for i, l in enumerate(lens):
        toks[i, :l] = rng.randint(0, cfg.vocab_size, size=l)
        pos[i, :l] = np.arange(l)
    toks, pos = jnp.asarray(toks), jnp.asarray(pos)

    cache_c = m.init_cache(B, max_ctx, enc_len=16, prefill_chunk=T)
    lg_c, cache_c = m.prefill(params, toks, pos, cache_c, None)

    bm = BlockManager(total_tokens=256, block_size=bs)
    MB = -(-max_ctx // bs)
    for i, l in enumerate(lens):
        assert bm.allocate(i, 0, l + n_new + 1)
    tbl = np.full((B, MB), -1, np.int32)
    for i in range(B):
        tbl[i, :len(bm.tables[i])] = bm.tables[i]
    tables = jnp.asarray(tbl)
    cache_p = m.init_paged_cache(B, bm.num_blocks, bs, enc_len=16)
    lg_p, cache_p = m.prefill_paged(params, toks, pos, tables, cache_p, None)
    np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_p))

    outs = [int(jnp.argmax(lg_c[i, lens[i] - 1])) for i in range(B)]
    cur = list(lens)
    for _ in range(n_new):
        tt = jnp.asarray(outs, jnp.int32)
        sl = jnp.asarray(cur, jnp.int32)
        lg_c, cache_c = m.decode_step(params, tt, sl, cache_c)
        lg_p, cache_p = m.decode_step_paged(params, tt, sl, tables, cache_p)
        np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_p))
        outs = [int(jnp.argmax(lg_c[i])) for i in range(B)]
        cur = [c + 1 for c in cur]

    # final cache contents: every written K/V slot must be identical
    if "k" in cache_c:
        from repro.models.layers import paged_view
        L = cache_c["k"].shape[0]
        for lay in range(L):
            kview, vview, kpos = paged_view(
                cache_p["k"], cache_p["v"], cache_p["pos"], tables, lay)
            for i, c in enumerate(cur):
                np.testing.assert_array_equal(
                    np.asarray(cache_c["k"][lay, i, :c]),
                    np.asarray(kview[i, :c]))
                np.testing.assert_array_equal(
                    np.asarray(cache_c["v"][lay, i, :c]),
                    np.asarray(vview[i, :c]))
                np.testing.assert_array_equal(
                    np.asarray(cache_c["pos"][i, :c]),
                    np.asarray(kpos[i, :c]))
    for key in ("conv", "ssm", "rec"):
        if key in cache_c:
            np.testing.assert_array_equal(np.asarray(cache_c[key]),
                                          np.asarray(cache_p[key]))


# ---------------------------------------------------------------------------
# engine-level equivalence + zero-copy lifecycle


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("chunked", [False, True])
def test_engine_paged_equals_contiguous(arch, chunked):
    cfg, m, params = setup_model(arch)
    rng = np.random.RandomState(0)
    prompts = [list(map(int, rng.randint(0, cfg.vocab_size,
                                         size=rng.randint(6, 30))))
               for _ in range(4)]

    def run(paged):
        serve = ServeConfig(policy="memory", b_max=4, max_new_tokens=5,
                            kv_pool_tokens=2048, chunked_prefill=chunked,
                            chunk_budget_tokens=8, n_prefill_lanes=2,
                            paged_kv=paged)
        eng = Engine(m, params, serve, max_context=64, buckets=(1, 2, 4),
                     prefill_chunk=8)
        hs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run()
        assert eng.total_finished == 4
        return [h.output_tokens for h in hs], eng

    out_c, eng_c = run(False)
    out_p, eng_p = run(True)
    assert out_c == out_p
    # the tentpole invariant: paged lifecycle performs ZERO row copies
    assert eng_p.copy_rows == 0
    assert eng_p.copy_bytes == 0
    if chunked:
        # contiguous lane promotion copies a full row per promoted request
        assert eng_c.copy_rows > 0
        assert eng_c.copy_bytes > 0


def test_paged_eviction_zero_copies():
    """Preemption storms under a tight pool stay O(1) in paged mode: blocks
    and the pinned state row are released, no cache_copy_row compaction."""
    cfg, m, params = setup_model("granite-3-8b")
    rng = np.random.RandomState(4)
    serve = ServeConfig(policy="static", b_max=8, max_new_tokens=40,
                        kv_pool_tokens=192, block_size=16, paged_kv=True,
                        chunked_prefill=True, chunk_budget_tokens=32,
                        n_prefill_lanes=2)
    eng = Engine(m, params, serve, max_context=64, buckets=(1, 2, 4, 8),
                 prefill_chunk=8)
    hs = [eng.submit(list(map(int, rng.randint(0, cfg.vocab_size, 10))),
                     max_new_tokens=40) for _ in range(6)]
    eng.run(max_steps=5000)
    assert eng.total_finished == 6
    assert eng.preemptions > 0
    assert eng.copy_rows == 0
    assert all(len(h.output_tokens) > 0 for h in hs)
    # allocator fully restored, no leaked blocks or slots
    assert eng.blocks.free_blocks == eng.blocks.num_blocks
    assert sorted(eng._free_slots) == list(range(eng.n_slots))


def test_release_is_one_warmed_program():
    """Clearing the pos rows of any number of freed blocks, more than a
    table's width too, runs the one program `warmup` compiled, and clears
    exactly those rows."""
    from repro.serving import engine as E
    cfg, m, params = setup_model("granite-3-8b")
    serve = ServeConfig(policy="static", b_max=2, max_new_tokens=4,
                        kv_pool_tokens=512, block_size=16, paged_kv=True,
                        chunked_prefill=True, chunk_budget_tokens=16)
    eng = Engine(m, params, serve, max_context=64, buckets=(1, 2),
                 prefill_chunk=8)
    nb = eng.cache["pos"].shape[0]
    assert nb > 2 * eng.max_blocks
    eng.cache = dict(eng.cache, pos=jnp.arange(nb * 16, dtype=jnp.int32)
                     .reshape(nb, 16))
    want = np.asarray(eng.cache["pos"]).copy()
    E._clear_pos_rows.clear_cache()
    eng.warmup()
    warmed = E._clear_pos_rows._cache_size()
    assert warmed == 1
    for freed in ([3], [0, 5, 9], list(range(10, 10 + 2 * eng.max_blocks
                                               + 1))):
        eng._release_blocks(freed)
        want[freed] = -1
        np.testing.assert_array_equal(np.asarray(eng.cache["pos"]), want)
    assert E._clear_pos_rows._cache_size() == warmed


def test_paged_multimodal_roundtrip():
    """Cross-KV state rides the pinned slot row; extras-carrying first
    chunks run through the paged single-row path."""
    cfg, m, params = setup_model("llama-3.2-vision-90b")
    rng = np.random.RandomState(4)
    extras = {"images": jnp.asarray(rng.randn(1, 16, cfg.d_model),
                                    jnp.float32)}
    prompt = list(map(int, rng.randint(0, cfg.vocab_size, size=6)))

    def run(paged):
        serve = ServeConfig(policy="memory", b_max=2, max_new_tokens=5,
                            kv_pool_tokens=1024, chunked_prefill=True,
                            chunk_budget_tokens=8, paged_kv=paged)
        eng = Engine(m, params, serve, max_context=64, buckets=(1, 2),
                     prefill_chunk=8, enc_len=16)
        h = eng.submit(prompt, max_new_tokens=5, extras=extras)
        eng.run()
        return h.output_tokens

    assert run(True) == run(False)


# ---------------------------------------------------------------------------
# allocator-drift regressions


def test_ssm_long_decode_no_spurious_preemptions():
    """State-only families must not leak a block per decode step: a long
    decode against a small pool finishes with zero preemptions and the
    allocator's footprint stays at admission size (one block/request)."""
    cfg, m, params = setup_model("mamba2-2.7b")
    assert cfg.kv_bytes_per_token() == 0
    rng = np.random.RandomState(0)
    serve = ServeConfig(policy="static", b_max=4, max_new_tokens=56,
                        kv_pool_tokens=64, block_size=16)  # only 4 blocks
    eng = Engine(m, params, serve, max_context=64, buckets=(1, 2, 4),
                 prefill_chunk=8)
    hs = [eng.submit(list(map(int, rng.randint(0, cfg.vocab_size, 6))),
                     max_new_tokens=56) for _ in range(3)]
    eng.run(max_steps=2000)
    assert eng.total_finished == 3
    assert all(len(h.output_tokens) == 56 for h in hs)
    # pre-fix: free_tokens drained ~1 block per request per block_size
    # steps, triggering spurious preemptions long before completion
    assert eng.preemptions == 0
    assert eng.blocks.free_blocks == eng.blocks.num_blocks


def test_sim_ssm_long_decode_no_drift():
    from repro.serving.cost_model import CostModel, PROFILES
    from repro.serving.sim import LengthDist, ServingSimulator

    cfg = get_config("mamba2-2.7b")
    cost = CostModel(cfg, PROFILES["a100x8"])
    lengths = LengthDist(mean_in=64, mean_out=256, fixed=True)
    serve = ServeConfig(policy="static", b_max=8, max_new_tokens=256,
                        kv_pool_tokens=0, block_size=16)
    sim = ServingSimulator(cfg, serve, cost, lengths, seed=0)
    sim.add_requests(8)
    res = sim.run()
    assert res.finished == 8
    assert res.preemptions == 0
    assert res.oom_events == 0
    assert sim.blocks.free_blocks == sim.blocks.num_blocks


@pytest.mark.parametrize("paged", [False, True])
def test_failed_grow_preempts_instead_of_drifting(paged):
    """A decode-step grow that fails must preempt the request (recompute),
    never emit tokens without backing blocks; the allocator invariant
    (owned + free == total) and per-request coverage must hold."""
    cfg, m, params = setup_model("granite-3-8b")
    rng = np.random.RandomState(1)
    serve = ServeConfig(policy="static", b_max=2, max_new_tokens=30,
                        kv_pool_tokens=512, block_size=16, paged_kv=paged)
    eng = Engine(m, params, serve, max_context=64, buckets=(1, 2),
                 prefill_chunk=8)
    hs = [eng.submit(list(map(int, rng.randint(0, cfg.vocab_size, 10))),
                     max_new_tokens=30) for _ in range(2)]
    for _ in range(3):
        eng.step()
    assert len(eng.active) == 2
    # exhaust the pool behind the scheduler's back and disable the
    # softer preempt-ahead check so the grow itself must fail
    eng.blocks.allocate(9999, 0, eng.blocks.free_tokens)
    eng._preempt_if_needed = lambda: None
    for _ in range(40):
        if eng.preemptions:
            break
        eng.step()
    assert eng.preemptions > 0
    bm = eng.blocks
    owned = sum(len(t) for t in bm.tables.values())
    assert owned + bm.free_blocks == bm.num_blocks
    # every still-active request has full block coverage for its context
    for r in eng.active:
        assert len(bm.tables[r.rid]) * bm.block_size >= r.context_len
    # evicted requests emitted nothing unbacked: outputs were cleared
    evicted = [h for h in hs if h in eng.waiting]
    assert all(h.output_tokens == [] for h in evicted)


def test_engine_admission_bucketized_matches_sim():
    """DESIGN §7 parity: with batch_buckets set, the engine bucketizes the
    policy cap exactly like the simulator — 7 ready requests against
    buckets (1,2,4) admit at most 4 concurrently in both."""
    from repro.core.batching import bucketize
    from repro.serving.cost_model import CostModel, PROFILES
    from repro.serving.sim import LengthDist, ServingSimulator

    cfg, m, params = setup_model("granite-3-8b")
    rng = np.random.RandomState(2)
    buckets = (1, 2, 4)
    serve = ServeConfig(policy="static", b_max=8, max_new_tokens=6,
                        kv_pool_tokens=4096, batch_buckets=buckets)
    cap = bucketize(serve.b_max, buckets)
    eng = Engine(m, params, serve, max_context=64, buckets=(1, 2, 4, 8),
                 prefill_chunk=8)
    hs = [eng.submit(list(map(int, rng.randint(0, cfg.vocab_size, 6))),
                     max_new_tokens=6) for _ in range(7)]
    peak = 0
    while eng.step():
        peak = max(peak, len(eng.active) + len(eng.prefilling))
    assert eng.total_finished == 7
    assert peak == cap
    assert all(len(h.output_tokens) == 6 for h in hs)

    sim_cfg = get_config("granite-3-8b")
    cost = CostModel(sim_cfg, PROFILES["a100x8"])
    lengths = LengthDist(mean_in=6, mean_out=6, fixed=True)
    sim = ServingSimulator(sim_cfg, serve, cost, lengths, seed=0)
    sim.add_requests(7)
    res = sim.run()
    assert res.finished == 7
    assert max(res.batch_trace) == cap


def test_pallas_paged_decode_matches_jnp(tmp_path):
    """The paged decode path through the Pallas kernel (interpret mode on
    CPU) must match the pure-jnp gathered view — subprocess per backend,
    mirroring tests/test_pallas_integration.py."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    src = str(Path(__file__).resolve().parent.parent / "src")
    script = textwrap.dedent("""
        import os
        os.environ["REPRO_USE_PALLAS"] = os.environ["WANT_PALLAS"]
        import jax, jax.numpy as jnp, numpy as np
        from repro.config.registry import get_config
        from repro.models.model import build_model
        from repro.serving.kv_cache import BlockManager

        cfg = get_config("granite-3-8b", "reduced")
        m = build_model(cfg, dtype=jnp.float32)
        params = m.init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        bm = BlockManager(total_tokens=128, block_size=16)
        bm.allocate(0, 0, 20); bm.allocate(1, 0, 20)
        tbl = np.full((2, 2), -1, np.int32)
        for i in range(2):
            tbl[i, :len(bm.tables[i])] = bm.tables[i]
        tables = jnp.asarray(tbl)
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 12)), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32)[None], (2, 12))
        cache = m.init_paged_cache(2, bm.num_blocks, 16)
        lg, cache = m.prefill_paged(params, toks, pos, tables, cache, None)
        outs = [int(jnp.argmax(lg[0, -1]))]
        vals = []
        for t in range(12, 18):
            lg, cache = m.decode_step_paged(
                params, jnp.full((2,), outs[-1], jnp.int32),
                jnp.full((2,), t, jnp.int32), tables, cache)
            outs.append(int(jnp.argmax(lg[0])))
            vals.append(np.asarray(lg))
        np.save(os.environ["OUT_NPY"], np.stack(vals))
    """)

    def run_variant(want, out):
        env = dict(os.environ, PYTHONPATH=src, WANT_PALLAS=want, OUT_NPY=out)
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=540)
        assert proc.returncode == 0, proc.stderr[-2000:]

    a, b = str(tmp_path / "a.npy"), str(tmp_path / "b.npy")
    run_variant("0", a)
    run_variant("1", b)
    np.testing.assert_allclose(np.load(a), np.load(b), rtol=2e-4, atol=2e-4)


def test_engine_watermark_counts_oom_events():
    """The vLLM-style 1% free-block floor refuses admissions that would
    empty the pool, counting oom_events (previously engine-only silent).
    A request the pool can NEVER hold is rejected outright instead of
    wedging the queue in a no-op busy-spin."""
    from repro.serving.request import RequestState

    cfg, m, params = setup_model("granite-3-8b")
    rng = np.random.RandomState(3)
    serve = ServeConfig(policy="static", b_max=2, max_new_tokens=4,
                        kv_pool_tokens=32, block_size=16)  # 2 blocks
    eng = Engine(m, params, serve, max_context=64, buckets=(1, 2),
                 prefill_chunk=8)
    big = eng.submit(list(map(int, rng.randint(0, cfg.vocab_size, 20))),
                     max_new_tokens=4)
    ok = eng.submit(list(map(int, rng.randint(0, cfg.vocab_size, 6))),
                    max_new_tokens=4)
    steps = eng.run(max_steps=1000)
    # big needs 2 blocks; admitting would leave 0 < watermark(1), and no
    # pool state can ever satisfy it: rejected, not head-of-line wedged
    assert eng.rejected == 1
    assert big.state == RequestState.FINISHED and big.rejected
    assert big.output_tokens == []
    # the queue behind it still gets served, and the run terminates
    assert len(ok.output_tokens) == 4
    assert eng.total_finished == 1
    assert steps < 1000
    assert eng.blocks.free_blocks == eng.blocks.num_blocks


def test_paged_rejects_prompt_exceeding_table_width():
    """A prompt needing more blocks than the per-request table width
    (ceil(max_context / block_size)) can never be represented — it must be
    rejected at admission, not crash the table build."""
    from repro.serving.request import RequestState

    cfg, m, params = setup_model("granite-3-8b")
    rng = np.random.RandomState(5)
    serve = ServeConfig(policy="static", b_max=2, max_new_tokens=4,
                        kv_pool_tokens=2048, block_size=16, paged_kv=True)
    eng = Engine(m, params, serve, max_context=32, buckets=(1, 2),
                 prefill_chunk=8)   # max_blocks = 2, pool = 128 blocks
    big = eng.submit(list(map(int, rng.randint(0, cfg.vocab_size, 40))),
                     max_new_tokens=4)
    ok = eng.submit(list(map(int, rng.randint(0, cfg.vocab_size, 6))),
                    max_new_tokens=4)
    eng.run(max_steps=1000)
    assert big.state == RequestState.FINISHED and big.rejected
    assert big.output_tokens == []
    assert eng.rejected == 1
    assert len(ok.output_tokens) == 4
