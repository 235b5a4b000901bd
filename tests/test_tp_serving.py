"""Mesh-sharded serving tests (DESIGN §12).

A subprocess gets 4 forced host devices and runs the engine on a
(data=2, model=2) test mesh: paged and contiguous layouts, swap on and
off, with three families of assertions —

* bitwise-identical output tokens vs the single-device engine (TP must
  not change what gets decoded);
* chip-aware capacity: the pool token capacity and Alg-1's free-token
  signal scale with the model-axis size at fixed per-chip pool, and a
  mesh engine at per-chip pool P behaves counter-for-counter like a
  single-device engine at pool m·P;
* engine-vs-sim differential parity under a mesh (the sim mirrors the
  per-chip budget), and the shard_map paged Pallas kernel is bitwise
  identical to the single-device kernel.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.config.base import ServeConfig
    from repro.config.registry import get_config
    from repro.core.telemetry import Telemetry
    from repro.models.model import build_model
    from repro.serving.cost_model import CostModel, PROFILES
    from repro.serving.engine import Engine
    from repro.serving.request import Request
    from repro.serving.sim import LengthDist, ServingSimulator

    MAX_CONTEXT = 96
    cfg = get_config("granite-3-8b", "reduced")
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    cost = CostModel(cfg, PROFILES["a100x8"])
    out = {}

    def prompts_of(lens, seed):
        rng = np.random.RandomState(seed)
        return [list(map(int, rng.randint(0, cfg.vocab_size, size=pl)))
                for pl in lens]

    def run_engine(serve, lens, max_new, seed=0):
        eng = Engine(model, params, serve, max_context=MAX_CONTEXT,
                     buckets=(1, 2, 4), prefill_chunk=8, cost=cost)
        hs = [eng.submit(p, max_new_tokens=max_new, arrival_time=0.0)
              for p in prompts_of(lens, seed)]
        eng.run(max_steps=20_000)
        return eng, [h.output_tokens for h in hs]

    def serve_cfg(mesh=(), paged=True, pool=256, swap=0, preempt="auto",
                  policy="static", chunked=True):
        return ServeConfig(policy=policy, b_max=4, max_new_tokens=8,
                           kv_pool_tokens=pool, block_size=16,
                           chunked_prefill=chunked, chunk_budget_tokens=24,
                           n_prefill_lanes=2, paged_kv=paged,
                           swap_space_blocks=swap, preempt=preempt,
                           mesh_shape=mesh)

    LENS = [28, 34, 22, 30, 26]

    # 1) paged: mesh vs single-device — identical tokens, scaled capacity,
    #    zero row copies, pool physically sharded over "model"
    e1, o1 = run_engine(serve_cfg(), LENS, 8)
    e2, o2 = run_engine(serve_cfg(mesh=(2, 2)), LENS, 8)
    out["paged"] = {
        "outputs_identical": o1 == o2,
        "capacity_single": e1.mem.eta, "capacity_mesh": e2.mem.eta,
        "model_shards": e2.model_shards,
        "copy_rows_mesh": e2.copy_rows,
        "pool_spec": str(e2.cache["k"].sharding.spec),
        "finished": [e1.total_finished, e2.total_finished],
    }

    # 2) contiguous fallback cache on the same mesh — identical tokens
    e3, o3 = run_engine(serve_cfg(paged=False), LENS, 8)
    e4, o4 = run_engine(serve_cfg(paged=False, mesh=(2, 2)), LENS, 8)
    out["contiguous"] = {
        "outputs_identical": o3 == o4 == o1,
        "cache_spec": str(e4.cache["k"].sharding.spec),
    }

    # 3) chip-aware accounting: mesh engine at per-chip pool P must match a
    #    single-device engine at pool m*P counter for counter (same eta ->
    #    same BlockManager decisions), under swap pressure, forced swaps
    tight = serve_cfg(mesh=(1, 2), pool=80, swap=24, preempt="swap")
    wide = serve_cfg(pool=160, swap=24, preempt="swap")
    e5, o5 = run_engine(tight, [40, 44, 38, 46], 12, seed=2)
    e6, o6 = run_engine(wide, [40, 44, 38, 46], 12, seed=2)
    out["perchip"] = {
        "eta": [e5.mem.eta, e6.mem.eta],
        "outputs_identical": o5 == o6,
        "swap_outs": [e5.swap_outs, e6.swap_outs],
        "swap_ins": [e5.swap_ins, e6.swap_ins],
        "preemptions": [e5.preemptions, e6.preemptions],
        "oom_events": [e5.oom_events, e6.oom_events],
        "admitted": [e5.admitted_total, e6.admitted_total],
    }

    # 4) engine-vs-sim differential parity under a mesh, swap on and off:
    #    the sim twin scales the same per-chip pool by the same shard rule
    def diff_pair(serve, lens, max_new, seed):
        eng, _ = run_engine(serve, lens, max_new, seed=seed)
        sim = ServingSimulator(cfg, serve, cost,
                               LengthDist(mean_in=float(np.mean(lens)),
                                          mean_out=float(max_new)),
                               seed=0, prefill_chunk=8,
                               max_context=MAX_CONTEXT)
        sim.tel = Telemetry()
        for i, pl in enumerate(lens):
            sim.waiting.append(Request(
                rid=i, arrival_time=0.0, prompt_len=pl,
                max_new_tokens=min(max_new, MAX_CONTEXT - pl - 1)))
        sim._all.extend(sim.waiting)
        res = sim.run(max_steps=20_000)
        return {
            "eta": [eng.mem.eta, sim.mem.eta],
            "admitted": [eng.admitted_total, res.admitted],
            "preemptions": [eng.preemptions, res.preemptions],
            "oom_events": [eng.oom_events, res.oom_events],
            "rejected": [eng.rejected, res.rejected],
            "swap_outs": [eng.swap_outs, res.swap_outs],
            "swap_ins": [eng.swap_ins, res.swap_ins],
            "drained": not (eng.waiting or eng.active or eng.prefilling
                            or eng.swapped or sim.waiting or sim.running
                            or sim.pending_prefill or sim.swapped),
        }

    out["diff_noswap"] = diff_pair(
        serve_cfg(mesh=(2, 2), pool=96, policy="memory"),
        [40, 44, 38, 46], 12, seed=1)
    out["diff_swap"] = diff_pair(
        serve_cfg(mesh=(2, 2), pool=80, swap=24, preempt="swap"),
        [40, 44, 38, 46], 12, seed=2)

    # 5) shard_map paged Pallas kernel (interpret): bitwise vs the
    #    single-device kernel, close to the jnp oracle
    from repro.kernels.decode_attention import paged_decode_attention_kernel
    from repro.kernels.ops import paged_decode_attention_tp
    from repro.kernels.ref import paged_decode_attention_ref
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh((2, 2))
    B, H, KV, hd, NB, bs = 3, 4, 2, 32, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    # stacked pools of 3 layers, read at layer 2
    kpool = jax.random.normal(ks[0], (3, NB, bs, KV, hd), jnp.float32)
    vpool = jax.random.normal(ks[1], (3, NB, bs, KV, hd), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, hd), jnp.float32)
    kpos = jnp.tile(jnp.arange(bs)[None], (NB, 1))
    tables = jnp.array([[0, 1, -1, -1], [2, 3, 4, -1], [5, -1, -1, -1]],
                       jnp.int32)
    qpos = jnp.array([20, 40, 10], jnp.int32)
    tp = paged_decode_attention_tp(q, kpool, vpool, qpos, kpos, tables, 2,
                                   mesh=mesh)
    single = paged_decode_attention_kernel(q, kpool, vpool, qpos, kpos,
                                           tables, 2, interpret=True)
    ref = paged_decode_attention_ref(q, kpool, vpool, qpos, kpos, tables, 2)
    # the oracle on layer 2 cut out alone: no layer index to get wrong
    alone = paged_decode_attention_ref(q, kpool[2:], vpool[2:], qpos, kpos,
                                       tables, 0)
    layer0 = paged_decode_attention_ref(q, kpool, vpool, qpos, kpos, tables,
                                        0)
    out["kernel"] = {
        "tp_bitwise_vs_single": bool(jnp.all(tp == single)),
        "tp_vs_ref_maxdiff": float(jnp.max(jnp.abs(tp - ref))),
        "tp_vs_layer_alone_maxdiff": float(jnp.max(jnp.abs(tp - alone))),
        "tp_vs_layer0_maxdiff": float(jnp.max(jnp.abs(tp - layer0))),
    }

    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def tp_results():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_paged_mesh_outputs_bitwise_identical(tp_results):
    r = tp_results["paged"]
    assert r["outputs_identical"]
    assert r["finished"][0] == r["finished"][1] == 5


def test_paged_pool_sharded_and_capacity_scales(tp_results):
    r = tp_results["paged"]
    assert r["model_shards"] == 2
    assert r["capacity_mesh"] == 2 * r["capacity_single"]
    assert "model" in r["pool_spec"]      # K/V pools physically sharded
    assert r["copy_rows_mesh"] == 0       # paged O(1) moves survive TP


def test_contiguous_mesh_outputs_bitwise_identical(tp_results):
    r = tp_results["contiguous"]
    assert r["outputs_identical"]
    assert "model" in r["cache_spec"]


def test_perchip_pool_equals_scaled_single_device(tp_results):
    """A (model=2) engine at per-chip pool P is counter-for-counter the
    single-device engine at pool 2P — admission, watermark, preemption,
    and swap all see the same sharded capacity (DESIGN §12)."""
    r = tp_results["perchip"]
    assert r["eta"][0] == r["eta"][1]
    assert r["swap_outs"][0] > 0          # the regime actually triggered
    for key in ("outputs_identical",):
        assert r[key]
    for key in ("swap_outs", "swap_ins", "preemptions", "oom_events",
                "admitted"):
        assert r[key][0] == r[key][1], (key, r)


@pytest.mark.parametrize("scenario", ["diff_noswap", "diff_swap"])
def test_differential_parity_under_mesh(tp_results, scenario):
    """Engine-vs-sim differential parity holds under a (2, 2) mesh: the
    sim mirrors the per-chip budget via the same shard rule."""
    r = tp_results[scenario]
    assert r["drained"]
    for key in ("eta", "admitted", "preemptions", "oom_events", "rejected",
                "swap_outs", "swap_ins"):
        assert r[key][0] == r[key][1], (key, r)
    if scenario == "diff_swap":
        assert r["swap_outs"][0] > 0


def test_shard_map_paged_kernel_bitwise(tp_results):
    r = tp_results["kernel"]
    assert r["tp_bitwise_vs_single"]
    assert r["tp_vs_ref_maxdiff"] < 1e-5


def test_shard_map_paged_kernel_reads_its_layer(tp_results):
    """The TP kernel at layer 2 of a 3-layer stack reads layer 2: it
    matches the oracle on that layer cut out alone, and not layer 0 (a
    kernel that always read layer 0 would pass every L = 1 test)."""
    r = tp_results["kernel"]
    assert r["tp_vs_layer_alone_maxdiff"] < 1e-5
    assert r["tp_vs_layer0_maxdiff"] > 1e-2
