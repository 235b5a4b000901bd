"""On-chip smoke test: serve granite-3-8b at published widths on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, mesh (1, 4)

One chip: builds the engine exactly as `python -m repro.launch.serve`
does (`launch/serve.py:build_engine`) for `--variant chip` (20 layers at
every published width, bf16, random weights from `--seed`) with the paged
KV pool, chunked prefill over two lanes and the combined policy; warms
every compiled shape, serves 8 seeded requests, then checks one paged
decode step through the Pallas kernel against the jnp reference path at
full width.

Four chips (only that path): the 40-layer `full` variant, which one chip
cannot hold, served tensor-parallel on mesh (1, 4); then the `chip`
variant's prefill and decode logits on that mesh against the same
variant alone on device 0.

Runs in this one process (a child could not reach the chip this process
holds). Exits non-zero, printing no result, when JAX finds no TPU. Every
phase raises on failure; the last stdout line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "granite-3-8b"
N_REQUESTS = 8
MAX_NEW = 32
PROMPT_LENS = (192, 256, 320, 384)   # whole 16-token chunks: no tail graphs
SERVE_FLAGS = ["--arch", ARCH, "--paged", "--chunked", "--lanes", "2",
               "--policy", "combined", "--max-context", "2048",
               "--pool-tokens", "16384", "--b-max", "8",
               "--max-new", str(MAX_NEW)]

# Tolerances. bf16 keeps 8 significant bits, so two correct paths that
# round in different places differ by about BF16_EPS relative per layer.
#
# Logits (kernel vs jnp reference; mesh (1, 4) vs one chip): the kernel
# keeps softmax and P.V in fp32 where the reference rounds to bf16, and
# the TP all-reduces sum bf16 partial products in another order. Each
# layer adds such a difference to the residual stream and they add up like
# independent errors, about BF16_EPS * sqrt(layers): at the reduced width
# on the CPU the kernel-vs-reference rel L2 was 0.0087 at 2 layers and
# 0.023 at 20, mesh-vs-single 0.030 at 20. The limits below are 4x
# (rel L2) and 8x (max) that law; a decode step that drops one kv tile
# moves logits by 0.32 rel L2 at 20 layers.
#
# Attention output (kernel vs `kernels/ref.py` on one layer's real pool):
# one rounding of each, no depth; a mask one key short over ~300 keys
# moves it by a few percent, so this check resolves what the depth-noisy
# logits cannot.
BF16_EPS = 2.0 ** -8
ATTN_REL_L2 = 4 * BF16_EPS
ATTN_MAX_REL = 8 * BF16_EPS


def logits_limits(num_layers: int):
    """(rel L2, max rel) limits for logits after `num_layers` layers."""
    root = num_layers ** 0.5
    return 4 * BF16_EPS * root, 8 * BF16_EPS * root


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit records its retrieval as the compile)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def lap(self):
        out = (self.seconds, self.hits)
        self.seconds, self.hits = 0.0, 0
        return out


def peak_bytes(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def rel_errors(got, want):
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    d = got - want
    return (float(np.linalg.norm(d) / np.linalg.norm(want)),
            float(np.abs(d).max() / np.abs(want).max()))


def check_close(name, got, want, rel_l2, max_rel):
    import numpy as np
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(want))):
        raise AssertionError(f"{name}: non-finite values")
    l2, mx = rel_errors(got, want)
    print(f"{name}: rel_l2={l2!r} (limit {rel_l2}) max_rel={mx!r} "
          f"(limit {max_rel})")
    if not (l2 <= rel_l2 and mx <= max_rel):
        raise AssertionError(f"{name}: disagree beyond tolerance")


def serve_phase(variant: str, extra_flags, seed: int, clock: CompileClock,
                tag: str):
    """Build the engine through `launch/serve.py`, warm it, serve
    N_REQUESTS seeded requests and check every one finished whole.
    Returns the engine."""
    import jax
    import numpy as np

    from repro.launch.serve import build_engine, build_parser

    args = build_parser().parse_args(
        SERVE_FLAGS + ["--variant", variant, "--seed", str(seed)]
        + list(extra_flags))
    clock.lap()
    t0 = time.perf_counter()
    eng, cfg = build_engine(args)
    jax.block_until_ready(eng.params)
    init_s = time.perf_counter() - t0
    init_compile = clock.lap()
    t0 = time.perf_counter()
    eng.warmup()
    warm_s = time.perf_counter() - t0
    warm_compile = clock.lap()

    rng = np.random.RandomState(seed)
    reqs = [eng.submit(list(map(int, rng.randint(
                0, cfg.vocab_size, size=int(rng.choice(PROMPT_LENS))))),
                       max_new_tokens=MAX_NEW)
            for _ in range(N_REQUESTS)]
    t0 = time.perf_counter()
    eng.run()
    serve_s = time.perf_counter() - t0
    serve_compile = clock.lap()
    summary = eng.summary()
    print(f"[{tag}] {cfg.name}: {cfg.num_layers} layers, "
          f"{cfg.param_count()} params, mesh {args.mesh or 'none'}")
    print(f"[{tag}] Engine.summary(): "
          f"{json.dumps({k: float(v) for k, v in summary.items()})}")
    print(f"[{tag}] init_s={init_s!r} compile_s(init)={init_compile[0]!r} "
          f"warmup_s={warm_s!r} compile_s(warmup)={warm_compile[0]!r} "
          f"cache_hits(warmup)={warm_compile[1]} serve_s={serve_s!r} "
          f"compile_s(serve)={serve_compile[0]!r}")
    devices = eng.mesh.devices.flat if eng.mesh is not None \
        else [jax.devices()[0]]
    print(f"[{tag}] peak_bytes_in_use={peak_bytes(list(devices))}")

    if summary["finished"] != N_REQUESTS or summary["rejected"]:
        raise AssertionError(f"[{tag}] {summary['finished']} of "
                             f"{N_REQUESTS} requests finished")
    for r in reqs:
        toks = r.output_tokens
        if len(toks) != MAX_NEW or not all(
                isinstance(t, int) and 0 <= t < cfg.vocab_size for t in toks):
            raise AssertionError(f"[{tag}] request {r.rid} output {toks}")
    return eng


def paged_batch(cfg, block_size: int, max_blocks: int, seed: int,
                n_decode: int):
    """Host inputs of a small paged batch: 4 prompts of different lengths,
    each in its own run of physical blocks, plus decode positions."""
    import numpy as np

    rng = np.random.RandomState(seed + 1)
    lens = [300, 187, 64, 251]
    T = max(lens)
    B = len(lens)
    toks = np.zeros((B, T), np.int32)
    pos = np.full((B, T), -1, np.int32)
    need = -(-(T + n_decode) // block_size)
    tables = np.full((B, max_blocks), -1, np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.randint(0, cfg.vocab_size, n)
        pos[i, :n] = np.arange(n)
        tables[i, :need] = np.arange(i * need, (i + 1) * need)
    return toks, pos, tables, np.asarray(lens, np.int32), B * need


def kernel_check(eng, seed: int):
    """One paged decode step at full width through the Pallas kernel vs
    the same step through the jnp reference path (`use_pallas` off), from
    one shared prefilled pool, then the kernel alone vs its oracle on
    the last layer of that stacked pool. The kernel step's HLO must hold the Mosaic
    custom call; the reference's must not."""
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models import layers

    model, cfg, params = eng.model, eng.cfg, eng.params
    bs = eng.serve.block_size
    toks, pos, tables, lens, nb = paged_batch(cfg, bs, eng.max_blocks,
                                              seed, 1)
    cache = model.init_paged_cache(len(lens), nb, bs)
    tables = jnp.asarray(tables)
    logits, cache = jax.jit(model.prefill_paged)(
        params, jnp.asarray(toks), jnp.asarray(pos), tables, cache, None)
    nxt = jnp.argmax(logits[jnp.arange(len(lens)), lens - 1], axis=-1)
    step = (params, nxt.astype(jnp.int32), jnp.asarray(lens), tables, cache)

    kern = jax.jit(model.decode_step_paged).lower(*step).compile()
    if "tpu_custom_call" not in kern.as_text():
        raise AssertionError("decode step has no Pallas (tpu_custom_call)")
    got, _ = kern(*step)
    # a new function, so jit traces again under the patch instead of
    # handing back the kernel trace it cached for decode_step_paged
    with mock.patch.object(layers, "use_pallas", lambda: False):
        ref = jax.jit(lambda *a: model.decode_step_paged(*a)) \
            .lower(*step).compile()
    if "tpu_custom_call" in ref.as_text():
        raise AssertionError("reference decode step still calls the kernel")
    want, _ = ref(*step)
    check_close("kernel_vs_reference decode logits", got, want,
                *logits_limits(cfg.num_layers))

    # the last layer's attention over the prefilled stacked pool, query at
    # the last written position so every written key is visible
    q = jax.random.normal(jax.random.PRNGKey(seed + 2),
                          (len(lens), cfg.num_heads, cfg.resolved_head_dim),
                          jnp.bfloat16)
    attn = (q, cache["k"], cache["v"], jnp.asarray(lens) - 1,
            cache["pos"], tables, cfg.num_layers - 1)
    got = ops.paged_decode_attention(*attn, use_kernel=True)
    want = ops.paged_decode_attention(*attn, use_kernel=False)
    check_close("kernel_vs_reference last-layer attention", got, want,
                ATTN_REL_L2, ATTN_MAX_REL)


def one_chip(seed: int, clock: CompileClock):
    eng = serve_phase("chip", [], seed, clock, "1 chip")
    kernel_check(eng, seed)


def mesh_logits(model, params, tables, toks, pos, lens, feed, nb, bs,
                mesh):
    """Prefill a paged batch, then decode the given `feed` tokens (one
    row per step; fed, not sampled, so both sides of a comparison see the
    same inputs) the way the engine's jitted steps run, under the ambient
    serving mesh when one is given. Returns [prefill last-token logits,
    decode logits...] as numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed import sharding

    def init():
        return model.init_paged_cache(len(lens), nb, bs)

    prev = sharding.set_serving_mesh(mesh)
    try:
        if mesh is None:
            cache = init()
            ctx = contextlib.nullcontext()
        else:
            shard = sharding.serve_cache_shardings(
                jax.eval_shape(init), model.cfg, mesh)
            with mesh:
                cache = jax.jit(init, out_shardings=shard)()
            ctx = mesh
        with ctx:
            lg, cache = jax.jit(model.prefill_paged)(
                params, toks, pos, tables, cache, None)
            out = [np.asarray(lg[jnp.arange(len(lens)), lens - 1])]
            step = jax.jit(model.decode_step_paged)
            for i, tok in enumerate(feed):
                lg, cache = step(params, tok, lens + i, tables, cache)
                out.append(np.asarray(lg))
    finally:
        sharding.set_serving_mesh(prev)
    return out


def four_chips(seed: int, clock: CompileClock):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config.registry import get_config
    from repro.distributed.sharding import serve_param_shardings
    from repro.launch.mesh import make_serving_mesh
    from repro.models.model import build_model

    # 1) the 40-layer published model, TP over four chips
    eng = serve_phase("full", ["--mesh", "1,4"], seed, clock, "4 chips")
    del eng
    gc.collect()

    # 2) `chip` variant: mesh (1, 4) vs device 0 alone, same weights
    cfg = get_config(ARCH, "chip")
    model = build_model(cfg, dtype=jnp.bfloat16)
    bs, n_decode = 16, 4
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    toks, pos, tables, lens, nb = paged_batch(cfg, bs, 2048 // bs,
                                              seed, n_decode)
    feed = np.random.RandomState(seed + 3).randint(
        0, cfg.vocab_size, (n_decode, len(lens))).astype(np.int32)
    batch = tuple(map(jnp.asarray, (tables, toks, pos, lens, feed)))
    single = mesh_logits(model, params, *batch, nb, bs, None)
    mesh = make_serving_mesh((1, 4))
    params = jax.device_put(params, serve_param_shardings(params, cfg, mesh))
    tp = mesh_logits(model, params, *batch, nb, bs, mesh)
    limits = logits_limits(cfg.num_layers)
    check_close("mesh(1,4)_vs_single prefill logits", tp[0], single[0],
                *limits)
    for i in range(1, n_decode + 1):
        check_close(f"mesh(1,4)_vs_single decode step {i} logits", tp[i],
                    single[i], *limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if "REPRO_USE_PALLAS" in os.environ:
        print("REPRO_USE_PALLAS is set: the smoke runs the chip's own "
              "kernel routing only; unset it", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX sees {devices[0].platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} but JAX sees {len(devices)} devices",
              file=sys.stderr)
        return 1
    print(f"compile cache: {cache}")
    clock = CompileClock()
    if args.chips == 1:
        one_chip(args.seed, clock)
    else:
        four_chips(args.seed, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
