"""Serving CLI: run the continuous-batching engine on any --arch (reduced
variants on CPU; `--variant chip` serves published widths on one TPU chip,
as `chip_smoke.py` at the repo root does through `build_engine`).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
        --policy combined --sla-ms 200 --requests 20

Every flag below is documented in the README's "Serving CLI flags" table;
`tests/test_docs.py` fails if a flag is added here without a table row.

jax is imported only AFTER argument parsing: `--mesh` (DESIGN §12) must be
able to provision forced host devices for CPU test meshes, which XLA reads
at first jax init.
"""
from __future__ import annotations

import argparse

from repro.config.base import ServeConfig
from repro.config.registry import VARIANTS, get_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import ensure_cpu_devices
from repro.serving.cost_model import PROFILES


def parse_buckets(spec: str):
    """"1,2,4" -> (1, 2, 4): compiled decode batch bucket sizes."""
    try:
        shape = tuple(int(p) for p in spec.split(",") if p)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--batch-buckets wants comma-separated ints, got {spec!r}")
    if any(s < 1 for s in shape):
        raise argparse.ArgumentTypeError(
            f"--batch-buckets sizes must be >= 1, got {spec!r}")
    return shape


def parse_mesh(spec: str):
    """"2,2" / "2x2" -> (2, 2); last axis is "model" (DESIGN §12)."""
    parts = [p for p in spec.replace("x", ",").split(",") if p]
    shape = tuple(int(p) for p in parts)
    if not shape or any(s < 1 for s in shape) or len(shape) > 3:
        raise argparse.ArgumentTypeError(
            f"--mesh wants 1-3 comma-separated sizes (data,model), got {spec!r}")
    return shape


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=list_archs())
    ap.add_argument("--variant", default="reduced", choices=list(VARIANTS),
                    help="'reduced' (CPU test widths), 'full' (published "
                         "config) or 'chip' (published widths, depth cut "
                         "to one chip's share; not every arch has one)")
    ap.add_argument("--policy", default="memory",
                    choices=["static", "memory", "sla", "combined"])
    ap.add_argument("--sla-ms", type=float, default=0.0)
    ap.add_argument("--b-max", type=int, default=16)
    ap.add_argument("--b-min", type=int, default=1,
                    help="Alg 1 lower batch bound B_min")
    # controller tolerance bands + Alg 2 window control (paper §III)
    ap.add_argument("--eps-d", type=float, default=2.0, metavar="MS",
                    help="SLA latency tolerance band eps_D (ms)")
    ap.add_argument("--eps-m", type=float, default=0.05,
                    help="memory-overflow probability budget eps_M")
    ap.add_argument("--alpha", type=int, default=16,
                    help="Alg 2 window-width control alpha")
    ap.add_argument("--delta", type=int, default=4,
                    help="Alg 2 anti-noise relaxation delta")
    ap.add_argument("--l0-refresh", type=int, default=32, metavar="N",
                    help="L0 offline refresh cadence in controller intervals")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV allocator block granularity (tokens)")
    ap.add_argument("--hbm-budget", type=int, default=0, metavar="BYTES",
                    help="M_max HBM budget override; 0 derives it from "
                         "the hardware profile")
    ap.add_argument("--batch-buckets", type=parse_buckets, default=None,
                    metavar="B1,B2,...",
                    help="compiled decode batch shapes, e.g. '1,2,4,8'; "
                         "default: powers of two up to --b-max")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    # trace replay + per-request goodput SLOs (DESIGN §15)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="replay a repro-trace JSONL file (DESIGN §15) "
                         "instead of synthesizing random prompts: token "
                         "records submit verbatim (ids clamped into the "
                         "model vocab), length-only records get synthetic "
                         "tokens, per-request max-new = min(l_out, "
                         "--max-new); overrides --requests")
    ap.add_argument("--ttft-sla", type=float, default=0.0, metavar="S",
                    help="per-request TTFT goodput SLA in seconds "
                         "(ttft_sla_s); 0 disables the check (DESIGN §15)")
    ap.add_argument("--tbt-sla", type=float, default=0.0, metavar="MS",
                    help="per-request mean-TBT goodput SLA in ms "
                         "(tbt_sla_ms); 0 disables the check (DESIGN §15)")
    ap.add_argument("--pool-tokens", type=int, default=4096)
    ap.add_argument("--max-context", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    # PD fusion (DESIGN §6)
    ap.add_argument("--chunked", action="store_true",
                    help="PD-fusion mode (chunked prefill)")
    ap.add_argument("--lanes", type=int, default=1,
                    help="concurrent prefill lanes")
    ap.add_argument("--pack", default="fifo", choices=["fifo", "srf"],
                    help="lane packer policy")
    ap.add_argument("--chunk-budget", type=int, default=512,
                    help="prefill token budget per fused interval")
    # paged KV cache (DESIGN §9)
    ap.add_argument("--paged", action="store_true",
                    help="physically paged KV cache (block-table pools)")
    # prefix sharing (DESIGN §10)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="ref-counted automatic prefix sharing "
                         "(requires --paged; attention-only families)")
    # two-tier KV memory (DESIGN §11)
    ap.add_argument("--swap-space", type=int, default=0, metavar="BLOCKS",
                    help="host-side swap pool size in KV blocks; 0 keeps "
                         "recompute-only preemption (requires --paged; "
                         "attention-only families)")
    ap.add_argument("--preempt", default="auto",
                    choices=["auto", "swap", "recompute"],
                    help="preemption flavor under pool pressure: 'auto' "
                         "applies the swap-vs-recompute cost crossover, "
                         "'swap' forces swap whenever possible, "
                         "'recompute' disables swapping")
    ap.add_argument("--profile", default="a100x8",
                    choices=sorted(PROFILES),
                    help="hardware profile the 'auto' crossover prices "
                         "PCIe vs re-prefill against (DESIGN §11)")
    # async dispatch-ahead pipeline (DESIGN §14)
    ap.add_argument("--overlap-depth", type=int, default=0,
                    help="device steps left in flight while the host "
                         "schedules the next interval: 0 = synchronous "
                         "loop, 1 = dispatch-ahead overlap (DESIGN §14); "
                         "outputs are bitwise-identical at every depth")
    # mesh-sharded serving (DESIGN §12)
    ap.add_argument("--mesh", type=parse_mesh, default=None,
                    metavar="DATA,MODEL",
                    help="run the engine tensor-parallel on this device "
                         "mesh, e.g. '1,2' or '2x2'; the LAST axis is the "
                         "'model' (TP) axis and --pool-tokens becomes a "
                         "PER-CHIP budget (DESIGN §12). On CPU, forced "
                         "host devices are provisioned automatically.")
    return ap


def build_engine(args):
    """The serving engine `main` runs, from parsed flags: model config,
    parameters created on the device(s) by one jitted init (bf16 for every
    non-reduced variant; under `--mesh` each shard is created in place),
    and the Engine. Returns (engine, model config)."""
    if args.mesh:
        n = 1
        for s in args.mesh:
            n *= s
        ensure_cpu_devices(n)

    enable_compile_cache()
    import jax

    if args.mesh and len(jax.devices()) < n:
        raise SystemExit(
            f"--mesh {','.join(map(str, args.mesh))} needs {n} devices but "
            f"jax sees {len(jax.devices())}. On CPU this usually means "
            f"XLA_FLAGS already pins --xla_force_host_platform_device_count "
            f"below {n} (ensure_cpu_devices won't override it) — unset it "
            f"or raise it to {n}.")
    import jax.numpy as jnp

    from repro.models.model import build_model, default_enc_len
    from repro.serving.cost_model import CostModel
    from repro.serving.engine import Engine

    cfg = get_config(args.arch, args.variant)
    model = build_model(cfg, dtype=jnp.float32 if args.variant == "reduced"
                        else jnp.bfloat16)
    mesh = None
    shardings = None
    if args.mesh:
        from repro.distributed.sharding import serve_param_shardings
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(args.mesh)
        shardings = serve_param_shardings(model.init_shapes(), cfg, mesh)
    params = jax.jit(model.init, out_shardings=shardings)(
        jax.random.PRNGKey(args.seed))
    buckets = args.batch_buckets or \
        tuple(2 ** i for i in range(0, args.b_max.bit_length()))
    serve = ServeConfig(policy=args.policy,
                        b_min=args.b_min, b_max=args.b_max,
                        d_sla_ms=args.sla_ms,
                        ttft_sla_s=args.ttft_sla,
                        tbt_sla_ms=args.tbt_sla,
                        eps_d_ms=args.eps_d, eps_m=args.eps_m,
                        alpha=args.alpha, delta=args.delta,
                        block_size=args.block_size,
                        hbm_budget_bytes=args.hbm_budget,
                        l0_refresh_interval=args.l0_refresh,
                        max_new_tokens=args.max_new,
                        batch_buckets=buckets,
                        kv_pool_tokens=args.pool_tokens,
                        chunked_prefill=args.chunked,
                        chunk_budget_tokens=args.chunk_budget,
                        n_prefill_lanes=args.lanes,
                        prefill_pack=args.pack,
                        paged_kv=args.paged,
                        prefix_cache=args.prefix_cache,
                        swap_space_blocks=args.swap_space,
                        preempt=args.preempt,
                        overlap_depth=args.overlap_depth,
                        mesh_shape=args.mesh or ())
    enc_len = 16 if default_enc_len(cfg) else 0
    eng = Engine(model, params, serve, max_context=args.max_context,
                 buckets=buckets,
                 prefill_chunk=16, enc_len=enc_len,
                 cost=CostModel(cfg, PROFILES[args.profile]), mesh=mesh)
    return eng, cfg


def main():
    args = build_parser().parse_args()
    eng, cfg = build_engine(args)
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(args.seed)
    enc_len = eng.enc_len

    def mk_extras():
        if not enc_len:
            return None
        key = "enc_frames" if cfg.family.value == "encdec" else "images"
        return {key: jnp.asarray(rng.randn(1, enc_len, cfg.d_model),
                                 jnp.float32)}

    if args.trace:
        # trace replay (DESIGN §15): submissions follow the trace's file
        # order; service is as-fast-as-possible (the engine clock is
        # wall time, arrival gating lives in the simulator twin)
        from repro.serving.workload import load_trace_events, trace_prompts
        events = load_trace_events(args.trace)
        for toks, lo in trace_prompts(events, cfg.vocab_size,
                                      seed=args.seed):
            eng.submit(toks, max_new_tokens=max(1, min(lo, args.max_new)),
                       extras=mk_extras())
    else:
        for _ in range(args.requests):
            eng.submit(list(map(int, rng.randint(0, cfg.vocab_size,
                                                 size=rng.randint(4, 24)))),
                       extras=mk_extras())
    eng.run()
    print({k: round(v, 2) for k, v in eng.summary().items()})


if __name__ == "__main__":
    main()
