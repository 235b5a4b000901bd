"""One cell, end to end: set-up, the measured window, the readers and the
comparison with the reference.

    setup()   build the engine, serve it the benchmark's weights, warm up
    serve()   the ramp and the window (open loop or backlog)
    check()   free the engine's state, run the reference over a sample of
              the finished requests, compare
"""
from __future__ import annotations

import dataclasses
import shutil
import sys
import time
from typing import Any, Dict, List

from bench.harness import driver as D
from bench.harness import traffic
from bench.harness.spec import Spec

TRACE_SECONDS = 10.0      # trace the window's last seconds, and the drain

# config-file key -> ModelConfig attribute, for the width check
MODEL_KEYS = {
    "hidden_size": "d_model", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "resolved_head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps", "tie_word_embeddings": "tie_embeddings",
}


class _Compiles:
    """JAX's backend-compile events (a persistent-cache hit records its
    retrieval as one), timestamped. One listener per process; runs add and
    remove their own sink."""
    sinks: List[list] = []
    _registered = False

    @classmethod
    def open(cls) -> list:
        import jax
        if not cls._registered:
            def on(event, duration, fun_name="", **_):
                if event == D.COMPILE_EVENT:
                    now = time.perf_counter()
                    for s in cls.sinks:
                        s.append((now, fun_name))
            jax.monitoring.register_event_duration_secs_listener(on)
            cls._registered = True
        sink: list = []
        cls.sinks.append(sink)
        return sink

    @classmethod
    def close(cls, sink: list) -> None:
        cls.sinks.remove(sink)


def prng_key(seed: int):
    """A key for any whole seed, wider than 32 bits too."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def register_variant(cfgj: dict) -> None:
    """Register the configuration's variant with the program's registry
    when the program has none (`"register"` in the config file)."""
    reg = cfgj["serve"].get("register")
    if not reg:
        return
    import importlib
    from repro.config.registry import get_config, register
    arch, variant = cfgj["serve"]["arch"], cfgj["serve"]["variant"]
    try:
        get_config(arch, variant)
        return
    except KeyError:
        pass
    mod = importlib.import_module(reg["module"])
    base = getattr(mod, reg["from"])
    changes = dict(reg["replace"])

    def made():
        return dataclasses.replace(base(), **changes)

    register(arch, mod.full, mod.reduced, **{variant: made})


def check_widths(cfgj: dict, mcfg) -> None:
    for key, attr in MODEL_KEYS.items():
        want, got = cfgj[key], getattr(mcfg, attr)
        if (float(want) if isinstance(want, (int, float)) else want) != \
                (float(got) if isinstance(got, (int, float)) else got):
            raise RuntimeError(f"config {key}={want!r} but the program "
                               f"serves {attr}={got!r}")


def warm_eager(eng, vocab: int) -> None:
    """Run, on stand-in arrays of the same shapes and types, the eager
    operations the engine does between its jitted steps: slicing and
    sampling a decode batch of each size, splicing a pending token into a
    bucket, picking a prefill chunk's first token, and clearing the
    positions of k freed blocks, for each k a request of this mix can
    hold. Each compiles once per shape, and would otherwise do so inside
    the window."""
    import jax
    import jax.numpy as jnp
    from repro.serving.sampling import sample
    key = jax.random.PRNGKey(0)
    key, sk = jax.random.split(key)
    lo = 0
    for b in eng.buckets:
        logits = jnp.zeros((b, vocab), jnp.float32)
        tok = jnp.argmax(logits[0])
        tt = jnp.zeros((b,), jnp.int32).at[0].set(tok)
        for n in range(lo + 1, b + 1):
            toks = sample(logits[:n], sk, eng.temperature)
            jax.block_until_ready(toks[n - 1])
        jax.block_until_ready(tt)
        lo = b
    for g in range(1, eng.n_lanes + 1):
        for t in range(1, eng.prefill_chunk + 1):
            logits = jnp.zeros((g, t, vocab), jnp.float32)
            jax.block_until_ready(jnp.array([[0] * t] * g, jnp.int32))
            for i in range(g):
                jax.block_until_ready(jnp.argmax(logits[i][t - 1]))


def warm_release(eng, counts) -> None:
    """Clearing the positions of a finished request's k freed blocks
    compiles nine small programs for each new k. A request of P prompt
    tokens that served E tokens frees ceil((P + E) / block_size) blocks;
    warm those counts for the requests that can finish in this run (all
    k up to the table's width would be thousands of programs)."""
    import jax
    import jax.numpy as jnp
    pos = jnp.zeros(eng.cache["pos"].shape, eng.cache["pos"].dtype)
    for k in counts:
        jax.block_until_ready(
            pos.at[jnp.asarray(list(range(k)), jnp.int32)].set(-1))


def log(t_start: float, msg: str) -> None:
    """A progress line on standard error, seconds since process start."""
    print(f"bench {time.perf_counter() - t_start:8.2f}s {msg}",
          file=sys.stderr, flush=True)


class CellRun:
    def __init__(self, spec: Spec, name: str, seed: int, seconds: float,
                 trace: bool, t_start: float, peak: Dict[str, float]):
        self.spec = spec
        self.name = name
        self.cell = spec.cell(name)
        self.cfgj = spec.config_file(self.cell["config"])
        self.mix = spec.traffic_file(self.cell["traffic"])
        self.limits = spec.cell_file(name)["limits"]
        self.ref = spec.reference(self.cfgj["reference"])
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.peak = peak
        self.rec = D.Record(cell=name, config=self.cfgj,
                            dims=self.ref.dims(self.cfgj), mix=self.mix,
                            t_start=t_start, peak=peak)
        self.trace_dir = str(spec.bench / ".out" / "trace")
        self._tracing = None

    # -- set-up ------------------------------------------------------------------
    def log(self, msg: str) -> None:
        log(self.rec.t_start, msg)

    def setup(self) -> None:
        self.compiles = _Compiles.open()
        self.log(f"set-up of {self.name}, seed {self.seed}")
        register_variant(self.cfgj)
        from repro.launch.serve import build_engine, build_parser
        sv = self.cfgj["serve"]
        argv = ["--arch", sv["arch"], "--variant", sv["variant"]] \
            + sv["flags"] + self.mix.get("serve_flags", [])
        self.pargs = build_parser().parse_args(argv)
        self.eng, mcfg = build_engine(self.pargs)
        check_widths(self.cfgj, mcfg)
        self.rec.pool_tokens = int(self.eng.mem.eta)
        self.log(f"engine built ({len(self.compiles)} programs)")
        self.install_weights(self.seed)
        self.log("weights made")
        self.make_streams()
        self.warm()
        self.log(f"warm ({len(self.compiles)} programs)")

    def install_weights(self, seed: int) -> None:
        """Swap the program's parameters for weights drawn from `seed` by
        the reference's own maker: same tree, same shapes and type."""
        import jax
        flat, tree = jax.tree_util.tree_flatten_with_path(self.eng.params)
        paths = ["/".join(str(getattr(k, "key", k)) for k in p)
                 for p, _ in flat]
        lay = self.ref.layout(self.cfgj)
        got = {p: tuple(v.shape) for p, (_, v) in zip(paths, flat)}
        want = {p: tuple(s) for p, (s, _) in lay.items()}
        if got != want:
            raise RuntimeError(f"parameter tree differs from the "
                               f"reference's layout: {got} vs {want}")
        dtype = flat[0][1].dtype
        for _, v in flat:
            v.delete()
        self.eng.params = None
        self.weights = None
        self.weights = self.ref.make_weights(self.cfgj, prng_key(seed), dtype)
        self.eng.params = jax.tree_util.tree_unflatten(
            tree, [self.weights[p] for p in paths])

    def make_streams(self) -> None:
        """The requests this run can send: the ramp's, the window's and
        the drain's for an open loop, the backlog's in its order."""
        mix = self.mix
        if mix["arrivals"]["process"] == "backlog":
            self.streams = {"backlog": traffic.stream(
                mix, mix["population"], traffic.WINDOW)}
        else:
            ramp, win, drain = traffic.open_loop(mix, self.seconds)
            self.streams = {"ramp": ramp, "window": win, "drain": drain}

    def finishing(self) -> List[traffic.Req]:
        """Requests that can finish in this run: an open loop's due before
        the drain ends, a backlog's first `warm_requests` in its order."""
        mix = self.mix
        if "backlog" in self.streams:
            return self.streams["backlog"][:mix["warm_requests"]]
        return [q for part in self.streams.values() for q in part]

    def warm(self) -> None:
        """Every shape the window can use, before it opens: the program's
        `warmup()`; the prefill tail shapes it leaves out (one graph per
        tail length and lane group), through the engine's own jitted paged
        prefill; and the small eager operations the engine runs between
        its steps, which compile once per shape (see `warm_eager`)."""
        import jax
        import jax.numpy as jnp
        eng = self.eng
        eng.warmup()
        fn = getattr(eng, "_prefill_paged_jit", None)
        if eng.paged and fn:
            for g in range(1, eng.n_lanes + 1):
                for t in range(1, eng.prefill_chunk):
                    tt = jnp.zeros((g, t), jnp.int32)
                    pos = jnp.full((g, t), -1, jnp.int32)
                    tables = jnp.full((g, eng.max_blocks), -1, jnp.int32)
                    rows = jnp.full((g,), eng.n_slots, jnp.int32)
                    logits, eng.cache = fn(eng.params, tt, pos, tables,
                                           rows, eng.cache, None)
                    jax.block_until_ready(logits)
        warm_eager(eng, self.cfgj["vocab_size"])
        if eng.paged and "pos" in eng.cache:
            bs, ctx = eng.serve.block_size, eng.max_context
            counts = set()
            for q in self.finishing():
                out = max(1, min(q.output_len, ctx - q.prompt_len - 1))
                counts.add(-(-(q.prompt_len + out) // bs))
            warm_release(eng, sorted(counts))

    # -- the window --------------------------------------------------------------
    def serve(self) -> D.Record:
        seconds, mix, eng = self.seconds, self.mix, self.eng
        drv = D.Driver(eng, self.rec, self.seed, self.cfgj["vocab_size"],
                       eng.max_context,
                       trace_cb=self._trace_cb if self.trace else None)
        self.driver = drv
        if mix["arrivals"]["process"] == "backlog":
            drv.backlog(self.streams["backlog"], mix["min_waiting"],
                        mix["ramp"], seconds)
        else:
            drv.open_loop(self.streams["ramp"], self.streams["window"],
                          self.streams["drain"], time.perf_counter(),
                          mix["ramp_seconds"], seconds, mix["drain_limit_s"])
        if self._tracing is not None:
            self._stop_trace()
        self.rec.compiles = list(self.compiles)
        r = self.rec
        self.log(f"window {r.w0 - r.t_start:.2f}-{r.w1 - r.t_start:.2f}s, "
                 f"stop {r.stop - r.t_start:.2f}s, {len(r.judged())} judged,"
                 f" {len(r.steps)} steps, {len(r.compiles)} programs")
        return self.rec

    def _trace_cb(self, drv: D.Driver, now: float) -> None:
        """Start the trace for the window's last `TRACE_SECONDS` (its last
        three quarters where it is shorter). It runs on to the end of the
        drain and is stopped and reduced only after `serve()`'s loop has
        returned, so that neither stalls a request the window judges."""
        rec = self.rec
        lead = max(self.seconds - TRACE_SECONDS, self.seconds / 4)
        if self._tracing is None and rec.w0 > 0 and not rec.trace \
                and not rec.w1 and now >= rec.w0 + lead:
            import jax
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = now
            drv.count_work = True

    def _stop_trace(self) -> None:
        import jax
        from bench.harness import trace as T
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.driver.count_work = False
        self._tracing = None
        self.rec.trace = T.reduce(T.find_xplane(self.trace_dir))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        span = self.rec.trace.window_s if self.rec.trace else 0.0
        self.log(f"trace of {span:.2f}s stopped and reduced in "
                 f"{time.perf_counter() - t0:.2f}s")

    # -- after the window ------------------------------------------------------------
    def memory_peak(self) -> int:
        import jax
        peaks = []
        for d in jax.devices()[:self.cell["chips"]]:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)

    def free_engine(self) -> None:
        """Free the engine's KV pool before the reference runs (the
        weights stay: the reference reads them)."""
        import jax
        for a in jax.tree.leaves(self.eng.cache):
            a.delete()
        self.eng.cache = None
        self.eng.params = None

    def close(self) -> None:
        """Drop every device array this run holds."""
        import jax
        if getattr(self, "compiles", None) is not None:
            _Compiles.close(self.compiles)
            self.compiles = None
        for a in jax.tree.leaves(getattr(self, "weights", None) or {}):
            a.delete()
        self.weights = None
        if getattr(self, "eng", None) is not None:
            if self.eng.cache is not None:
                self.free_engine()
            self.eng = None

    def check(self, control: bool = False) -> Dict[str, Any]:
        """Compare a sample of the finished requests, drawn from the seed
        and holding the one with the most served tokens, with the
        reference. With `control`, the float8 control stands in the
        program's place: at each position of the same prompts and served
        tokens, the gap of the token it puts first goes through the same
        rule (the program's own gap is kept as a reading)."""
        import numpy as np
        done = [c for c in self.rec.clients if c.complete and c.expected > 0]
        short = [c for c in self.rec.clients
                 if c.done and not c.failed and len(c.times) != c.expected]
        k = self.mix["check"]["requests"]
        sample = []
        if done:
            longest = max(done, key=lambda c: (c.expected, -c.req.idx))
            rest = [c for c in done if c is not longest]
            rng = np.random.default_rng([self.seed, 3])
            pick = rng.permutation(len(rest))[:k - 1]
            sample = [longest] + [rest[i] for i in sorted(pick)]
        gaps, ctl, served = [], [], 0
        self.log(f"reference over {len(sample)} requests")
        for c in sample:
            out = [int(t) for t in c.r.output_tokens[:c.expected]]
            gs, gc = self.ref.served_gaps(self.weights, self.cfgj,
                                          c.r.prompt_tokens, out, control)
            gaps.append(float(gs.max()))
            if control:
                ctl.append(float(gc.max()))
            served += len(out)
        self.log("reference done")
        program_gap = max(gaps) if gaps else None
        gap = (max(ctl) if ctl else None) if control else program_gap
        checks = {
            "logit_gap": {"value": gap, "limit": self.limits["logit_gap"],
                          "rule": "<="},
            "requests_checked": {"value": len(sample), "limit": k,
                                 "rule": ">="},
            "short_requests": {"value": len(short), "limit": 0,
                               "rule": "<="},
        }
        ok = gap is not None and gap <= self.limits["logit_gap"] \
            and len(sample) == k and not short
        return {"correct": bool(ok), "checks": checks,
                "program_gap": program_gap, "served_checked": served}
