"""jaxpr trace auditor (DESIGN §13): jit each model family's serving steps
on tiny reduced configs and inspect the closed jaxpr.

Three audits per family config in `src/repro/configs/`:

* **dtype**: no float64/complex128 value anywhere in the traced serving
  step (inputs, constants, any equation output, recursively through
  sub-jaxprs). A stray f64 literal silently doubles KV bytes-per-token and
  halves every MemoryModel budget the scheduler trusts.
* **callback**: no `pure_callback` / `io_callback` / `debug_callback`
  primitive inside a jitted serving step — a callback is a hidden
  host-device sync point the host-sync lint cannot see (it hides behind
  jit), and the async dispatch-ahead loop (ROADMAP) cannot overlap it.
* **recompile**: tracing the decode step across the compiled
  `batch_buckets` shapes retraces exactly once per bucket — a step
  function that closes over drifting Python state retraces per call and
  turns every scheduling interval into a compile.

Imports jax lazily: the AST rules must stay importable (and fast) without
an accelerator stack.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

from repro.analysis.framework import Finding

#: callback primitives banned inside jitted serving steps
CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback"}

#: dtypes banned anywhere in a serving-step jaxpr
BAD_DTYPES = {"float64", "complex128"}


def _sub_jaxprs(v) -> Iterable:
    """Jaxprs nested inside an eqn param (closed or open, possibly lists)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _sub_jaxprs(item)


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _audit_closed(closed, step: str, path: str) -> List[Finding]:
    """dtype + callback audit of one closed jaxpr."""
    out: List[Finding] = []
    seen_dtypes = set()
    for v in list(closed.jaxpr.invars) + list(closed.jaxpr.constvars):
        dt = getattr(v.aval, "dtype", None)
        if dt is not None and str(dt) in BAD_DTYPES:
            seen_dtypes.add(str(dt))
    callbacks = set()
    for eqn in _iter_eqns(closed.jaxpr):
        if eqn.primitive.name in CALLBACK_PRIMS:
            callbacks.add(eqn.primitive.name)
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and str(dt) in BAD_DTYPES:
                seen_dtypes.add(str(dt))
    for dt in sorted(seen_dtypes):
        out.append(Finding(
            "jaxpr-audit", path, 1,
            f"{step}: {dt} value in the traced serving step — double-width "
            f"math silently breaks every MemoryModel byte budget"))
    for cb in sorted(callbacks):
        out.append(Finding(
            "jaxpr-audit", path, 1,
            f"{step}: {cb} primitive inside a jitted serving step — a "
            f"hidden host sync the async engine loop cannot overlap"))
    return out


def audit_arch(arch: str, buckets: Sequence[int] = (1, 2),
               max_context: int = 32, prefill_chunk: int = 8,
               recompile: bool = True) -> List[Finding]:
    """Run the full audit for one registry arch (reduced variant)."""
    import jax
    import jax.numpy as jnp

    from repro.config.registry import _ARCH_MODULES, get_config
    from repro.models.model import build_model, default_enc_len

    path = f"src/repro/configs/{_ARCH_MODULES[arch]}.py"
    cfg = get_config(arch, "reduced")
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    out: List[Finding] = []

    # decode step: one token against a max_context cache
    b = max(buckets)
    cache = model.init_cache(b, max_context)
    toks = jnp.zeros((b,), jnp.int32)
    lens = jnp.full((b,), -1, jnp.int32)
    closed = jax.make_jaxpr(model.decode_step)(params, toks, lens, cache)
    out.extend(_audit_closed(closed, f"{arch} decode_step", path))

    # chunked prefill (the engine's per-lane graph shape)
    T = prefill_chunk
    pcache = model.init_cache(1, max_context, prefill_chunk=T)
    tt = jnp.zeros((1, T), jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    enc_len = default_enc_len(cfg)
    extras = None
    if enc_len:
        key = "enc_frames" if cfg.family.value == "encdec" else "images"
        extras = {key: jnp.zeros((1, enc_len, cfg.d_model), jnp.float32)}
    closed = jax.make_jaxpr(
        lambda p, t, q, c: model.prefill(p, t, q, c, extras))(
            params, tt, pos, pcache)
    out.extend(_audit_closed(closed, f"{arch} prefill", path))

    # paged decode step (DESIGN §9): pools + block tables
    block_size = 16
    max_blocks = -(-max_context // block_size)
    num_blocks = b * max_blocks
    pgcache = model.init_paged_cache(b, num_blocks, block_size)
    tables = jnp.full((b, max_blocks), -1, jnp.int32)
    closed = jax.make_jaxpr(model.decode_step_paged)(
        params, toks, lens, tables, pgcache)
    out.extend(_audit_closed(closed, f"{arch} decode_step_paged", path))

    if recompile:
        traces = {"n": 0}

        def step(p, t, l, c):
            traces["n"] += 1
            return model.decode_step(p, t, l, c)

        jf = jax.jit(step)
        for bb in buckets:
            bcache = model.init_cache(bb, max_context)
            bt = jnp.zeros((bb,), jnp.int32)
            bl = jnp.full((bb,), -1, jnp.int32)
            for _ in range(2):   # second call must hit the jit cache
                _, bcache = jf(params, bt, bl, bcache)
        if traces["n"] != len(buckets):
            out.append(Finding(
                "jaxpr-audit", path, 1,
                f"{arch} decode_step retraced {traces['n']}x across "
                f"{len(buckets)} batch buckets — expected exactly one "
                f"trace per bucket shape (a retrace per call turns every "
                f"scheduling interval into a compile)"))
    return out


def run_jaxpr_audit(archs: Optional[Sequence[str]] = None,
                    recompile: bool = True) -> List[Finding]:
    from repro.config.registry import list_archs
    out: List[Finding] = []
    for arch in (archs if archs is not None else list_archs()):
        out.extend(audit_arch(arch, recompile=recompile))
    return out
