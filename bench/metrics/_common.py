"""Shared arithmetic of the metric readers (not a metric itself)."""
from __future__ import annotations

import math
from typing import List


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile, q in 0..100, of a non-empty list."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def ttft(c, stop: float) -> float:
    """Seconds from due to first token; a request with none by the end of
    the drain (refused ones too) counts at its wait so far."""
    return (c.times[0] if c.times else stop) - c.due


def gaps_ms(c) -> List[float]:
    return [(b - a) * 1e3 for a, b in zip(c.times, c.times[1:])]


def compiles_in_window(rec) -> float:
    """Programs compiled, or loaded from the persistent cache, inside the
    window (JAX's backend-compile events)."""
    return float(sum(1 for t, _ in rec.compiles if rec.w0 <= t <= rec.w1))


def step_mfu(rec):
    """Share of the chip's bf16 peak that the model's required FLOPs take
    of the device time of the step programs (prefill and decode), over
    the traced part of the window. Required: the linear maps of every
    prompt and decode token, attention over each token's live context,
    and the LM head for the tokens whose logits are used."""
    from bench.harness import arith
    t = rec.trace
    if t is None:
        return None
    sec = t.program_seconds("prefill") + t.program_seconds("decode")
    z = rec.dims
    flops = 0
    for s in rec.trace_steps:
        for k in s.prefill_keys + s.decode_keys:
            flops += arith.token_flops(z, k, logits=False)
        flops += s.logit_rows * 2 * z["d"] * z["V"]
    if not sec or not flops:
        return None
    return 100.0 * flops / (sec * rec.peak["bf16_flops"])


def paged_decode_roofline(rec):
    """Share of its roofline the paged decode attention kernel reaches
    over the traced part of the window: the least time the chip needs for
    the bytes the calls need (each live row's K and V over its context,
    plus q and the output; the bound is memory), over the kernel's device
    time."""
    from bench.harness import arith
    t = rec.trace
    if t is None:
        return None
    sec = t.kernel_seconds("paged_decode")
    z = rec.dims
    need = flops = 0
    for s in rec.trace_steps:
        if s.decode_keys:
            need += z["L"] * arith.decode_kernel_bytes(z, s.decode_keys)
            flops += z["L"] * arith.decode_kernel_flops(z, s.decode_keys)
    if not sec or not need:
        return None
    least, _ = arith.roofline_seconds(flops, need, rec.peak)
    return 100.0 * least / sec
