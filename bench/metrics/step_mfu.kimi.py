"""Share of the chip's bf16 peak that the FLOPs the work needs take of
the device time of the step programs (prefill and decode), over the traced
part of the window, for an MLA + held-expert MoE configuration. Needed: MLA
in the absorbed form served and latent attention over each token's live
keys, the dense layers, each MoE layer's router and shared expert, the
routed work of held-expert selections only (the counter's mean held
selections per token times the traced tokens; the dense pass over rows
that did not choose an expert does not count), and the LM head where
logits are used."""
from bench.harness import arith_mla_moe as A
from bench.metrics._held import per_token


def read(rec):
    t = rec.trace
    sel = per_token(rec)
    if t is None or sel is None:
        return None
    sec = t.program_seconds("prefill") + t.program_seconds("decode")
    z = rec.dims
    flops, tokens = 0, 0
    for s in rec.trace_steps:
        for k in s.prefill_keys + s.decode_keys:
            flops += A.token_flops(z, k)
        tokens += len(s.prefill_keys) + len(s.decode_keys)
        flops += s.logit_rows * 2 * z["d"] * z["V"]
    flops += A.expert_flops(z, sel * tokens)
    if not sec or not flops:
        return None
    return 100.0 * flops / (sec * rec.peak["bf16_flops"])
