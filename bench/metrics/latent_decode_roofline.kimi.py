"""Share of its roofline the paged decode kernel's latent mode reaches
over the traced part of the window: the least time the chip needs for the
bytes the calls need (each live key's [c ; k_rope] once, plus each row's
absorbed q and its output; the bound is memory) over the kernel's device
time (`kernel_seconds("paged_decode")`)."""
from bench.harness import arith
from bench.harness import arith_mla_moe as A


def read(rec):
    t = rec.trace
    if t is None:
        return None
    sec = t.kernel_seconds("paged_decode")
    z = rec.dims
    need = flops = 0
    for s in rec.trace_steps:
        if s.decode_keys:
            need += z["L"] * A.latent_decode_bytes(z, s.decode_keys)
            flops += z["L"] * A.latent_decode_flops(z, s.decode_keys)
    if not sec or not need:
        return None
    least, _ = arith.roofline_seconds(flops, need, rec.peak)
    return 100.0 * least / sec
