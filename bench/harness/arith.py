"""Operations and bytes the served work needs, from a configuration's
sizes and the shapes of each step. What the program happens to compute
beyond this (padding rows, logits it throws away, the walk over unused
block-table slots) is not counted, so a share of a peak built on these
numbers cannot pass 100% unless the time leaves out work."""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def matmul_params_per_layer(z: Dict[str, int]) -> int:
    """Weights of one layer's linear maps (q, k, v, o and the SwiGLU)."""
    d, H, KV, hd, f = z["d"], z["H"], z["KV"], z["hd"], z["f"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f


def token_flops(z: Dict[str, int], keys: int, logits: bool) -> int:
    """FLOPs one token needs through all layers: the linear maps,
    attention over `keys` live positions (its own included), and the LM
    head when its logits are used."""
    per_layer = 2 * matmul_params_per_layer(z) \
        + 4 * z["H"] * z["hd"] * keys
    return z["L"] * per_layer + (2 * z["d"] * z["V"] if logits else 0)


def decode_kernel_bytes(z: Dict[str, int], keys: Iterable[int]) -> int:
    """Bytes one call of the paged decode attention kernel (one layer)
    needs for a batch whose live rows attend over `keys` positions each:
    their live K and V, plus each row's q and output."""
    keys = list(keys)
    kv = sum(keys) * 2 * z["KV"] * z["hd"] * BF16
    qo = len(keys) * 2 * z["H"] * z["hd"] * BF16
    return kv + qo


def decode_kernel_flops(z: Dict[str, int], keys: Iterable[int]) -> int:
    """FLOPs of the same call: q.k and p.v over the live keys."""
    return sum(4 * z["H"] * z["hd"] * k for k in keys)


def roofline_seconds(flops: float, nbytes: float, peak: Dict[str, float]):
    """(least seconds the chip could take, which bound sets it)."""
    tc = flops / peak["bf16_flops"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tm, "memory") if tm >= tc else (tc, "compute")
