"""Operations and bytes the served work of an MLA + held-expert MoE
configuration needs (`references/mla_moe.dims` sizes), in the absorbed
form the program serves. As in `arith`, what the program computes beyond
this is not counted: the dense pass of each held expert over rows that did
not choose it, padding rows, logits it throws away, the walk over unused
block-table slots."""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def latent_width(z: Dict[str, int]) -> int:
    """[c ; k_rope] per token per layer."""
    return z["kl"] + z["rope"]


def mla_flops(z: Dict[str, int], keys: int) -> int:
    """One token's MLA in one layer, absorbed: the q and kv projections,
    q_nope W_uk, scores over `keys` latents (C wide) and the weighted sum
    of their c (kv_lora wide), W_uv and W_o."""
    d, H, ql, kl = z["d"], z["H"], z["ql"], z["kl"]
    C = latent_width(z)
    proj = d * ql + ql * H * (z["nope"] + z["rope"]) + d * C \
        + H * z["nope"] * kl + H * kl * z["vd"] + H * z["vd"] * d
    return 2 * proj + 2 * H * (C + kl) * keys


def token_flops(z: Dict[str, int], keys: int) -> int:
    """One token through every layer, without its routed experts: MLA,
    the dense layers' SwiGLU, and each MoE layer's router and shared
    expert."""
    d, Ld, L = z["d"], z["Ld"], z["L"]
    return L * mla_flops(z, keys) + Ld * 6 * d * z["f"] \
        + (L - Ld) * (2 * d * z["E"] + 6 * d * z["shared"])


def expert_flops(z: Dict[str, int], selections: float) -> float:
    """Routed work of `selections` token-expert selections on held
    experts (SwiGLU of width fe each)."""
    return selections * 6 * z["d"] * z["fe"]


def latent_decode_bytes(z: Dict[str, int], keys: Iterable[int]) -> int:
    """Bytes one call of the latent decode kernel (one layer) needs for a
    batch whose live rows attend over `keys` positions each: each live
    key's [c ; k_rope] once, plus each row's absorbed q (H x C) and its
    output (H x kv_lora)."""
    keys = list(keys)
    C = latent_width(z)
    return sum(keys) * C * BF16 + len(keys) * z["H"] * (C + z["kl"]) * BF16


def latent_decode_flops(z: Dict[str, int], keys: Iterable[int]) -> int:
    """FLOPs of the same call: scores over C and the sum over kv_lora."""
    return sum(2 * z["H"] * (latent_width(z) + z["kl"]) * k for k in keys)
