"""Shared transformer layers: norms, RoPE, attention (with unified ring/full
KV cache), multi-head latent attention (MLA) with yarn RoPE, SwiGLU MLP,
GShard-style dense-dispatch MoE, and the held-expert MoE layer.

Conventions
-----------
* params are nested dicts of jnp arrays; layer stacks carry a leading L axis.
* activations default to the config dtype; softmax/norm accumulate in fp32.
* attention caches store absolute positions per physical slot (`pos`, int32,
  -1 = empty). This unifies full caches and ring-buffer (sliding-window)
  caches: masking is purely position arithmetic, and RoPE is applied at
  absolute positions before the write so ring wrap-around is transparent.
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig


def use_pallas() -> bool:
    """Pallas kernels are the default backend on TPU; REPRO_USE_PALLAS=1
    forces them on CPU (interpret mode — used by the integration tests)."""
    env = os.environ.get("REPRO_USE_PALLAS")
    if env is not None:
        return env == "1"
    return jax.default_backend() == "tpu"

# ---------------------------------------------------------------------------
# init helpers


def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.float32):
    """Scaled normal draw made in `dtype` itself: a bf16 model never holds
    an fp32 copy of a weight, even transiently."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = (1.0 / math.sqrt(fan_in)) if scale is None else scale
    return jax.random.normal(key, shape, dtype) * scale


def rms_norm(x, w, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE


def rope_freqs(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x, positions, theta: float, inv_freq=None,
               mscale: float = 1.0):
    """x: (..., T, H, hd); positions: broadcastable to (..., T).
    inv_freq: (hd/2,) frequencies in place of theta's (yarn); mscale
    scales cos and sin (yarn's attention factor)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta) if inv_freq is None \
        else jnp.asarray(inv_freq)                      # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., T, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core

_NEG_INF = -1e30


def attend(q, k, v, q_pos, k_pos, *, window: int = 0, causal: bool = True,
           k_valid=None, scale: Optional[float] = None):
    """Masked GQA attention.

    q: (B, Tq, H, hd); k: (B, Tk, KV, hd); v: (B, Tk, KV, dv)
    q_pos: (B, Tq) int32 absolute positions of queries
    k_pos: (B, Tk) int32 absolute positions of keys (-1 = empty slot)
    window: if >0, keys older than q_pos - window + 1 are masked
    k_valid: optional (B, Tk) bool extra mask (e.g. encoder padding)
    scale: softmax scale; None = 1/sqrt(hd)
    Returns (B, Tq, H * dv).
    """
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    # fp32 ACCUMULATION without materializing fp32 copies of the KV cache
    # (an .astype(f32) on k/v doubles the decode memory term — §Perf iter C)
    qh = q.reshape(B, Tq, KV, G, hd)
    scores = jnp.einsum("btkgd,bskd->bkgts", qh, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd) if scale is None else scores * scale
    mask = k_pos[:, None, :] >= 0                        # (B, Tq->1?, Tk)
    mask = jnp.broadcast_to(mask, (B, Tq, k.shape[1]))
    if causal:
        mask = mask & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window:
        mask = mask & (k_pos[:, None, :] > q_pos[:, :, None] - window)
    if k_valid is not None:
        mask = mask & k_valid[:, None, :]
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)          # fp32
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Tq, H * v.shape[-1]).astype(q.dtype)


def init_attention(key, cfg: ModelConfig, dtype, *, cross: bool = False):
    d, H, KV = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, KV * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, KV * hd), dtype=dtype),
        "wo": dense_init(ks[3], (H * hd, d),
                         scale=0.02 / math.sqrt(2 * max(cfg.num_layers, 1)),
                         dtype=dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if cross:
        p["gate"] = jnp.zeros((), dtype)   # tanh-gated cross-attn (VLM)
    return p


def attention_qkv(p, x, cfg: ModelConfig):
    B, T, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, T, H, hd), k.reshape(B, T, KV, hd),
            v.reshape(B, T, KV, hd))


def self_attention_train(p, x, positions, cfg: ModelConfig, *, window: int = 0):
    """Full-sequence causal self-attention (no cache)."""
    q, k, v = attention_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    out = attend(q, k, v, positions, positions, window=window, causal=True)
    return out @ p["wo"]


def self_attention_cached(p, x, positions, cache_k, cache_v, cache_pos,
                          cfg: ModelConfig, *, window: int = 0):
    """Self-attention through a (possibly ring) KV cache.

    x: (B, T, d) new tokens at absolute `positions` (B, T).
    cache_k/v: (B, S_phys, KV, hd); cache_pos: (B, S_phys) absolute pos, -1 empty.
    Returns (out, new_cache_k, new_cache_v, new_cache_pos).
    """
    B, T, _ = x.shape
    S = cache_k.shape[1]
    q, k, v = attention_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # ring slot (== pos when S >= ctx); padding (pos < 0) writes out of
    # bounds and is dropped by the scatter
    slots = jnp.where(positions >= 0, positions % S, S)
    bidx = jnp.arange(B)[:, None]
    cache_k = cache_k.at[bidx, slots].set(k, mode="drop")
    cache_v = cache_v.at[bidx, slots].set(v, mode="drop")
    cache_pos = cache_pos.at[bidx, slots].set(positions, mode="drop")
    if T == 1 and use_pallas():
        # flash-decode Pallas kernel (kernels/decode_attention.py)
        from repro.kernels import ops
        out = ops.decode_attention(q[:, 0], cache_k, cache_v,
                                   positions[:, 0], cache_pos, window=window)
        out = out.reshape(B, 1, -1)
    else:
        out = attend(q, cache_k, cache_v, positions, cache_pos,
                     window=window, causal=True)
    return out @ p["wo"], cache_k, cache_v, cache_pos


def _pool_write(pool, flat_slots, val):
    """Scatter per-token values into a paged pool viewed flat over its
    slot axes (DESIGN §9).

    pool: (NB, bs) for the pos map, (L, NB, bs, KV, hd) for a stacked K/V
    pool; val: (B, T) + trailing axes; flat_slots: (B, T) indices into the
    pool's leading axes flattened, with any index past their end marking
    padding/unallocated tokens (dropped)."""
    rest = val.shape[flat_slots.ndim:]
    flat = pool.reshape((-1,) + rest)
    return flat.at[flat_slots].set(val, mode="drop").reshape(pool.shape)


def paged_step_slots(positions, tables, pool_pos, num_layers: int):
    """Each new token's slot in the paged pools, and the pos map with this
    step's positions written (DESIGN §9). Both are the same for every
    layer, so a step makes them once, before its layer loop.

    positions: (B, T) absolute, -1 = padding; tables: (B, MB) physical
    block ids, -1 = unallocated; pool_pos: (NB, bs). A token at position
    p goes to block tables[b, p // bs], offset p % bs: slot
    tables[b, p // bs] * bs + p % bs of one layer's NB*bs slots, and
    layer i's K/V sit i*NB*bs further on in the stacked pool. Padding and
    unallocated blocks get num_layers*NB*bs, past the end of the stacked
    pool at every layer's offset, so their writes drop.
    Returns (slots (B, T), new pool_pos)."""
    NB, bs = pool_pos.shape
    MB = tables.shape[1]
    blk = jnp.clip(positions // bs, 0, MB - 1)
    phys = jnp.take_along_axis(tables, blk, axis=1)            # (B, T)
    ok = (positions >= 0) & (phys >= 0)
    slots = jnp.where(ok, phys * bs + positions % bs, num_layers * NB * bs)
    return slots, _pool_write(pool_pos, slots, positions)


def paged_view(pool_k, pool_v, pool_pos, tables, layer):
    """Gather a per-request contiguous (B, MB*bs) view of the stacked
    paged pools at `layer` (DESIGN §9). Delegates to the canonical block-table gather in
    `kernels.ref` so the production path and the kernel oracle can never
    diverge on layout semantics."""
    from repro.kernels.ref import paged_view as _paged_view
    return _paged_view(pool_k, pool_v, pool_pos, tables, layer)


def self_attention_paged(p, x, positions, k_all, v_all, pool_pos, tables,
                         slots, layer, cfg: ModelConfig, *, window: int = 0):
    """Self-attention of one layer through the stacked paged KV pools,
    read and written in place (DESIGN §9).

    x: (B, T, d) new tokens at absolute `positions` (B, T); k_all/v_all:
    (L, NB, bs, KV, hd) stacked physical pools; pool_pos: (NB, bs) absolute
    positions (-1 = empty), already holding this step's positions; tables:
    (B, MB) per-request physical block ids (-1 = unallocated); slots and
    pool_pos come from `paged_step_slots`; layer: this layer's index into
    the stack (traced inside the layer loop). The new K/V scatter into the
    stack at layer*NB*bs + slot, and attention reads the stack at `layer`:
    no per-layer slice of the pool is ever made.
    Returns (out, new_k_all, new_v_all).
    """
    B, T, _ = x.shape
    NB, bs = pool_pos.shape
    q, k, v = attention_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    at = slots + layer * NB * bs
    k_all = _pool_write(k_all, at, k)
    v_all = _pool_write(v_all, at, v)
    # kernel routing under a serving mesh (DESIGN §12), derived from the
    # SAME rule that placed the pool (`kv_head_axes`): sharded on
    # kv-heads -> shard_map'd TP kernel; sharded on head_dim -> the
    # Pallas custom call cannot partition it (GSPMD would all-gather the
    # whole pool onto every chip), so take the gather-view fallback
    # whose jnp gathers stay sharded; replicated -> the single-device
    # kernel is safe.
    from repro.distributed.sharding import (kv_head_axes, serving_mesh,
                                            serving_model_axis)
    kv_ax = hd_ax = None
    if serving_model_axis() > 1:
        kv_ax, hd_ax = kv_head_axes(serving_mesh(), k_all.shape[-2],
                                    k_all.shape[-1])
    if T == 1 and use_pallas() and hd_ax is None:
        # paged flash-decode Pallas kernel: the kv-block grid axis walks the
        # block table (kernels/decode_attention.py, DESIGN §9)
        from repro.kernels import ops
        if kv_ax is not None:
            out = ops.paged_decode_attention_tp(
                q[:, 0], k_all, v_all, positions[:, 0], pool_pos, tables,
                layer, mesh=serving_mesh(), window=window)
        else:
            out = ops.paged_decode_attention(q[:, 0], k_all, v_all,
                                             positions[:, 0], pool_pos,
                                             tables, layer, window=window)
        out = out.reshape(B, 1, -1)
    else:
        kview, vview, kpos = paged_view(k_all, v_all, pool_pos, tables, layer)
        out = attend(q, kview, vview, positions, kpos,
                     window=window, causal=True)
    return out @ p["wo"], k_all, v_all


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V3 MLA, DESIGN §17)
#
# q = W_qb rms(W_qa x), split per head into nope and rope parts; W_kva x
# gives the latent c (normalised) and one rotary key k_rope shared by every
# head. Every path — training, the contiguous cache and the paged pool —
# runs the absorbed form: the query q_lat = [q_nope W_uk ; rope(q_rope)]
# meets the cached [c ; rope(k_rope)] of each key, the softmax weights the
# c part, and W_uv then W_o map the result back. Rotation is the
# repository's half-split layout: DeepSeek-V3 rotates interleaved pairs, the
# same model with the rope columns of W_qb and W_kva permuted.


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V3 `yarn_get_mscale`."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def mla_rope(cfg: ModelConfig):
    """(inv_freq (rope/2,), cos/sin scale) of MLA's rotary part: plain
    RoPE at rope_theta, or DeepSeek-V3's yarn — frequencies below the
    correction range interpolated by `factor`, above it kept, a linear
    ramp between (`yarn_find_correction_range`, `yarn_linear_ramp_mask`)."""
    dim = cfg.mla.qk_rope_head_dim
    theta = cfg.rope_theta
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = cfg.rope_scaling
    if y is None:
        return base.astype(np.float32), 1.0

    def corr(rot):
        return dim * math.log(y.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = base / y.factor * (1.0 - keep) + base * keep
    return inv.astype(np.float32), \
        yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor,
                                                     y.mscale_all_dim)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-1/2, times yarn_mscale(factor,
    mscale_all_dim)^2 under yarn (DeepSeek-V3's attention)."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def init_mla(key, cfg: ModelConfig, dtype):
    m, d, H = cfg.mla, cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], (d, m.q_lora_rank), dtype=dtype),
        "q_norm": jnp.zeros((m.q_lora_rank,), dtype),
        "wq_b": dense_init(ks[1], (m.q_lora_rank, H * qk), dtype=dtype),
        "wkv_a": dense_init(ks[2], (d, m.latent_dim), dtype=dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "w_uk": dense_init(ks[3], (m.kv_lora_rank, H * m.qk_nope_head_dim),
                           dtype=dtype),
        "w_uv": dense_init(ks[4], (m.kv_lora_rank, H * m.v_head_dim),
                           dtype=dtype),
        "wo": dense_init(ks[5], (H * m.v_head_dim, d),
                         scale=0.02 / math.sqrt(2 * max(cfg.num_layers, 1)),
                         dtype=dtype),
    }


def mla_qkv(p, x, positions, cfg: ModelConfig):
    """x: (B, T, d) at `positions` (B, T) -> (q_lat (B, T, H, C), the
    tokens' latents (B, T, C)), C = `latent_lanes`: the absorbed queries
    [q_nope W_uk ; rope(q_rope) ; 0] and [rms(c) ; rope(k_rope) ; 0], the
    zeros padding kv_lora + qk_rope to whole lane tiles (they add nothing
    to a score)."""
    m = cfg.mla
    B, T, _ = x.shape
    H, nope, c = cfg.num_heads, m.qk_nope_head_dim, m.kv_lora_rank
    q = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.rms_eps) @ p["wq_b"]
    q = q.reshape(B, T, H, nope + m.qk_rope_head_dim)
    kv = x @ p["wkv_a"]
    lat = rms_norm(kv[..., :c], p["kv_norm"], cfg.rms_eps)
    inv, ms = mla_rope(cfg)
    q_pe = apply_rope(q[..., nope:], positions, cfg.rope_theta, inv, ms)
    k_pe = apply_rope(kv[..., None, c:], positions, cfg.rope_theta, inv,
                      ms)[..., 0, :]
    q_lat = jnp.einsum("bthn,chn->bthc", q[..., :nope],
                       p["w_uk"].reshape(c, H, nope))
    pad = m.latent_lanes - m.latent_dim
    return (jnp.concatenate([q_lat, q_pe, jnp.zeros(q_pe.shape[:-1] + (pad,),
                                                    q_pe.dtype)], axis=-1),
            jnp.concatenate([lat, k_pe, jnp.zeros(k_pe.shape[:-1] + (pad,),
                                                  k_pe.dtype)], axis=-1))


def mla_attend(q_lat, kv, q_pos, k_pos, cfg: ModelConfig):
    """Causal latent attention: q_lat (B, Tq, H, C) against one shared
    latent key kv (B, Tk, C) per position, its first kv_lora lanes the
    values. Returns (B, Tq, H, kv_lora)."""
    c = cfg.mla.kv_lora_rank
    out = attend(q_lat, kv[:, :, None, :], kv[:, :, None, :c], q_pos, k_pos,
                 causal=True, scale=mla_softmax_scale(cfg))
    return out.reshape(q_lat.shape[:3] + (c,))


def mla_out(p, o_lat, cfg: ModelConfig):
    """(B, T, H, kv_lora) attention over the latents -> (B, T, d): each
    head's W_uv, then W_o."""
    B, T, H, c = o_lat.shape
    v = jnp.einsum("bthc,chv->bthv", o_lat,
                   p["w_uv"].reshape(c, H, cfg.mla.v_head_dim))
    return v.reshape(B, T, -1) @ p["wo"]


def mla_train(p, x, positions, cfg: ModelConfig):
    """Full-sequence causal MLA (no cache)."""
    q_lat, kv = mla_qkv(p, x, positions, cfg)
    return mla_out(p, mla_attend(q_lat, kv, positions, positions, cfg), cfg)


def mla_cached(p, x, positions, cache_kv, cache_pos, cfg: ModelConfig):
    """MLA through a contiguous latent cache: cache_kv (B, S, C), cache_pos
    (B, S). Returns (out, new cache_kv, new cache_pos)."""
    S = cache_kv.shape[1]
    q_lat, kv = mla_qkv(p, x, positions, cfg)
    slots = jnp.where(positions >= 0, positions % S, S)
    bidx = jnp.arange(x.shape[0])[:, None]
    cache_kv = cache_kv.at[bidx, slots].set(kv, mode="drop")
    cache_pos = cache_pos.at[bidx, slots].set(positions, mode="drop")
    o = mla_attend(q_lat, cache_kv, positions, cache_pos, cfg)
    return mla_out(p, o, cfg), cache_kv, cache_pos


def mla_paged(p, x, positions, kv_all, pool_pos, tables, slots, layer,
              cfg: ModelConfig):
    """MLA of one layer through the stacked latent pool (L, NB, bs, C),
    C = `latent_lanes`,
    read and written in place (DESIGN §9, §17): [c ; k_rope] once per
    token per layer, no V pool. Decode runs the paged kernel's latent mode
    (one C-wide head shared by every absorbed query head, V the first
    kv_lora lanes of the same block); prefill chunks attend over the
    block-table view. Returns (out, new kv_all)."""
    NB, bs = pool_pos.shape
    q_lat, kv = mla_qkv(p, x, positions, cfg)
    kv_all = _pool_write(kv_all, slots + layer * NB * bs, kv)
    c = cfg.mla.kv_lora_rank
    if x.shape[1] == 1 and use_pallas():
        from repro.kernels import ops
        o = ops.paged_decode_attention(
            q_lat[:, 0], kv_all, None, positions[:, 0], pool_pos, tables,
            layer, scale=mla_softmax_scale(cfg), latent_v=c)[:, None]
    else:
        kview, _, kpos = paged_view(kv_all, None, pool_pos, tables, layer)
        o = mla_attend(q_lat, kview, positions, kpos, cfg)
    return mla_out(p, o, cfg), kv_all


def cross_attention(p, x, kv_k, kv_v, k_valid, cfg: ModelConfig, *,
                    gated: bool = False):
    """Cross-attention to fixed encoder/image keys (precomputed, no RoPE)."""
    B, T, _ = x.shape
    H, hd = cfg.num_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, T, H, hd)
    Tk = kv_k.shape[1]
    zeros_q = jnp.zeros((B, T), jnp.int32)
    k_pos = jnp.zeros((B, Tk), jnp.int32)
    out = attend(q, kv_k, kv_v, zeros_q, k_pos, causal=False, k_valid=k_valid)
    out = out @ p["wo"]
    if gated:
        out = jnp.tanh(p["gate"].astype(jnp.float32)).astype(out.dtype) * out
    return out


def cross_kv(p, enc_out, cfg: ModelConfig):
    B, S, _ = enc_out.shape
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = (enc_out @ p["wk"]).reshape(B, S, KV, hd)
    v = (enc_out @ p["wv"]).reshape(B, S, KV, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLP (SwiGLU)


def init_mlp(key, d: int, f: int, num_layers: int, dtype):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, f), dtype=dtype),
        "w_up": dense_init(ks[1], (d, f), dtype=dtype),
        "w_down": dense_init(ks[2], (f, d),
                             scale=0.02 / math.sqrt(2 * max(num_layers, 1)),
                             dtype=dtype),
    }


def mlp(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE (GShard-style dense dispatch — TPU friendly, no dynamic scatter)


def init_moe(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    m = cfg.moe
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.num_experts), scale=0.02, dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (m.num_experts, d, m.expert_ff_dim), dtype=dtype),
        "w_up": dense_init(ks[2], (m.num_experts, d, m.expert_ff_dim), dtype=dtype),
        "w_down": dense_init(ks[3], (m.num_experts, m.expert_ff_dim, d),
                             scale=0.02 / math.sqrt(2 * cfg.num_layers), dtype=dtype),
    }
    if m.shared_ff_dim:
        p["shared"] = init_mlp(ks[4], d, m.shared_ff_dim, cfg.num_layers, dtype)
    return p


MOE_GROUP = 128  # tokens per dispatch group (GShard 'S'); bounds capacity mem


def moe_capacity(group: int, cfg: ModelConfig, no_drop: bool) -> int:
    m = cfg.moe
    if no_drop:
        return group  # worst case: every token in the group picks expert e
    c = int(math.ceil(m.num_experts_per_tok * group * m.capacity_factor
                      / m.num_experts))
    return max(c, 1)


def moe_apply(p, x, cfg: ModelConfig, *, no_drop: bool = False,
              group_size: int = MOE_GROUP):
    """x: (B, T, d) -> (y, aux_loss).

    GShard-style dense einsum dispatch over token groups of `group_size`
    (keeps the (G, E, C) dispatch tensor bounded regardless of sequence
    length). `no_drop=True` sets capacity to the exact worst case — used by
    the serving engine so chunked prefill / decode are bitwise consistent
    with the full forward pass.
    """
    B, T, d = x.shape
    m = cfg.moe
    E, K = m.num_experts, m.num_experts_per_tok

    S = B * T
    G = min(group_size, S)
    pad = (-S) % G
    xf = x.reshape(S, d)
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, d), x.dtype)], axis=0)
    nG = (S + pad) // G
    xg = xf.reshape(nG, G, d)
    C = moe_capacity(G, cfg, no_drop)

    logits = (xg.astype(jnp.float32) @ p["router"])       # (nG,G,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                   # (nG,G,K)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) selection inside its expert's queue;
    # earlier k-choices get priority (GShard semantics)
    counts = jnp.zeros((nG, E), jnp.int32)
    dispatch = jnp.zeros((nG, G, E, C), jnp.bool_)
    combine = jnp.zeros((nG, G, E, C), jnp.float32)
    for j in range(K):
        oh = jax.nn.one_hot(idx[:, :, j], E, dtype=jnp.int32)      # (nG,G,E)
        pos = jnp.cumsum(oh, axis=1) - 1 + counts[:, None, :]      # (nG,G,E)
        keep = (pos < C) & (oh > 0)
        pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]
        dispatch = dispatch | (pos_oh > 0)
        combine = combine + pos_oh * gate[:, :, j, None, None]
        counts = counts + oh.sum(axis=1)

    xin = jnp.einsum("gtec,gtd->gecd", dispatch.astype(x.dtype), xg)
    h = jnp.einsum("gecd,edf->gecf", xin, p["w_gate"])
    h = jax.nn.silu(h) * jnp.einsum("gecd,edf->gecf", xin, p["w_up"])
    out_e = jnp.einsum("gecf,efd->gecd", h, p["w_down"])            # (nG,E,C,d)
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(x.dtype), out_e)

    y = y.reshape(nG * G, d)
    if pad:
        y = y[:S]
    y = y.reshape(B, T, d)

    if "shared" in p:
        y = y + mlp(p["shared"], x)

    # load-balance aux loss (Switch/GShard)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx[:, :, 0], E, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs) * m.router_aux_loss_coef
    return y, aux


# ---------------------------------------------------------------------------
# Held-expert MoE (DeepSeek-V3 routing, expert parallel; DESIGN §17)


def init_held_moe(key, cfg: ModelConfig, dtype):
    """Router over all num_experts (and its selection bias), the
    `held` routed experts this chip holds, the shared expert."""
    d, m = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 5)
    scale = 0.02 / math.sqrt(2 * cfg.num_layers)
    return {
        "router": dense_init(ks[0], (d, m.num_experts), scale=0.02,
                             dtype=dtype),
        "router_bias": jnp.zeros((m.num_experts,), dtype),
        "w_gate": dense_init(ks[1], (m.held, d, m.expert_ff_dim),
                             dtype=dtype),
        "w_up": dense_init(ks[2], (m.held, d, m.expert_ff_dim), dtype=dtype),
        "w_down": dense_init(ks[3], (m.held, m.expert_ff_dim, d),
                             scale=scale, dtype=dtype),
        "shared": init_mlp(ks[4], d, m.shared_ff_dim, cfg.num_layers, dtype),
    }


def held_moe_apply(p, x, cfg: ModelConfig, valid=None):
    """x: (B, T, d) -> (y, counts).

    DeepSeek-V3 noaux_tc routing over all num_experts (one group): fp32
    sigmoid scores, the top-k of score + selection bias, gates the
    unbiased scores of the chosen experts, normalised (norm_topk_prob,
    as every such model sets it) and times routed_scale. Only the held experts [first, first + held)
    are computed: a dense pass of each over every row, its gate 0 where
    the row did not choose it (a decode step is weight-bound at these
    rows; no (G, E, C) dispatch tensor is built). The shared expert is
    added once. `counts` (B, held) int32: each row's selections that
    landed on each held expert, over its `valid` (B, T) tokens."""
    m = cfg.moe
    f32 = jnp.float32
    scores = jax.nn.sigmoid(x.astype(f32) @ p["router"].astype(f32))
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(f32),
                           m.num_experts_per_tok)              # (B, T, K)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20) * m.routed_scale
    hit = (idx - m.first_held_expert)[..., None] \
        == jnp.arange(m.held)                                  # (B,T,K,held)
    g = jnp.sum(jnp.where(hit, gate[..., None], 0.0), axis=2)  # (B,T,held)
    a = jnp.einsum("btd,edf->btef", x, p["w_gate"],
                   preferred_element_type=f32)
    b = jnp.einsum("btd,edf->btef", x, p["w_up"],
                   preferred_element_type=f32)
    h = (jax.nn.silu(a) * b * g[..., None]).astype(x.dtype)
    y = jnp.einsum("btef,efd->btd", h, p["w_down"]) + mlp(p["shared"], x)
    if valid is None:
        valid = jnp.ones(x.shape[:2], bool)
    counts = jnp.sum(hit & valid[:, :, None, None], axis=(1, 2),
                     dtype=jnp.int32)
    return y, counts
