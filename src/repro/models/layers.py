"""Shared transformer layers: norms, RoPE, attention (with unified ring/full
KV cache), SwiGLU MLP, and GShard-style dense-dispatch MoE.

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


def apply_rope(x, positions, theta: float):
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., T, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core

_NEG_INF = -1e30


def attend(q, k, v, q_pos, k_pos, *, window: int = 0, causal: bool = True,
           k_valid=None):
    """Masked GQA attention.

    q: (B, Tq, H, hd); k/v: (B, Tk, KV, hd)
    q_pos: (B, Tq) int32 absolute positions of queries
    k_pos: (B, Tk) int32 absolute positions of keys (-1 = empty slot)
    window: if >0, keys older than q_pos - window + 1 are masked
    k_valid: optional (B, Tk) bool extra mask (e.g. encoder padding)
    """
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    # fp32 ACCUMULATION without materializing fp32 copies of the KV cache
    # (an .astype(f32) on k/v doubles the decode memory term — §Perf iter C)
    qh = q.reshape(B, Tq, KV, G, hd)
    scores = jnp.einsum("btkgd,bskd->bkgts", qh, k,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
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
    return out.reshape(B, Tq, H * hd).astype(q.dtype)


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
