"""Backbone assembly for all six architecture families.

Design notes
------------
* Layer stacks are parameter-stacked (leading L axis) and driven by
  jax.lax.scan so the HLO is O(1) in depth — this keeps the 80 dry-run
  compiles tractable and matches production practice (MaxText-style).
* A single cached-attention code path serves chunked prefill AND decode
  (decode = chunk of length 1). Caches store absolute positions per slot,
  so ring-buffer (sliding-window) and full caches share all code.
* Padding tokens carry position -1; their cache writes are dropped via
  out-of-bounds scatter (mode='drop').
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ArchFamily, AttentionKind, ModelConfig
from repro.models import layers as L
from repro.models import rglru as R
from repro.models import ssm as S

Params = Dict[str, Any]
Cache = Dict[str, Any]


def cfg_dtype(cfg: ModelConfig, override=None):
    if override is not None:
        return override
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


def _stack_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def window_of(cfg: ModelConfig) -> int:
    if cfg.attention == AttentionKind.SLIDING:
        return cfg.sliding_window
    if cfg.attention == AttentionKind.LOCAL_HYBRID:
        return cfg.rglru.window_size
    return 0


def phys_cache_len(cfg: ModelConfig, max_context: int, chunk: int = 1) -> int:
    """Ring capacity for windowed attention: a chunk of T queries written
    before attending must still see window-1 keys behind its OLDEST query,
    so the ring holds window + chunk - 1 positions (chunk=1 decode -> just
    the window)."""
    w = window_of(cfg)
    return min(max_context, w + chunk - 1) if w else max_context


# ---------------------------------------------------------------------------
# per-layer blocks (single-layer params)


def _dense_layer_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 2)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": L.init_attention(ks[0], cfg, dtype),
        "mlp": L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.num_layers, dtype),
    }


def _moe_layer_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 2)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": L.init_attention(ks[0], cfg, dtype),
        "moe": L.init_moe(ks[1], cfg, dtype),
    }


def _cross_layer_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 2)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "attn": L.init_attention(ks[0], cfg, dtype, cross=True),
        "mlp": L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.num_layers, dtype),
    }


def _ssm_layer_init(key, cfg: ModelConfig, dtype):
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "mixer": S.init_mamba2_block(key, cfg, dtype),
    }


def _rec_layer_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 2)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "rec": R.init_rglru_block(ks[0], cfg, dtype),
        "mlp": L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.num_layers, dtype),
    }


def _attn_block_train(p, x, positions, cfg, window, no_drop=False):
    h = L.rms_norm(x, p["ln1"], cfg.rms_eps)
    x = x + L.self_attention_train(p["attn"], h, positions, cfg, window=window)
    h = L.rms_norm(x, p["ln2"], cfg.rms_eps)
    if "moe" in p:
        y, aux = L.moe_apply(p["moe"], h, cfg, no_drop=no_drop)
        return x + y, aux
    return x + L.mlp(p["mlp"], h), 0.0


def _serve_ffn(p, x, cfg):
    h = L.rms_norm(x, p["ln2"], cfg.rms_eps)
    if "moe" in p:
        # no_drop: serving must be chunking-invariant (see moe_apply docs)
        y, _ = L.moe_apply(p["moe"], h, cfg,
                           no_drop=cfg.moe.inference_no_drop)
        return x + y
    return x + L.mlp(p["mlp"], h)


def _attn_block_cached(p, x, positions, ck, cv, cpos, cfg, window):
    h = L.rms_norm(x, p["ln1"], cfg.rms_eps)
    a, ck, cv, cpos = L.self_attention_cached(
        p["attn"], h, positions, ck, cv, cpos, cfg, window=window)
    return _serve_ffn(p, x + a, cfg), ck, cv, cpos


def _attn_block_paged(p, x, positions, k_all, v_all, cpos, tables, slots,
                      layer, cfg, window):
    """Paged twin of `_attn_block_cached`: K/V go through the block-table
    indexed stacked pools at `layer`, in place (DESIGN §9)."""
    h = L.rms_norm(x, p["ln1"], cfg.rms_eps)
    a, k_all, v_all = L.self_attention_paged(
        p["attn"], h, positions, k_all, v_all, cpos, tables, slots, layer,
        cfg, window=window)
    return _serve_ffn(p, x + a, cfg), k_all, v_all


def _cross_block(p, x, kv_k, kv_v, k_valid, cfg, gated):
    h = L.rms_norm(x, p["ln1"], cfg.rms_eps)
    x = x + L.cross_attention(p["attn"], h, kv_k, kv_v, k_valid, cfg, gated=gated)
    h = L.rms_norm(x, p["ln2"], cfg.rms_eps)
    return x + L.mlp(p["mlp"], h)


def _ssm_block(p, x, cfg, conv_state=None, ssm_state=None, decode=False):
    h = L.rms_norm(x, p["ln1"], cfg.rms_eps)
    y, (conv_state, ssm_state) = S.mamba2_block(
        p["mixer"], h, cfg, conv_state=conv_state, ssm_state=ssm_state,
        decode=decode)
    return x + y, conv_state, ssm_state


def _rec_block(p, x, cfg, conv_state=None, rec_state=None, decode=False):
    h = L.rms_norm(x, p["ln1"], cfg.rms_eps)
    y, (conv_state, rec_state) = R.rglru_block(
        p["rec"], h, cfg, conv_state=conv_state, rec_state=rec_state,
        decode=decode)
    x = x + y
    h = L.rms_norm(x, p["ln2"], cfg.rms_eps)
    return x + L.mlp(p["mlp"], h), conv_state, rec_state


# ---------------------------------------------------------------------------
# init for the whole model


def init_params(key, cfg: ModelConfig, dtype=None) -> Params:
    dt = cfg_dtype(cfg, dtype)
    ks = jax.random.split(key, 8)
    p: Params = {
        "embed": L.dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                              scale=0.02, dtype=dt),
        "ln_f": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                    dtype=dt)
    fam = cfg.family
    if fam in (ArchFamily.DENSE,):
        p["layers"] = _stack_init(
            lambda k: _dense_layer_init(k, cfg, dt), ks[2], cfg.num_layers)
    elif fam == ArchFamily.MOE:
        p["layers"] = _stack_init(
            lambda k: _moe_layer_init(k, cfg, dt), ks[2], cfg.num_layers)
    elif fam == ArchFamily.SSM:
        p["layers"] = _stack_init(
            lambda k: _ssm_layer_init(k, cfg, dt), ks[2], cfg.num_layers)
    elif fam == ArchFamily.HYBRID:
        kinds = cfg.layer_kinds()
        n_rec = sum(1 for k in kinds if k == "recurrent")
        n_att = len(kinds) - n_rec
        p["rec_layers"] = _stack_init(
            lambda k: _rec_layer_init(k, cfg, dt), ks[2], n_rec)
        p["att_layers"] = _stack_init(
            lambda k: _dense_layer_init(k, cfg, dt), ks[3], n_att)
    elif fam == ArchFamily.VLM:
        p["layers"] = _stack_init(
            lambda k: _dense_layer_init(k, cfg, dt), ks[2], cfg.num_layers)
        p["cross_layers"] = _stack_init(
            lambda k: _cross_layer_init(k, cfg, dt), ks[3], cfg.num_cross_layers)
    elif fam == ArchFamily.ENCDEC:
        p["enc_layers"] = _stack_init(
            lambda k: _dense_layer_init(k, cfg, dt), ks[2], cfg.encoder_layers)
        p["dec_layers"] = _stack_init(
            lambda k: _dense_layer_init(k, cfg, dt), ks[3], cfg.num_layers)
        p["dec_cross"] = _stack_init(
            lambda k: _cross_layer_init(k, cfg, dt), ks[4], cfg.num_layers)
    else:
        raise ValueError(fam)
    return p


def logits_head(p, x, cfg: ModelConfig):
    h = L.rms_norm(x, p["ln_f"], cfg.rms_eps)
    wout = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    return (h @ wout).astype(jnp.float32)


# ---------------------------------------------------------------------------
# TRAIN forward (full sequence, no cache)


def _scan_layers(body, x, stacked, remat: bool, init_aux=0.0):
    if remat:
        body = jax.checkpoint(body)

    def f(carry, lp):
        return body(carry, lp), None

    (x, aux), _ = jax.lax.scan(f, (x, init_aux), stacked)
    return x, aux


def forward_train(p: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                  *, remat: bool = True, no_drop: bool = False,
                  return_hidden: bool = False
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B,T,V) fp32, aux_loss scalar); with
    return_hidden=True returns the pre-head hidden states (B,T,d) instead of
    logits (the chunked-loss path never materializes (B,T,V) — §Perf B)."""
    tokens = batch["tokens"]
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    x = p["embed"][tokens]
    fam = cfg.family
    win = window_of(cfg)

    if fam in (ArchFamily.DENSE, ArchFamily.MOE):
        def body(carry, lp):
            h, aux = carry
            h, a = _attn_block_train(lp, h, positions, cfg, win, no_drop)
            return h, aux + a
        x, aux = _scan_layers(body, x, p["layers"], remat)

    elif fam == ArchFamily.SSM:
        def body(carry, lp):
            h, aux = carry
            h, _, _ = _ssm_block(lp, h, cfg)
            return h, aux
        x, aux = _scan_layers(body, x, p["layers"], remat)

    elif fam == ArchFamily.HYBRID:
        x, aux = _hybrid_train(p, x, positions, cfg, remat)

    elif fam == ArchFamily.VLM:
        x, aux = _vlm_train(p, x, positions, batch["images"], cfg, remat)

    elif fam == ArchFamily.ENCDEC:
        x, aux = _encdec_train(p, x, positions, batch, cfg, remat)
    else:
        raise ValueError(fam)

    if return_hidden:
        return x, aux
    return logits_head(p, x, cfg), aux


def _hybrid_train(p, x, positions, cfg, remat):
    kinds = cfg.layer_kinds()
    pat = cfg.rglru.block_pattern
    n_pat = len(pat)
    n_groups = cfg.num_layers // n_pat
    rec_per_group = sum(1 for k in pat if k == "recurrent")
    att_per_group = n_pat - rec_per_group
    win = cfg.rglru.window_size

    rec_grouped = jax.tree.map(
        lambda a: a[: n_groups * rec_per_group].reshape(
            (n_groups, rec_per_group) + a.shape[1:]), p["rec_layers"])
    att_grouped = jax.tree.map(
        lambda a: a[: n_groups * att_per_group].reshape(
            (n_groups, att_per_group) + a.shape[1:]), p["att_layers"])

    def group_body(carry, lp):
        h, aux = carry
        rec_p, att_p = lp
        ri = ai = 0
        for k in pat:
            if k == "recurrent":
                one = jax.tree.map(lambda a: a[ri], rec_p)
                h, _, _ = _rec_block(one, h, cfg)
                ri += 1
            else:
                one = jax.tree.map(lambda a: a[ai], att_p)
                h, _ = _attn_block_train(one, h, positions, cfg, win)
                ai += 1
        return h, aux

    body = jax.checkpoint(group_body) if remat else group_body

    def f(carry, lp):
        return body(carry, lp), None

    (x, aux), _ = jax.lax.scan(f, (x, 0.0), (rec_grouped, att_grouped))

    # leftover layers (pattern remainder), unrolled
    used_rec = n_groups * rec_per_group
    used_att = n_groups * att_per_group
    ri, ai = used_rec, used_att
    for k in kinds[n_groups * n_pat:]:
        if k == "recurrent":
            one = jax.tree.map(lambda a: a[ri], p["rec_layers"])
            x, _, _ = _rec_block(one, x, cfg)
            ri += 1
        else:
            one = jax.tree.map(lambda a: a[ai], p["att_layers"])
            x, _ = _attn_block_train(one, x, positions, cfg, win)
            ai += 1
    return x, aux


def _vlm_train(p, x, positions, images, cfg, remat):
    """images: (B, P, d) stub patch embeddings. Cross layer every
    `vlm_cross_every` self layers."""
    n_cross = cfg.num_cross_layers
    per = cfg.num_layers // n_cross
    self_grouped = jax.tree.map(
        lambda a: a.reshape((n_cross, per) + a.shape[1:]), p["layers"])
    kv = jax.vmap(lambda cp: L.cross_kv(cp["attn"], images, cfg))(
        p["cross_layers"])  # (Lc, B, P, KV, hd) x2

    def group_body(carry, lp):
        h, aux = carry
        self_p, cross_p, (ck, cv) = lp

        def inner(c, one):
            hh, ax = c
            hh, a = _attn_block_train(one, hh, positions, cfg, 0)
            return (hh, ax + a), None

        (h, aux), _ = jax.lax.scan(inner, (h, aux), self_p)
        h = _cross_block(cross_p, h, ck, cv, None, cfg, gated=True)
        return h, aux

    body = jax.checkpoint(group_body) if remat else group_body

    def f(carry, lp):
        return body(carry, lp), None

    (x, aux), _ = jax.lax.scan(
        f, (x, 0.0), (self_grouped, p["cross_layers"], kv))
    return x, aux


def encode(p, enc_frames, cfg: ModelConfig, remat: bool = False):
    """Bidirectional encoder over stub frame embeddings (B, S, d)."""
    B, Senc, _ = enc_frames.shape
    pos = jnp.broadcast_to(jnp.arange(Senc, dtype=jnp.int32)[None], (B, Senc))

    def body(carry, lp):
        h, aux = carry
        hh = L.rms_norm(h, lp["ln1"], cfg.rms_eps)
        q, k, v = L.attention_qkv(lp["attn"], hh, cfg)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        att = L.attend(q, k, v, pos, pos, causal=False)
        h = h + att @ lp["attn"]["wo"]
        hh = L.rms_norm(h, lp["ln2"], cfg.rms_eps)
        return h + L.mlp(lp["mlp"], hh), aux

    x, _ = _scan_layers(body, enc_frames, p["enc_layers"], remat)
    return x


def _encdec_train(p, x, positions, batch, cfg, remat):
    enc_out = encode(p, batch["enc_frames"], cfg, remat)
    kv = jax.vmap(lambda cp: L.cross_kv(cp["attn"], enc_out, cfg))(
        p["dec_cross"])

    def body(carry, lp):
        h, aux = carry
        dec_p, cross_p, (ck, cv) = lp
        h, a = _attn_block_train(dec_p, h, positions, cfg, 0)
        h = _cross_block(cross_p, h, ck, cv, None, cfg, gated=False)
        return h, aux + a

    bodyf = jax.checkpoint(body) if remat else body

    def f(carry, lp):
        return bodyf(carry, lp), None

    (x, aux), _ = jax.lax.scan(
        f, (x, 0.0), (p["dec_layers"], p["dec_cross"], kv))
    return x, aux


# ---------------------------------------------------------------------------
# caches


def init_cache(cfg: ModelConfig, batch: int, max_context: int,
               dtype=None, enc_len: int = 0, chunk: int = 1) -> Cache:
    dt = cfg_dtype(cfg, dtype)
    hd = cfg.resolved_head_dim
    KV = cfg.num_kv_heads
    S = phys_cache_len(cfg, max_context, chunk)
    fam = cfg.family
    c: Cache = {}
    if fam in (ArchFamily.DENSE, ArchFamily.MOE, ArchFamily.VLM,
               ArchFamily.ENCDEC):
        Ldec = cfg.num_layers
        c["k"] = jnp.zeros((Ldec, batch, S, KV, hd), dt)
        c["v"] = jnp.zeros((Ldec, batch, S, KV, hd), dt)
        c["pos"] = jnp.full((batch, S), -1, jnp.int32)
    if fam == ArchFamily.VLM:
        # cross KV filled at prefill from image embeddings
        c["cross_k"] = jnp.zeros(
            (cfg.num_cross_layers, batch, enc_len, KV, hd), dt)
        c["cross_v"] = jnp.zeros_like(c["cross_k"])
    if fam == ArchFamily.ENCDEC:
        c["cross_k"] = jnp.zeros((cfg.num_layers, batch, enc_len, KV, hd), dt)
        c["cross_v"] = jnp.zeros_like(c["cross_k"])
    if fam == ArchFamily.SSM:
        d_in, H, P, N = S_dims_of(cfg)
        conv_ch = d_in + 2 * N
        c["conv"] = jnp.zeros(
            (cfg.num_layers, batch, cfg.ssm.conv_width - 1, conv_ch), dt)
        c["ssm"] = jnp.zeros((cfg.num_layers, batch, H, P, N), jnp.float32)
    if fam == ArchFamily.HYBRID:
        kinds = cfg.layer_kinds()
        n_rec = sum(1 for k in kinds if k == "recurrent")
        n_att = len(kinds) - n_rec
        w = cfg.rglru.lru_width or cfg.d_model
        c["k"] = jnp.zeros((n_att, batch, S, KV, hd), dt)
        c["v"] = jnp.zeros((n_att, batch, S, KV, hd), dt)
        c["pos"] = jnp.full((batch, S), -1, jnp.int32)
        c["conv"] = jnp.zeros(
            (n_rec, batch, cfg.rglru.conv_width - 1, w), dt)
        c["rec"] = jnp.zeros((n_rec, batch, w), jnp.float32)
    return c


def init_paged_cache(cfg: ModelConfig, n_slots: int, num_blocks: int,
                     block_size: int, dtype=None, enc_len: int = 0) -> Cache:
    """Physically paged serving cache (DESIGN §9).

    Attention K/V live in (layers, num_blocks, block_size, KV, hd) pools
    shared by every request and indexed through per-request block tables
    (the BlockManager's tables ARE the storage map); `pos` is the pool-wide
    (num_blocks, block_size) absolute-position map (-1 = empty slot).
    Constant-size per-request state (SSM conv/ssm, RG-LRU conv/rec,
    cross-KV) stays per-slot with `n_slots` rows, pinned to a request for
    its whole life so lane promotion / eviction never copy it."""
    dt = cfg_dtype(cfg, dtype)
    hd = cfg.resolved_head_dim
    KV = cfg.num_kv_heads
    fam = cfg.family
    c: Cache = {}
    if fam in (ArchFamily.DENSE, ArchFamily.MOE, ArchFamily.VLM,
               ArchFamily.ENCDEC):
        Ldec = cfg.num_layers
        c["k"] = jnp.zeros((Ldec, num_blocks, block_size, KV, hd), dt)
        c["v"] = jnp.zeros((Ldec, num_blocks, block_size, KV, hd), dt)
        c["pos"] = jnp.full((num_blocks, block_size), -1, jnp.int32)
    if fam == ArchFamily.VLM:
        c["cross_k"] = jnp.zeros(
            (cfg.num_cross_layers, n_slots, enc_len, KV, hd), dt)
        c["cross_v"] = jnp.zeros_like(c["cross_k"])
    if fam == ArchFamily.ENCDEC:
        c["cross_k"] = jnp.zeros((cfg.num_layers, n_slots, enc_len, KV, hd), dt)
        c["cross_v"] = jnp.zeros_like(c["cross_k"])
    if fam == ArchFamily.SSM:
        d_in, H, P, N = S_dims_of(cfg)
        conv_ch = d_in + 2 * N
        c["conv"] = jnp.zeros(
            (cfg.num_layers, n_slots, cfg.ssm.conv_width - 1, conv_ch), dt)
        c["ssm"] = jnp.zeros((cfg.num_layers, n_slots, H, P, N), jnp.float32)
    if fam == ArchFamily.HYBRID:
        kinds = cfg.layer_kinds()
        n_rec = sum(1 for k in kinds if k == "recurrent")
        n_att = len(kinds) - n_rec
        w = cfg.rglru.lru_width or cfg.d_model
        c["k"] = jnp.zeros((n_att, num_blocks, block_size, KV, hd), dt)
        c["v"] = jnp.zeros((n_att, num_blocks, block_size, KV, hd), dt)
        c["pos"] = jnp.full((num_blocks, block_size), -1, jnp.int32)
        c["conv"] = jnp.zeros(
            (n_rec, n_slots, cfg.rglru.conv_width - 1, w), dt)
        c["rec"] = jnp.zeros((n_rec, n_slots, w), jnp.float32)
    return c


def S_dims_of(cfg):
    return S.ssm_dims(cfg)


def cache_bytes(cfg: ModelConfig, batch: int, max_context: int,
                enc_len: int = 0) -> int:
    cache = jax.eval_shape(
        lambda: init_cache(cfg, batch, max_context, enc_len=enc_len))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


# ---------------------------------------------------------------------------
# PREFILL / DECODE (unified chunked step; decode = chunk of length 1)


def forward_cached(p: Params, tokens, positions, cache: Cache,
                   cfg: ModelConfig, *, decode: bool,
                   extras: Optional[Dict[str, jnp.ndarray]] = None,
                   last_only: bool = False,
                   tables=None) -> Tuple[jnp.ndarray, Cache]:
    """tokens: (B, T) int32; positions: (B, T) absolute, -1 for padding.

    Returns (logits (B, T, V) fp32, updated cache). For SSM/recurrent layers
    `decode=True` selects the O(1) step (requires T == 1).
    last_only: compute the vocab projection for the final position only
    (production serving path — avoids materializing (B, T, V); §Perf iter A).
    tables: optional (B, MB) per-request physical block tables; when given,
    the cache's k/v/pos are the paged pools of `init_paged_cache` and all
    attention layers read/write through the tables (DESIGN §9).
    """
    extras = extras or {}
    x = p["embed"][tokens]
    fam = cfg.family
    win = window_of(cfg)
    new_cache = dict(cache)

    if fam in (ArchFamily.DENSE, ArchFamily.MOE):
        x, new_cache = _attn_stack_cached(
            p["layers"], x, positions, cache, cfg, win, new_cache,
            tables=tables)

    elif fam == ArchFamily.SSM:
        def body(carry, lp):
            h = carry
            one, conv_s, ssm_s = lp
            h, conv_s, ssm_s = _ssm_block(
                one, h, cfg, conv_state=conv_s, ssm_state=ssm_s, decode=decode)
            return h, (conv_s, ssm_s)

        x, (conv_n, ssm_n) = jax.lax.scan(
            body, x, (p["layers"], cache["conv"], cache["ssm"]))
        new_cache["conv"], new_cache["ssm"] = conv_n, ssm_n

    elif fam == ArchFamily.HYBRID:
        x, new_cache = _hybrid_cached(p, x, positions, cache, cfg, decode,
                                      tables=tables)

    elif fam == ArchFamily.VLM:
        if "images" in extras:  # prefill: compute cross KV once
            kv_k, kv_v = jax.vmap(
                lambda cp: L.cross_kv(cp["attn"], extras["images"], cfg))(
                p["cross_layers"])
            new_cache["cross_k"], new_cache["cross_v"] = kv_k, kv_v
        x, new_cache = _vlm_cached(p, x, positions, new_cache, cfg,
                                   tables=tables)

    elif fam == ArchFamily.ENCDEC:
        if "enc_frames" in extras:  # prefill: run encoder, fill cross KV
            enc_out = encode(p, extras["enc_frames"], cfg)
            kv_k, kv_v = jax.vmap(
                lambda cp: L.cross_kv(cp["attn"], enc_out, cfg))(
                p["dec_cross"])
            new_cache["cross_k"], new_cache["cross_v"] = kv_k, kv_v
        x, new_cache = _encdec_cached(p, x, positions, new_cache, cfg,
                                      tables=tables)
    else:
        raise ValueError(fam)

    if last_only:
        x = x[:, -1:]
    return logits_head(p, x, cfg), new_cache


def _attn_stack_cached(stacked, x, positions, cache, cfg, win, new_cache,
                       tables=None):
    """Layer loop for the cached (serving) path.

    Uses fori_loop with the stacked caches as loop CARRY rather than scan
    xs/ys: scan rebuilds the stacked (L,B,S,KV,hd) cache as fresh ys
    output (2-3x full-cache temp traffic per step); a while-loop carry
    lets XLA update the (donated) buffer in place (§Perf iteration E).
    The contiguous cache is sliced per layer and written back. With
    `tables` the k/v are the stacked paged pools (DESIGN §9), read and
    written in place by layer index, so a step's pool traffic follows its
    tokens, not the pool's size: slots and the pos map are made once,
    before the loop, and no layer ever slices the pool."""
    cpos0 = cache["pos"]
    n = cache["k"].shape[0]
    if tables is not None:
        slots, cpos0 = L.paged_step_slots(positions, tables, cpos0, n)

    def body(i, carry):
        h, k_all, v_all, cpos = carry
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            stacked)
        if tables is not None:
            h, k_all, v_all = _attn_block_paged(
                lp, h, positions, k_all, v_all, cpos, tables, slots, i, cfg,
                win)
            return (h, k_all, v_all, cpos)
        ck = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
        h, ck, cv, cpos = _attn_block_cached(
            lp, h, positions, ck, cv, cpos0, cfg, win)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, ck, i, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, cv, i, 0)
        return (h, k_all, v_all, cpos)

    x, k_n, v_n, cpos = jax.lax.fori_loop(
        0, n, body, (x, cache["k"], cache["v"], cpos0))
    new_cache["k"], new_cache["v"], new_cache["pos"] = k_n, v_n, cpos
    return x, new_cache


def _hybrid_cached(p, x, positions, cache, cfg, decode, tables=None):
    """fori_loop over the heterogeneous layer pattern with in-place cache
    carry (§Perf iter E). Static index maps translate the flat layer index
    into the recurrent-stack / attention-stack positions; lax.cond picks
    the branch (both return the full same-shape carry). With `tables` the
    attention branch goes through the paged pools (DESIGN §9)."""
    import numpy as np
    kinds = cfg.layer_kinds()
    win = cfg.rglru.window_size
    cpos0 = cache["pos"]
    if tables is not None:
        slots, cpos0 = L.paged_step_slots(positions, tables, cpos0,
                                          cache["k"].shape[0])
    is_att = jnp.asarray(np.array([k == "attention" for k in kinds]))
    rec_of = jnp.asarray(np.cumsum([k == "recurrent" for k in kinds]) - 1)
    att_of = jnp.asarray(np.cumsum([k == "attention" for k in kinds]) - 1)

    def take(t, j):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False), t)

    def body(i, carry):
        h, k_all, v_all, cpos, conv_all, rec_all = carry

        def att_branch(args):
            h, k_all, v_all, cpos, conv_all, rec_all = args
            j = att_of[i]
            one = take(p["att_layers"], j)
            if tables is not None:
                h, k_all, v_all = _attn_block_paged(
                    one, h, positions, k_all, v_all, cpos, tables, slots, j,
                    cfg, win)
                return (h, k_all, v_all, cpos, conv_all, rec_all)
            ck = jax.lax.dynamic_index_in_dim(k_all, j, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(v_all, j, 0, keepdims=False)
            h, ck, cv, cpos = _attn_block_cached(
                one, h, positions, ck, cv, cpos0, cfg, win)
            k_all = jax.lax.dynamic_update_index_in_dim(k_all, ck, j, 0)
            v_all = jax.lax.dynamic_update_index_in_dim(v_all, cv, j, 0)
            return (h, k_all, v_all, cpos, conv_all, rec_all)

        def rec_branch(args):
            h, k_all, v_all, cpos, conv_all, rec_all = args
            j = rec_of[i]
            one = take(p["rec_layers"], j)
            conv_s = jax.lax.dynamic_index_in_dim(conv_all, j, 0,
                                                  keepdims=False)
            rec_s = jax.lax.dynamic_index_in_dim(rec_all, j, 0,
                                                 keepdims=False)
            h, conv_s, rec_s = _rec_block(
                one, h, cfg, conv_state=conv_s, rec_state=rec_s,
                decode=decode)
            conv_all = jax.lax.dynamic_update_index_in_dim(
                conv_all, conv_s, j, 0)
            rec_all = jax.lax.dynamic_update_index_in_dim(
                rec_all, rec_s, j, 0)
            return (h, k_all, v_all, cpos, conv_all, rec_all)

        return jax.lax.cond(is_att[i], att_branch, rec_branch, carry)

    carry = (x, cache["k"], cache["v"], cpos0, cache["conv"], cache["rec"])
    x, k_n, v_n, cpos, conv_n, rec_n = jax.lax.fori_loop(
        0, len(kinds), body, carry)
    new_cache = dict(cache)
    new_cache.update(k=k_n, v=v_n, pos=cpos, conv=conv_n, rec=rec_n)
    return x, new_cache


def _vlm_cached(p, x, positions, cache, cfg, tables=None):
    """fori_loop with in-place cache carry (§Perf iter E); a cross-attn
    layer fires after every `per` self layers via lax.cond. With `tables`
    self-attention goes through the paged pools (DESIGN §9)."""
    n_cross = cfg.num_cross_layers
    per = cfg.num_layers // n_cross
    cpos0 = cache["pos"]
    n = cfg.num_layers
    if tables is not None:
        slots, cpos0 = L.paged_step_slots(positions, tables, cpos0, n)

    def body(i, carry):
        h, k_all, v_all, cpos = carry
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            p["layers"])
        if tables is not None:
            h, k_all, v_all = _attn_block_paged(
                lp, h, positions, k_all, v_all, cpos, tables, slots, i, cfg, 0)
        else:
            ck = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
            h, ck, cv, cpos = _attn_block_cached(
                lp, h, positions, ck, cv, cpos0, cfg, 0)
            k_all = jax.lax.dynamic_update_index_in_dim(k_all, ck, i, 0)
            v_all = jax.lax.dynamic_update_index_in_dim(v_all, cv, i, 0)

        def with_cross(hh):
            j = i // per
            cp = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j, 0,
                                                       keepdims=False),
                p["cross_layers"])
            xk = jax.lax.dynamic_index_in_dim(cache["cross_k"], j, 0,
                                              keepdims=False)
            xv = jax.lax.dynamic_index_in_dim(cache["cross_v"], j, 0,
                                              keepdims=False)
            return _cross_block(cp, hh, xk, xv, None, cfg, gated=True)

        h = jax.lax.cond((i + 1) % per == 0, with_cross, lambda hh: hh, h)
        return (h, k_all, v_all, cpos)

    x, k_n, v_n, cpos = jax.lax.fori_loop(
        0, n, body, (x, cache["k"], cache["v"], cpos0))
    cache = dict(cache)
    cache["k"], cache["v"], cache["pos"] = k_n, v_n, cpos
    return x, cache


def _encdec_cached(p, x, positions, cache, cfg, tables=None):
    """fori_loop with in-place self-KV cache carry (§Perf iter E); with
    `tables` decoder self-attention goes through the paged pools
    (DESIGN §9)."""
    cpos0 = cache["pos"]
    if tables is not None:
        slots, cpos0 = L.paged_step_slots(positions, tables, cpos0,
                                          cfg.num_layers)

    def body(i, carry):
        h, k_all, v_all, cpos = carry
        take = lambda t: jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), t)
        dec_p = take(p["dec_layers"])
        cross_p = take(p["dec_cross"])
        xk = jax.lax.dynamic_index_in_dim(cache["cross_k"], i, 0,
                                          keepdims=False)
        xv = jax.lax.dynamic_index_in_dim(cache["cross_v"], i, 0,
                                          keepdims=False)
        if tables is not None:
            h, k_all, v_all = _attn_block_paged(
                dec_p, h, positions, k_all, v_all, cpos, tables, slots, i,
                cfg, 0)
        else:
            ck = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
            h, ck, cv, cpos = _attn_block_cached(
                dec_p, h, positions, ck, cv, cpos0, cfg, 0)
            k_all = jax.lax.dynamic_update_index_in_dim(k_all, ck, i, 0)
            v_all = jax.lax.dynamic_update_index_in_dim(v_all, cv, i, 0)
        h = _cross_block(cross_p, h, xk, xv, None, cfg, gated=False)
        return (h, k_all, v_all, cpos)

    x, k_n, v_n, cpos = jax.lax.fori_loop(
        0, cfg.num_layers, body, (x, cache["k"], cache["v"], cpos0))
    cache = dict(cache)
    cache["k"], cache["v"], cache["pos"] = k_n, v_n, cpos
    return x, cache
