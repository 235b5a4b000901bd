"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_attention_ref(q, k, v, q_pos, k_pos, *, window: int = 0):
    """q: (B, H, hd); k/v: (B, S, KV, hd); q_pos: (B,); k_pos: (B, S)."""
    B, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qh = q.reshape(B, KV, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qh, k.astype(jnp.float32))
    scores = scores / math.sqrt(hd)
    mask = (k_pos >= 0) & (k_pos <= q_pos[:, None])
    if window:
        mask = mask & (k_pos > q_pos[:, None] - window)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


def paged_view(pool_k, pool_v, pool_pos, tables, layer):
    """Gather a per-request contiguous (B, MB*bs) view of the paged pools
    (DESIGN §9) — the canonical block-table gather, shared by the paged
    decode oracle below and `models.layers.paged_view` (the production
    non-kernel path), so the two can never diverge.

    pool_k/v: the stacked (L, NB, bs, KV, hd) pools, read at `layer` (an
    int32 scalar) in place: the gather indexes the flattened stack at
    layer*NB*bs + tables*bs + j, so no per-layer slice of the pool is
    made. pool_pos: (NB, bs), shared by every layer.

    Logical block j of request b sits at view indices [j*bs, (j+1)*bs), so
    a token at absolute position p lands at view index p — the same index
    it has in a non-ring contiguous cache row, which keeps the paged and
    contiguous layouts bitwise comparable. Unallocated table entries (-1)
    read as empty slots (K/V = 0, pos = -1)."""
    n, NB, bs = pool_k.shape[:3]
    B, MB = tables.shape
    own = tables >= 0
    offs = jnp.arange(bs)[None, None, :]

    def flat_idx(base, oob):
        return (jnp.where(own, base, oob)[:, :, None] + offs).reshape(
            B, MB * bs)

    kv_idx = flat_idx(layer * NB * bs + tables * bs, n * NB * bs)
    pos_idx = flat_idx(tables * bs, NB * bs)
    kf = pool_k.reshape((n * NB * bs,) + pool_k.shape[3:])
    vf = pool_v.reshape((n * NB * bs,) + pool_v.shape[3:])
    pf = pool_pos.reshape(NB * bs)
    k = kf.at[kv_idx].get(mode="fill", fill_value=0)
    v = vf.at[kv_idx].get(mode="fill", fill_value=0)
    kpos = pf.at[pos_idx].get(mode="fill", fill_value=-1)
    return k, v, kpos


def paged_decode_attention_ref(q, k_pool, v_pool, q_pos, kpos_pool, tables,
                               layer, *, window: int = 0):
    """Gather-then-attend oracle for the paged kernel (DESIGN §9).

    q: (B, H, hd); k/v_pool: (L, NB, bs, KV, hd) read at `layer`;
    q_pos: (B,); kpos_pool: (NB, bs); tables: (B, MB), -1 = unallocated."""
    k, v, kpos = paged_view(k_pool, v_pool, kpos_pool, tables, layer)
    return decode_attention_ref(q, k, v, q_pos, kpos, window=window)


def flash_attention_ref(q, k, v, q_pos, k_pos, *, window: int = 0,
                        causal: bool = True):
    """q: (B, Tq, H, hd); k/v: (B, Tk, KV, hd); q_pos: (B,Tq); k_pos: (B,Tk)."""
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qh = q.reshape(B, Tq, KV, G, hd).astype(jnp.float32)
    scores = jnp.einsum("btkgd,bskd->bkgts", qh, k.astype(jnp.float32))
    scores = scores / math.sqrt(hd)
    mask = (k_pos[:, None, :] >= 0)
    if causal:
        mask = mask & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window:
        mask = mask & (k_pos[:, None, :] > q_pos[:, :, None] - window)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Tq, H * hd).reshape(B, Tq, H, hd).astype(q.dtype)


def ssd_intra_ref(xdt, cum_a, Br, Cr):
    """Intra-chunk SSD term + per-chunk states (the Pallas kernel's scope).

    xdt:   (B, nc, Q, H, P)  dt-scaled inputs
    cum_a: (B, nc, Q, H)     within-chunk cumulative log-decay
    Br/Cr: (B, nc, Q, N)
    Returns y_intra (B, nc, Q, H, P), s_chunk (B, nc, H, P, N).
    """
    f32 = jnp.float32
    xdt, cum_a = xdt.astype(f32), cum_a.astype(f32)
    Br, Cr = Br.astype(f32), Cr.astype(f32)
    Q = xdt.shape[2]
    li = cum_a[:, :, :, None, :]
    lj = cum_a[:, :, None, :, :]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(tri[None, None, :, :, None], jnp.exp(li - lj), 0.0)
    cb = jnp.einsum("bzin,bzjn->bzij", Cr, Br)
    w = cb[..., None] * L
    y_intra = jnp.einsum("bzijh,bzjhp->bzihp", w, xdt)
    seg_end = cum_a[:, :, -1:, :]
    decay_to_end = jnp.exp(seg_end - cum_a)
    s_chunk = jnp.einsum("bzjn,bzjhp->bzhpn", Br, xdt * decay_to_end[..., None])
    return y_intra, s_chunk


def rmsnorm_ref(x, w, *, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps) * (1.0 + w.astype(jnp.float32))
    return y.astype(x.dtype)


def rglru_scan_ref(a, bx, h0):
    """h_t = a_t * h_{t-1} + bx_t. a/bx: (B, T, W) fp32; h0: (B, W).

    Returns (h_all (B,T,W), h_T (B,W))."""
    def combine(left, right):
        al, bl = left
        ar, br = right
        return al * ar, ar * bl + br

    bx = bx.at[:, 0].add(a[:, 0] * h0)
    _, h_all = jax.lax.associative_scan(combine, (a, bx), axis=1)
    return h_all, h_all[:, -1]
