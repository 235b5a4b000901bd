"""Flash-decode GQA attention Pallas kernels (the serving hot spot).

One new query token per sequence against the KV cache, in two layouts:

* contiguous (`decode_attention_kernel`): k/v are per-slot (B, S, KV, hd)
  rows; grid = (batch, kv_blocks) over the contiguous S axis.
* paged (`paged_decode_attention_kernel`, DESIGN §9): k/v live in shared
  (layers, num_blocks, block_size, KV, hd) pools and the kv-block grid axis
  walks the per-request block table instead of a contiguous row — the
  layer index and the table are scalar-prefetch operands so the BlockSpec
  index maps can chase them into the stacked pool.

Both accumulate an online softmax in VMEM scratch. Masking is
position-based (absolute positions per cache slot, -1 = empty), identical
to the model's semantics — ring buffers / sliding windows / ragged paged
tails need no extra code.

TPU notes: every tile takes all kv heads, so a K/V block's minor two axes
are the array's own (KV, hd) and Mosaic's (8, 128) tiling rule holds at
any head count; query positions and block tables live in SMEM (scalar
prefetch). `tests/test_tpu_compile.py` compiles both kernels for a
described v5e at granite widths; the reduced test shapes run under
interpret=True.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask(kpos, qpos, window: int):
    """Position mask law shared with `models.layers.attend`: a key is
    visible when its slot holds a position (>= 0) at or before the query,
    and inside the sliding window when one is set."""
    mask = (kpos >= 0) & (kpos <= qpos)
    if window:
        mask = mask & (kpos > qpos - window)
    return mask


def _flash_accumulate(s, ns, q_ref, k_ref, v_ref, mask, o_ref, m_ref, l_ref,
                      acc_ref):
    """One kv tile of the online-softmax accumulate for every kv head,
    shared by the contiguous and paged decode kernels (which differ only
    in how the tile is addressed and masked).

    q_ref: (1, KV, G, hd); k_ref/v_ref: (1, BS, KV, hd) — the tile holds
    every kv head, so its minor two axes (KV, hd) are the array's own and
    the TPU tiling rule holds at any head count; mask: (1, BS) bool.
    Initializes the VMEM scratch on the first tile and writes o_ref on the
    last."""
    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hd = q_ref.shape[-1]
    for h in range(k_ref.shape[2]):
        q = q_ref[0, h].astype(jnp.float32)              # (G, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)        # (BS, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)        # (BS, hd)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / math.sqrt(hd)  # (G, BS)
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_ref[h]                                # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                      # (G, BS)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(s == ns - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _scratch(KV: int, G: int, hd: int):
    return [
        pltpu.VMEM((KV, G, 1), jnp.float32),    # running max m
        pltpu.VMEM((KV, G, 1), jnp.float32),    # running denom l
        pltpu.VMEM((KV, G, hd), jnp.float32),   # weighted-value accumulator
    ]


def _kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, window: int, seq_len: int):
    b = pl.program_id(0)
    s = pl.program_id(1)
    kpos = kpos_ref[0]                                   # (1, BS)
    mask = _mask(kpos, qpos_ref[b], window)
    bs = kpos.shape[-1]
    if seq_len % bs:
        # ragged last tile: slots past S hold whatever the edge block read
        col = s * bs + jax.lax.broadcasted_iota(jnp.int32, kpos.shape, 1)
        mask = mask & (col < seq_len)
    _flash_accumulate(s, pl.num_programs(1), q_ref, k_ref, v_ref, mask,
                      o_ref, m_ref, l_ref, acc_ref)


def decode_attention_kernel(q, k, v, q_pos, k_pos, *, window: int = 0,
                            block_s: int = 128, interpret: bool = True):
    """q: (B, H, hd); k/v: (B, S, KV, hd); q_pos: (B,); k_pos: (B, S).

    Grid = (batch, kv_block); `q_pos` is a scalar-prefetch (SMEM) operand
    and `k_pos` is viewed as (B, 1, S) so its (1, bs) tile spans the
    array's own unit axis. On TPU, bs must be a multiple of 128 or S.
    Returns (B, H, hd)."""
    B, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    bs = min(block_s, S)
    ns = -(-S // bs)
    qr = q.reshape(B, KV, G, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, ns),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, s, qp: (b, 0, 0, 0)),  # q
            pl.BlockSpec((1, bs, KV, hd), lambda b, s, qp: (b, s, 0, 0)),  # k
            pl.BlockSpec((1, bs, KV, hd), lambda b, s, qp: (b, s, 0, 0)),  # v
            pl.BlockSpec((1, 1, bs), lambda b, s, qp: (b, 0, s)),      # kpos
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), lambda b, s, qp: (b, 0, 0, 0)),
        scratch_shapes=_scratch(KV, G, hd),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, seq_len=S),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), qr, k, v,
      k_pos.astype(jnp.int32).reshape(B, 1, S))
    return out.reshape(B, H, hd)


def _paged_kernel(lyr_ref, tbl_ref, qpos_ref, q_ref, k_ref, v_ref, kpos_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, window: int):
    b = pl.program_id(0)
    s = pl.program_id(1)
    # unallocated table slots (-1) were clamped to physical block 0 by the
    # index map; a query limit of -1 masks the whole tile so block 0's real
    # tenant is invisible
    qlim = jnp.where(tbl_ref[b, s] >= 0, qpos_ref[b], -1)
    mask = _mask(kpos_ref[0], qlim, window)              # (1, BS)
    _flash_accumulate(s, pl.num_programs(1), q_ref, k_ref, v_ref, mask,
                      o_ref, m_ref, l_ref, acc_ref)


def paged_decode_attention_kernel(q, k_pool, v_pool, q_pos, kpos_pool,
                                  tables, layer, *, window: int = 0,
                                  interpret: bool = True):
    """Paged flash decode (DESIGN §9).

    q: (B, H, hd); k_pool/v_pool: (L, NB, bs, KV, hd) stacked physical
    pools, read at `layer` (an int32 scalar, traced inside the model's
    layer loop); q_pos: (B,); kpos_pool: (NB, bs) absolute positions
    (-1 = empty), shared by every layer; tables: (B, MB) physical block
    ids per request (-1 = unallocated).

    Grid = (batch, table_slot): the innermost axis walks the block TABLE,
    not physical memory — `layer`, `tables` and `q_pos` ride in as
    scalar-prefetch operands so the k/v BlockSpec index maps resolve
    (layer, tables[b, s]) to the block to stream straight out of the
    stacked pool, with no per-layer slice of it. Each step streams one
    block of every kv head; `kpos_pool` is viewed as (NB, 1, bs) so its
    tile spans the array's own unit axis. Returns (B, H, hd)."""
    B, H, hd = q.shape
    _, NB, bs, KV, _ = k_pool.shape
    MB = tables.shape[1]
    G = H // KV
    qr = q.reshape(B, KV, G, hd)

    def pool_map(b, s, lyr, t, qp):
        return (lyr[0], jnp.maximum(t[b, s], 0), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd),
                         lambda b, s, lyr, t, qp: (b, 0, 0, 0)),          # q
            pl.BlockSpec((None, 1, bs, KV, hd), pool_map),                # k
            pl.BlockSpec((None, 1, bs, KV, hd), pool_map),                # v
            pl.BlockSpec((1, 1, bs), lambda b, s, lyr, t, qp: (
                jnp.maximum(t[b, s], 0), 0, 0)),                          # kpos
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd),
                               lambda b, s, lyr, t, qp: (b, 0, 0, 0)),
        scratch_shapes=_scratch(KV, G, hd),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
      q_pos.astype(jnp.int32), qr, k_pool, v_pool,
      kpos_pool.astype(jnp.int32).reshape(NB, 1, bs))
    return out.reshape(B, H, hd)
