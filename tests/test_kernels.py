"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,hd,S,bs", [
    (2, 8, 2, 32, 64, 32),
    (1, 4, 4, 16, 128, 128),   # MHA-style, single block
    (3, 8, 1, 64, 96, 32),     # MQA, ragged block count
])
def test_decode_attention_sweep(B, H, KV, hd, S, bs, dtype):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, H, hd), dtype)
    k = rand(ks[1], (B, S, KV, hd), dtype)
    v = rand(ks[2], (B, S, KV, hd), dtype)
    q_pos = jnp.array([S - 1, S // 2, 3][:B], jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    k_pos = jnp.where(k_pos <= q_pos[:, None], k_pos, -1)
    out = ops.decode_attention(q, k, v, q_pos, k_pos, block_s=bs)
    want = ref.decode_attention_ref(q, k, v, q_pos, k_pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_decode_attention_ring_buffer_semantics():
    """Positions not slot order decide masking — emulate a wrapped ring."""
    B, H, KV, hd, S = 1, 4, 1, 16, 8
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, KV, hd), jnp.float32)
    v = rand(ks[2], (B, S, KV, hd), jnp.float32)
    # ring: slots hold positions 8..15 wrapped (slot i has pos 8+((i+3) % 8))
    k_pos = jnp.array([[11, 12, 13, 14, 15, 8, 9, 10]], jnp.int32)
    q_pos = jnp.array([15], jnp.int32)
    out = ops.decode_attention(q, k, v, q_pos, k_pos, window=4, block_s=4)
    want = ref.decode_attention_ref(q, k, v, q_pos, k_pos, window=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention (prefill)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Tq,Tk,H,KV,hd,bq,bk,window,causal", [
    (64, 64, 8, 4, 32, 32, 32, 0, True),
    (32, 96, 4, 1, 16, 16, 32, 0, True),    # chunk continuing a cache
    (64, 64, 4, 4, 32, 64, 64, 16, True),   # sliding window
    (32, 32, 8, 2, 16, 32, 32, 0, False),   # bidirectional (encoder)
])
def test_flash_attention_sweep(Tq, Tk, H, KV, hd, bq, bk, window, causal,
                               dtype):
    B = 2
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, Tq, H, hd), dtype)
    k = rand(ks[1], (B, Tk, KV, hd), dtype)
    v = rand(ks[2], (B, Tk, KV, hd), dtype)
    off = Tk - Tq
    qp = jnp.broadcast_to(off + jnp.arange(Tq, dtype=jnp.int32)[None], (B, Tq))
    kp = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32)[None], (B, Tk))
    out = ops.flash_attention(q, k, v, qp, kp, window=window, causal=causal,
                              block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, qp, kp, window=window,
                                   causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


# ---------------------------------------------------------------------------
# SSD intra-chunk


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,nc,Q,H,P,N", [
    (2, 3, 16, 4, 8, 12),
    (1, 1, 64, 2, 32, 16),
    (2, 4, 8, 8, 16, 8),
])
def test_ssd_intra_sweep(B, nc, Q, H, P, N, dtype):
    ks = jax.random.split(KEY, 4)
    xdt = rand(ks[0], (B, nc, Q, H, P), dtype)
    cum_a = -jnp.abs(rand(ks[1], (B, nc, Q, H), jnp.float32)).cumsum(axis=2)
    Br = rand(ks[2], (B, nc, Q, N), dtype)
    Cr = rand(ks[3], (B, nc, Q, N), dtype)
    y, s = ops.ssd_intra(xdt, cum_a, Br, Cr)
    yr, sr = ref.ssd_intra_ref(xdt, cum_a, Br, Cr)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), **tol(dtype))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), **tol(dtype))


# ---------------------------------------------------------------------------
# RG-LRU scan


@pytest.mark.parametrize("B,T,W,bw", [
    (2, 32, 256, 128),
    (1, 128, 128, 128),
    (4, 16, 512, 64),
])
def test_rglru_scan_sweep(B, T, W, bw):
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(rand(ks[0], (B, T, W), jnp.float32))
    bx = rand(ks[1], (B, T, W), jnp.float32)
    h0 = rand(ks[2], (B, W), jnp.float32)
    y, hT = ops.rglru_scan(a, bx, h0, block_w=bw)
    yr, hTr = ref.rglru_scan_ref(a, bx, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTr), rtol=1e-5,
                               atol=1e-5)


def test_decode_attention_matches_model_semantics():
    """Kernel mask law == models.layers.attend mask law (same positions)."""
    from repro.models.layers import attend
    B, H, KV, hd, S = 2, 4, 2, 16, 32
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, KV, hd), jnp.float32)
    v = rand(ks[2], (B, S, KV, hd), jnp.float32)
    q_pos = jnp.array([20, 7], jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    k_pos = jnp.where(k_pos <= q_pos[:, None], k_pos, -1)
    out = ops.decode_attention(q, k, v, q_pos, k_pos, block_s=8)
    want = attend(q[:, None], k, v, q_pos[:, None], k_pos).reshape(B, H, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# fused RMSNorm


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,br", [
    ((2, 32, 128), 16),
    ((4, 7, 256), 128),     # rows not a block multiple (pad path)
    ((1, 1, 64), 8),
])
def test_rmsnorm_sweep(shape, br, dtype):
    ks = jax.random.split(KEY, 2)
    x = rand(ks[0], shape, dtype)
    w = rand(ks[1], (shape[-1],), jnp.float32) * 0.1
    out = ops.rmsnorm(x, w, block_rows=br)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_rmsnorm_matches_model_layer():
    from repro.models.layers import rms_norm
    x = rand(KEY, (2, 8, 96), jnp.float32)
    w = rand(jax.random.fold_in(KEY, 1), (96,), jnp.float32) * 0.1
    np.testing.assert_allclose(
        np.asarray(ops.rmsnorm(x, w)), np.asarray(rms_norm(x, w)),
        rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention: the latent (MLA) mode and the GQA kernel's output


def _paged_case(key, B, H, KV, hd, L, NB, bs, dtype, latent=False):
    """A stacked paged pool at layer 1, two ragged rows through block
    tables with -1 padding (KV/hd are the latent pool's lanes when
    `latent`)."""
    ks = jax.random.split(key, 3)
    q = rand(ks[0], (B, H, hd), dtype)
    pool = (L, NB, bs, hd) if latent else (L, NB, bs, KV, hd)
    kp = rand(ks[1], pool, dtype)
    vp = None if latent else rand(ks[2], pool, dtype)
    tables = jnp.array([[3, 5, -1, -1], [0, 7, 2, -1]][:B], jnp.int32)
    kpos = jnp.full((NB, bs), -1, jnp.int32)
    for b, (blocks, n) in enumerate([([3, 5], 25), ([0, 7, 2], 40)][:B]):
        for j, blk in enumerate(blocks):
            p = np.arange(j * bs, (j + 1) * bs)
            kpos = kpos.at[blk].set(jnp.where(p < n, p, -1))
    qpos = jnp.array([24, 39][:B], jnp.int32)
    return q, kp, vp, qpos, kpos, tables


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("H,C,dv,scale", [
    (4, 128, 96, 0.3),          # reduced widths: 40 lanes padded to 128
    (64, 640, 512, 0.1309),     # Kimi-K2: 576 lanes padded to 640, 64 heads
])
def test_latent_paged_decode_matches_ref(dtype, H, C, dv, scale):
    """The paged kernel's latent mode (DESIGN §17) against
    `ref.paged_decode_attention_ref`: one C-wide latent head shared by
    every query head, V the first `dv` lanes of the same block."""
    q, kp, _, qpos, kpos, tables = _paged_case(
        jax.random.PRNGKey(3), 2, H, 1, C, 2, 8, 16, dtype, latent=True)
    out = ops.paged_decode_attention(q, kp, None, qpos, kpos, tables, 1,
                                     scale=scale, latent_v=dv)
    want = ref.paged_decode_attention_ref(q, kp, None, qpos, kpos, tables, 1,
                                          scale=scale, latent_v=dv)
    assert out.shape == (2, H, dv)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_granite_paged_decode_output_unchanged():
    """The GQA paged kernel at granite-3-8b's head widths (32 q / 8 kv
    heads of 128), interpret mode, bf16: its output is bitwise what the
    kernel gave before it gained the latent mode and a softmax scale
    (sha256 recorded from that kernel on this input)."""
    import hashlib
    case = _paged_case(jax.random.PRNGKey(11), 2, 32, 8, 128, 2, 8, 16,
                       jnp.bfloat16)
    out = np.asarray(ops.paged_decode_attention(*case, 1).astype(
        jnp.float32))
    assert hashlib.sha256(out.tobytes()).hexdigest() == \
        "9b94f4e5dd42e71f6d08adbebc57870ab479c129ea4f161421442be3c03bd3a3"
