"""jit'd dispatch wrappers for the Pallas kernels.

On the CPU backend kernels run under interpret=True; on every other backend
they lower natively, never interpreted. `use_kernel=False` routes to the
pure-jnp oracle — the serving and training stacks call these entry points
so the backend is a config switch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention_kernel,
                                            paged_decode_attention_kernel)
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.rglru_scan import rglru_scan_kernel
from repro.kernels.rmsnorm import rmsnorm_kernel
from repro.kernels.ssd_scan import ssd_intra_kernel


def _interpret() -> bool:
    """Interpret mode is the CPU backend's stand-in for Mosaic; a TPU
    always compiles the kernel natively."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("window", "use_kernel", "block_s"))
def decode_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                     use_kernel: bool = True, block_s: int = 128):
    if not use_kernel:
        return ref.decode_attention_ref(q, k, v, q_pos, k_pos, window=window)
    return decode_attention_kernel(q, k, v, q_pos, k_pos, window=window,
                                   block_s=block_s, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "use_kernel",
                                             "scale", "latent_v"))
def paged_decode_attention(q, k_pool, v_pool, q_pos, kpos_pool, tables, layer,
                           *, window: int = 0, use_kernel: bool = True,
                           scale=None, latent_v: int = 0):
    """Flash decode through the stacked paged KV pools + block tables at
    one layer (DESIGN §9); with `latent_v`, through the MLA latent pool
    (v_pool None, DESIGN §17). `scale`: softmax scale, None =
    1/sqrt(head dim)."""
    if not use_kernel:
        return ref.paged_decode_attention_ref(q, k_pool, v_pool, q_pos,
                                              kpos_pool, tables, layer,
                                              window=window, scale=scale,
                                              latent_v=latent_v)
    return paged_decode_attention_kernel(q, k_pool, v_pool, q_pos, kpos_pool,
                                         tables, layer, window=window,
                                         scale=scale, latent_v=latent_v,
                                         interpret=_interpret())


def paged_decode_attention_tp(q, k_pool, v_pool, q_pos, kpos_pool, tables,
                              layer, *, mesh, window: int = 0,
                              use_kernel: bool = True):
    """Tensor-parallel paged flash decode via shard_map (DESIGN §12).

    The paged kernel's per-kv-head work is fully independent, so TP is a
    shard_map over the "model" axis: each shard streams its kv-head slice
    of the stacked (L, NB, bs, KV, hd) K/V pools against its q-head slice
    (heads are kv-major, so H/m q-heads pair with KV/m kv-heads), with the
    layer index, block table and pos map replicated. No collective runs
    inside the kernel, which keeps shard outputs bitwise identical to the
    single-device kernel. Requires KV % model_axis == 0 — head_dim
    sharding would split the softmax contraction and is storage-only
    (callers fall back to the gathered single-device path)."""
    from jax.sharding import PartitionSpec as P

    KV = k_pool.shape[3]
    m = int(mesh.shape["model"])
    if KV % m != 0:
        raise ValueError(f"kv heads {KV} not divisible by model axis {m}")

    def local(q, kp, vp, qp, pp, tb, lyr):
        if not use_kernel:
            return ref.paged_decode_attention_ref(q, kp, vp, qp, pp, tb, lyr,
                                                  window=window)
        return paged_decode_attention_kernel(q, kp, vp, qp, pp, tb, lyr,
                                             window=window,
                                             interpret=_interpret())

    head_spec = P(None, "model", None)
    pool_spec = P(None, None, None, "model", None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(head_spec, pool_spec, pool_spec, P(None), P(None, None),
                  P(None, None), P()),
        out_specs=head_spec, check_vma=False,
    )(q, k_pool, v_pool, q_pos, kpos_pool, tables,
      jnp.asarray(layer, jnp.int32))


@functools.partial(jax.jit, static_argnames=("window", "causal", "use_kernel",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                    causal: bool = True, use_kernel: bool = True,
                    block_q: int = 128, block_k: int = 128):
    if not use_kernel:
        return ref.flash_attention_ref(q, k, v, q_pos, k_pos, window=window,
                                       causal=causal)
    return flash_attention_kernel(q, k, v, q_pos, k_pos, window=window,
                                  causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def ssd_intra(xdt, cum_a, Br, Cr, *, use_kernel: bool = True):
    if not use_kernel:
        return ref.ssd_intra_ref(xdt, cum_a, Br, Cr)
    return ssd_intra_kernel(xdt, cum_a, Br, Cr, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("use_kernel", "block_w"))
def rglru_scan(a, bx, h0, *, use_kernel: bool = True, block_w: int = 128):
    if not use_kernel:
        return ref.rglru_scan_ref(a, bx, h0)
    return rglru_scan_kernel(a, bx, h0, block_w=block_w,
                             interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eps", "use_kernel",
                                             "block_rows"))
def rmsnorm(x, w, *, eps: float = 1e-6, use_kernel: bool = True,
            block_rows: int = 128):
    if not use_kernel:
        return ref.rmsnorm_ref(x, w, eps=eps)
    return rmsnorm_kernel(x, w, eps=eps, block_rows=block_rows,
                          interpret=_interpret())
