"""Sharding rules: FSDP(data[,pod]) x tensor(model) x expert parallelism.

Logical mapping (DESIGN §5):
  * up-projections  (d -> heads/ffn/experts): in-dim over FSDP axes,
    out-dim over "model"
  * down-projections (heads/ffn -> d): in-dim over "model" (activations
    already model-sharded; XLA inserts the all-reduce), out-dim over FSDP
  * MoE experts: expert axis over "model" (expert parallelism), d over FSDP
  * KV caches: batch over FSDP axes; kv-heads (or head_dim when kv < 16)
    over "model"; batch=1 long-context decode sequence-shards the cache
  * small/1-D tensors replicated

Rules are name-based over the param pytree paths, so every architecture
family resolves through one table.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config.base import ArchFamily, InputShape, ModelConfig


import os


def fsdp_axes(mesh: Mesh):
    """Axes that shard parameters/optimizer state.

    REPRO_POD_MODE=dp keeps FSDP within a pod and makes the pod axis pure
    data parallelism (params replicated per pod, gradient all-reduce across
    pods) — §Perf iteration I: cheaper steady-state collectives when params
    fit per pod, at 2x parameter memory.
    """
    names = mesh.axis_names
    if "pod" in names and os.environ.get("REPRO_POD_MODE", "fsdp") != "dp":
        return ("pod", "data")
    return ("data",)


def data_axes(mesh: Mesh):
    """Axes that shard the batch — always include the pod axis."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def _spec_for(name: str, ndim: int, cfg: ModelConfig, fsdp) -> P:
    """PartitionSpec for one (stacked) parameter leaf."""
    f = fsdp if len(fsdp) > 1 else fsdp[0]

    def stacked(*dims):  # prepend the layer-stack axis when present
        return P(*( (None,) * (ndim - len(dims)) + dims ))

    # --- embeddings / head ---------------------------------------------
    # vocab over model, d REPLICATED: sharding d (the head's contracting
    # dim) over data made every (B,T,V) logits tensor a partial sum that
    # XLA all-reduced at full size — 250 GiB/step on a 256k vocab
    # (§Perf iteration F)
    if name.endswith("embed"):
        return P("model", None)                   # (V, d)
    if name.endswith("lm_head"):
        return P(None, "model")                   # (d, V)
    # --- MoE ------------------------------------------------------------
    if "/moe/" in name or name.startswith("moe/"):
        if "router" in name:
            return stacked(f, None)               # (L, d, E)
        if "w_down" in name and "shared" not in name:
            return stacked("model", None, f)      # (L, E, f_e, d)
        if ("w_gate" in name or "w_up" in name) and "shared" not in name:
            return stacked("model", f, None)      # (L, E, d, f_e)
        # shared expert = plain mlp rules below
    # --- attention -------------------------------------------------------
    if name.endswith("attn/wq") or name.endswith("attn/wk") \
            or name.endswith("attn/wv"):
        return stacked(f, "model")                # (L, d, out)
    if name.endswith("attn/wo"):
        return stacked("model", f)                # (L, H*hd, d)
    if name.endswith("attn/bq") or name.endswith("attn/bk") \
            or name.endswith("attn/bv"):
        return stacked("model")
    # --- mlp --------------------------------------------------------------
    if name.endswith("w_gate") or name.endswith("w_up"):
        return stacked(f, "model")
    if name.endswith("w_down"):
        return stacked("model", f)
    # --- mamba2 -------------------------------------------------------------
    if name.endswith("mixer/in_proj"):
        return stacked(f, "model")                # (L, d, d_proj)
    if name.endswith("mixer/out_proj"):
        return stacked("model", f)                # (L, d_in, d)
    if name.endswith("mixer/conv_w"):
        return stacked(None, "model")             # (L, W, ch)
    if name.endswith("mixer/conv_b") or name.endswith("mixer/norm_w"):
        return stacked("model")
    if name.endswith("dt_bias") or name.endswith("A_log") \
            or name.endswith("mixer/D"):
        return stacked(None)                      # (L, H): H=80 not 16-divisible
    # --- RG-LRU ----------------------------------------------------------------
    if name.endswith("rec/w_x") or name.endswith("rec/w_gate_branch"):
        return stacked(f, "model")                # (L, d, w)
    if name.endswith("rec/w_out"):
        return stacked("model", f)                # (L, w, d)
    if name.endswith("rec/w_a") or name.endswith("rec/w_i"):
        return stacked(None, "model")             # (L, w, w)
    if name.endswith("rec/conv_w"):
        return stacked(None, "model")
    if name.endswith("rec/conv_b") or name.endswith("rec/b_a") \
            or name.endswith("rec/b_i") or name.endswith("rec/lam"):
        return stacked("model")
    # --- norms, gates, everything 1-D-ish: replicate --------------------------
    return P()


def param_shardings(params_shape, cfg: ModelConfig, mesh: Mesh):
    """NamedSharding pytree matching a params (shape) pytree."""
    f = fsdp_axes(mesh)
    # NOTE (§Perf iteration H, REFUTED): when num_heads doesn't divide the
    # model axis (starcoder2: 36 heads / 16 ranks) the flat (H*hd)
    # projection shards across head boundaries and GSPMD all-reduces full
    # (B,H,T,T) attention scores (3 x 144 GiB on train_4k). Forcing
    # attention replication over "model" removes the all-reduce but
    # multiplies the attention memory term ~3x (score temps unsharded) —
    # measured strictly worse. Proper fix is a TP degree that divides the
    # head count (mesh choice) or padding heads; kept as deployment
    # guidance, not forced here.

    def one(path, leaf):
        name = _path_str(path)
        spec = _spec_for(name, leaf.ndim, cfg, f)
        spec = _validate(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def _validate(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop axis assignments that don't divide the dim (e.g. 36 heads % 16)."""
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= _axis_size(mesh, a)
        out.append(ax if dim % size == 0 else None)
    return P(*out)


# ---------------------------------------------------------------------------
# activations / caches


def batch_spec(mesh: Mesh) -> P:
    f = data_axes(mesh)
    return P(f if len(f) > 1 else f[0])


def batch_shardings(batch_shape: Dict[str, Any], cfg: ModelConfig,
                    mesh: Mesh):
    """Shard every batch leaf's leading (batch) dim over the FSDP axes."""
    bs = batch_spec(mesh)

    def one(leaf):
        spec = P(*(tuple(bs) + (None,) * (leaf.ndim - 1)))
        spec = _validate(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree.map(one, batch_shape)


def kv_head_axes(mesh: Mesh, kv: int, hd: int):
    """Which cache axis takes "model": kv-heads when they divide the axis,
    head_dim as the fallback, else replicate (DESIGN §5; the same rule
    `core.memory_model.kv_shard_factor` applies jax-free)."""
    m = _axis_size(mesh, "model")
    if kv % m == 0:
        return "model", None
    if hd % m == 0:
        return None, "model"
    return None, None


def cache_shardings(cache_shape, cfg: ModelConfig, mesh: Mesh,
                    seq_shard: bool = False):
    """KV/state cache shardings.

    Default: batch over FSDP, kv-heads (or head_dim fallback) over "model".
    seq_shard=True (batch=1 long-context decode): the cache sequence axis is
    sharded over "data" instead — distributed flash-decode.
    """
    f = data_axes(mesh)
    fs = f if len(f) > 1 else f[0]

    def one(path, leaf):
        name = _path_str(path)
        shape = leaf.shape
        if name in ("k", "v", "cross_k", "cross_v"):
            # (L, B, S, KV, hd)
            kv_ax, hd_ax = kv_head_axes(mesh, shape[3], shape[4])
            if seq_shard and name in ("k", "v"):
                spec = P(None, None, "data", kv_ax, hd_ax)
            else:
                spec = P(None, fs, None, kv_ax, hd_ax)
        elif name == "pos":
            spec = P(None, "data") if seq_shard else P(fs, None)
        elif name == "conv":                       # (L, B, W-1, ch)
            spec = P(None, None if seq_shard else fs, None, "model")
        elif name == "rec":                        # (L, B, w)
            spec = P(None, None if seq_shard else fs, "model")
        elif name == "ssm":                        # (L, B, H, P, N)
            spec = P(None, None if seq_shard else fs, None, None, None)
        else:
            spec = P()
        spec = _validate(spec, shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def maybe_constrain(x, *spec):
    """with_sharding_constraint that no-ops outside a mesh context and drops
    axes the ambient mesh doesn't have — lets model code carry sharding
    hints without binding to a mesh (single-device tests unaffected)."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return x
    names = set(m.axis_names)
    clean = []
    for ax in spec:
        if ax is None:
            clean.append(None)
        elif isinstance(ax, tuple):
            keep = tuple(a for a in ax if a in names)
            clean.append(keep if keep else None)
        else:
            clean.append(ax if ax in names else None)
    # drop axes that don't divide the dim
    final = []
    for dim, ax in zip(x.shape, clean):
        if ax is None:
            final.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= m.shape[a]
        final.append(ax if dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*final))


def decode_input_shardings(cfg: ModelConfig, mesh: Mesh, batch: int):
    """(tokens (B,), seq_lens (B,)) shardings for serve_step."""
    f = data_axes(mesh)
    fs = f if len(f) > 1 else f[0]
    total = 1
    for a in f:
        total *= _axis_size(mesh, a)
    spec = P(fs) if batch % total == 0 else P()
    return NamedSharding(mesh, spec)


# ---------------------------------------------------------------------------
# mesh-sharded serving (DESIGN §12)


def serve_param_shardings(params, cfg: ModelConfig, mesh: Mesh):
    """Serving twin of `param_shardings`: the same §5 name-based rules,
    with every FSDP/data axis replaced by replication. Serving carries no
    optimizer state, so params replicate over ("pod",) "data" (plain data
    parallelism) and shard over "model" only — tensor parallelism
    (DESIGN §12). Works on concrete params or a shape pytree."""

    def strip(ax):
        if isinstance(ax, tuple):
            keep = tuple(a for a in ax if a == "model")
            return keep[0] if keep else None
        return ax if ax == "model" else None

    def one(path, leaf):
        name = _path_str(path)
        spec = _spec_for(name, leaf.ndim, cfg, ("data",))
        spec = P(*(strip(ax) for ax in spec))
        return NamedSharding(mesh, _validate(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, params)


def serve_cache_shardings(cache, cfg: ModelConfig, mesh: Mesh):
    """Serving-cache shardings over the "model" axis (DESIGN §12).

    Covers both layouts with one rule set — the leading axes differ but
    the trailing (KV, hd) axes are shared:

      * paged pools     k/v (L, NB, bs, KV, hd), pos (NB, bs)
      * contiguous rows k/v (L, B, S, KV, hd),   pos (B, S)
      * cross-KV        (Lc, slots, enc_len, KV, hd)

    K/V shard on kv-heads ("model"), head_dim fallback (`kv_head_axes`);
    the pos map and slot bookkeeping replicate; per-slot recurrent state
    shards on its channel axis when divisible. Batch/block axes stay
    unsharded — serving batches are bucketized and dynamic, so rows
    replicate over "data"."""

    def one(path, leaf):
        name = _path_str(path)
        shape = leaf.shape
        if name in ("k", "v", "cross_k", "cross_v"):
            kv_ax, hd_ax = kv_head_axes(mesh, shape[-2], shape[-1])
            spec = P(*((None,) * (leaf.ndim - 2) + (kv_ax, hd_ax)))
        elif name == "conv":                       # (L, slots, W-1, ch)
            spec = P(None, None, None, "model")
        elif name == "rec":                        # (L, slots, w)
            spec = P(None, None, "model")
        else:                                      # pos / ssm / misc
            spec = P()
        return NamedSharding(mesh, _validate(spec, shape, mesh))

    return jax.tree_util.tree_map_with_path(one, cache)


# The engine's ambient serving mesh (DESIGN §12): set around every jit'd
# serving step so model code (layers.self_attention_paged) can route the
# paged flash-decode kernel through its shard_map wrapper. A module slot,
# not a Mesh context: training meshes must NOT trigger the serving path.
_SERVING_MESH = None


def set_serving_mesh(mesh):
    """Install `mesh` as the ambient serving mesh; returns the previous
    value so callers can restore it (engines with and without a mesh can
    interleave in one process)."""
    global _SERVING_MESH
    prev = _SERVING_MESH
    _SERVING_MESH = mesh
    return prev


def serving_mesh():
    return _SERVING_MESH


def serving_model_axis() -> int:
    """Size of the ambient serving mesh's "model" axis (1 = no TP)."""
    m = _SERVING_MESH
    if m is None or "model" not in m.axis_names:
        return 1
    return int(m.shape["model"])
