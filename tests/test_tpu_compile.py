"""The serving path's Pallas kernels compile for a TPU v5e at granite widths.

Compiled here, without a chip, for a described `v5e:2x2` topology: the TPU
compiler refuses what interpret mode accepts (blocks off the (8, 128)
tiling, too much VMEM). Nothing runs; each test asserts the Mosaic kernel
(`tpu_custom_call`) is in the compiled program. The topology is described
inside a module fixture, so only the worker that runs this file loads the
TPU library.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config.registry import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import (decode_attention_kernel,
                                            paged_decode_attention_kernel)
from repro.models import layers
from repro.models.model import build_model

# granite-3-8b attention widths, a 2048-token context in 16-token blocks
H, KV, HD = 32, 8, 128
BLOCK, MAX_BLOCKS, NUM_BLOCKS = 16, 128, 1024
SEQ = BLOCK * MAX_BLOCKS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs cannot be read back from the persistent
    # cache, only written: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("batch", [8, 16])
def test_paged_decode_kernel_compiles(one_chip, batch):
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = (2, NUM_BLOCKS, BLOCK, KV, HD)
    text = _compiled_text(
        lambda q, k, v, qp, kp, t, lyr: paged_decode_attention_kernel(
            q, k, v, qp, kp, t, lyr, interpret=False),
        _spec(one_chip, (batch, H, HD), bf), _spec(one_chip, pool, bf),
        _spec(one_chip, pool, bf), _spec(one_chip, (batch,), i32),
        _spec(one_chip, (NUM_BLOCKS, BLOCK), i32),
        _spec(one_chip, (batch, MAX_BLOCKS), i32), _spec(one_chip, (), i32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("layers_in_stack", [1, 20])
def test_tp_paged_decode_kernel_compiles_on_four_chips(topo, monkeypatch,
                                                      layers_in_stack):
    """The shard_map'd paged kernel for a (1, 4) serving mesh over the
    described chips: stacked pools split on kv heads, the layer index and
    tables replicated. Each chip runs the Mosaic kernel on its own heads;
    no collective moves the pool."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    bf, i32, batch = jnp.bfloat16, jnp.int32, 8
    pool = (layers_in_stack, NUM_BLOCKS, BLOCK, KV, HD)

    def on(spec, shape, dtype):
        return _spec(NamedSharding(mesh, spec), shape, dtype)

    pool_spec = P(None, None, None, "model", None)
    text = _compiled_text(
        lambda q, k, v, qp, kp, t, lyr: ops.paged_decode_attention_tp(
            q, k, v, qp, kp, t, lyr, mesh=mesh),
        on(P(None, "model", None), (batch, H, HD), bf),
        on(pool_spec, pool, bf), on(pool_spec, pool, bf),
        on(P(None), (batch,), i32), on(P(None, None), (NUM_BLOCKS, BLOCK), i32),
        on(P(None, None), (batch, MAX_BLOCKS), i32), on(P(), (), i32))
    assert "tpu_custom_call" in text
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective


@pytest.mark.parametrize("batch,seq,window", [(8, SEQ, 0), (16, SEQ, 0),
                                              (8, 200, 100)])
def test_contiguous_decode_kernel_compiles(one_chip, batch, seq, window):
    """Full-context rows, plus a ragged sliding-window row (S not a
    multiple of the 128-slot block)."""
    bf, i32 = jnp.bfloat16, jnp.int32
    kv = (batch, seq, KV, HD)
    text = _compiled_text(
        lambda q, k, v, qp, kp: decode_attention_kernel(
            q, k, v, qp, kp, window=window, interpret=False),
        _spec(one_chip, (batch, H, HD), bf), _spec(one_chip, kv, bf),
        _spec(one_chip, kv, bf), _spec(one_chip, (batch,), i32),
        _spec(one_chip, (batch, seq), i32))
    assert "tpu_custom_call" in text


def test_paged_decode_step_compiles_at_full_width(one_chip, monkeypatch):
    """One paged decode step of granite-3-8b at every published width, cut
    to 2 layers, from `eval_shape` shapes. The model's own routing picks
    the kernel on a TPU; steer it here, since this process's backend is
    the CPU."""
    monkeypatch.setattr(layers, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("granite-3-8b", "full"),
                              num_layers=2)
    model = build_model(cfg, dtype=jnp.bfloat16)
    batch = 8

    def placed(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = placed(model.init_shapes())
    cache = placed(jax.eval_shape(
        lambda: model.init_paged_cache(batch, NUM_BLOCKS, BLOCK)))
    i32 = jnp.int32
    text = _compiled_text(
        model.decode_step_paged, params, _spec(one_chip, (batch,), i32),
        _spec(one_chip, (batch,), i32),
        _spec(one_chip, (batch, MAX_BLOCKS), i32), cache)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind,rows,tokens", [("decode", 8, 1),
                                              ("prefill", 2, 16)])
def test_paged_step_temp_under_one_layer_pool(one_chip, monkeypatch, kind,
                                              rows, tokens):
    """The paged steps address the stacked K/V pool in place (DESIGN §9):
    mistral-nemo-12b at every published width, 10 layers, a 131072-token
    pool and 8192-token tables, cache donated. A step that copied a
    layer's pool out of the stack would need at least that pool, 268 MB,
    of temporary memory; the in-place steps need under it, and the
    pools stay aliased to the donated input."""
    monkeypatch.setattr(layers, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("mistral-nemo-12b", "full"),
                              num_layers=10)
    model = build_model(cfg, dtype=jnp.bfloat16)
    pool_tokens, block, context = 131072, 16, 8192

    def placed(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = placed(model.init_shapes())
    cache = placed(jax.eval_shape(lambda: model.init_paged_cache(
        rows, pool_tokens // block, block)))
    i32 = jnp.int32
    tables = _spec(one_chip, (rows, context // block), i32)
    if kind == "decode":
        fn = model.decode_step_paged
        toks = pos = _spec(one_chip, (rows,), i32)
    else:
        fn = model.prefill_paged
        toks = pos = _spec(one_chip, (rows, tokens), i32)
    compiled = jax.jit(fn, donate_argnums=(4,)).lower(
        params, toks, pos, tables, cache).compile()
    mem = compiled.memory_analysis()
    layer_pool = cache["k"].size // cfg.num_layers * 2     # bf16 bytes
    assert mem.temp_size_in_bytes < layer_pool, mem.temp_size_in_bytes
    pools = (cache["k"].size + cache["v"].size) * 2
    assert mem.alias_size_in_bytes >= pools
