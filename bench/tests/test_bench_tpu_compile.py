"""The nemo cell's steps compile for a described TPU v5e at the cell's
own sizes: the paged decode step at the 32-row bucket and a two-lane
16-token prefill chunk, both over the 131072-token pool and 8192-token
block tables, at every published width (head_dim 128 != 5120 / 32).
Nothing runs. The topology is described inside a module fixture, so only
the worker that runs this file loads the TPU library."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config.registry import get_config
from repro.kernels import ops
from repro.models import layers
from repro.models.model import build_model

LAYERS, BLOCK, POOL_TOKENS, MAX_CONTEXT = 10, 16, 131072, 8192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _placed(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("kind,rows,tokens", [("decode", 32, 1),
                                              ("prefill", 2, 16)])
def test_nemo_chip_step_compiles(one_chip, monkeypatch, kind, rows, tokens):
    monkeypatch.setattr(layers, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("mistral-nemo-12b", "full"),
                              num_layers=LAYERS)
    model = build_model(cfg, dtype=jnp.bfloat16)
    params = _placed(one_chip, model.init_shapes())
    cache = _placed(one_chip, jax.eval_shape(
        lambda: model.init_paged_cache(rows, POOL_TOKENS // BLOCK, BLOCK)))
    i32 = jnp.int32
    spec = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, i32, sharding=one_chip)
    tables = spec((rows, MAX_CONTEXT // BLOCK))
    if kind == "decode":
        fn = model.decode_step_paged
        args = (params, spec((rows,)), spec((rows,)), tables, cache)
    else:
        fn = model.prefill_paged
        args = (params, spec((rows, tokens)), spec((rows, tokens)), tables,
                cache)
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    print(kind, "args", mem.argument_size_in_bytes, "out",
          mem.output_size_in_bytes, "temp", mem.temp_size_in_bytes)
    if kind == "decode":
        assert "tpu_custom_call" in compiled.as_text()
    # weights and pool must fit one chip's 16 GiB with the step's temps
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2 ** 30
