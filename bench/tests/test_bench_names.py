"""The names the trace reduction finds the program by
(`trace.PROGRAMS`, `trace.KERNELS`) are the names the engine's own
programs carry: its jitted paged decode step, compiled for a described
TPU v5e at granite-3-8b widths (two layers), is `jit__decode_paged_fn`
and holds the Pallas kernel as `paged_decode_attention`; its jitted
paged prefill step is `jit__prefill_paged_fn`. Nothing runs. The topology
is described inside a module fixture, so only the worker that runs this
file loads the TPU library."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench.harness import trace as T
from repro.config.base import ServeConfig
from repro.config.registry import get_config
from repro.kernels import ops
from repro.models import layers
from repro.models.model import build_model
from repro.serving.engine import Engine

ROWS, BLOCK, POOL_BLOCKS, MAX_CONTEXT = 8, 16, 64, 256


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def engine(monkeypatch):
    """A two-layer granite engine at published widths; its parameters
    are never made (only the step programs are compiled)."""
    monkeypatch.setattr(layers, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("granite-3-8b", "full"),
                              num_layers=2)
    model = build_model(cfg, dtype=jnp.bfloat16)
    serve = ServeConfig(policy="static", b_max=ROWS, paged_kv=True,
                        chunked_prefill=True, n_prefill_lanes=2,
                        block_size=BLOCK,
                        kv_pool_tokens=POOL_BLOCKS * BLOCK)
    return Engine(model, None, serve, max_context=MAX_CONTEXT,
                  buckets=(ROWS,), prefill_chunk=16)


def _args(sharding, eng, rows, tokens):
    put = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=sharding)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=sharding)
    params = jax.tree.map(put, eng.model.init_shapes())
    cache = jax.tree.map(put, eng.cache)
    tok = i32(rows, tokens) if tokens else i32(rows)
    return params, tok, tok, i32(rows, eng.max_blocks), i32(rows), cache


def test_decode_step_and_kernel_names(one_chip, engine):
    text = engine._decode_paged_jit.lower(
        *_args(one_chip, engine, ROWS, 0)).compile().as_text()
    module = re.match(r"HloModule (\S+?),", text).group(1)
    assert T.program_name(module) in T.PROGRAMS["decode"]
    calls = [T.op_name(line.strip()) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # the layers share one loop body, so one call site
    assert calls == [T.KERNELS["paged_decode"][0]], calls


def test_prefill_step_name(one_chip, engine):
    lowered = engine._prefill_paged_jit.lower(
        *_args(one_chip, engine, 2, 16), None)
    module = re.search(r"module @(\S+)", lowered.as_text()).group(1)
    assert T.program_name(module) in T.PROGRAMS["prefill"]
