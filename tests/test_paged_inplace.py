"""The paged layer loop reads and writes the stacked K/V pool in place
(DESIGN §9): no step slices a layer's pool out of the stack or writes one
back, and the result is bitwise the contiguous cache's.

Structure is read from the jaxpr of `decode_step_paged` and
`prefill_paged` at reduced widths, for every family with a paged K/V
pool: dense, MoE, hybrid, VLM and enc-dec; values from a 3-layer model on
the CPU.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_audit import _iter_eqns
from repro.config.registry import get_config
from repro.models import layers
from repro.models.model import build_model

NB, BS, MB = 12, 8, 6          # pool blocks, block size, table width
ROWS, CHUNK = 3, 16
# depth per arch: enough that the stacked pool holds at least two layers
# (recurrentgemma-9b's pattern puts one attention layer in three)
DEPTH = {"recurrentgemma-9b": 6}


def _model(arch, n_layers=3):
    cfg = dataclasses.replace(get_config(arch, "reduced"),
                              num_layers=n_layers)
    return cfg, build_model(cfg, dtype=jnp.float32)


@pytest.mark.parametrize("arch", ["granite-3-8b", "qwen2-moe-a2.7b",
                                  "recurrentgemma-9b", "llama-3.2-vision-90b",
                                  "seamless-m4t-medium"])
@pytest.mark.parametrize("step", ["decode", "decode_kernel", "prefill"])
def test_step_never_slices_the_pool(monkeypatch, arch, step):
    """No dynamic_slice of the stacked pool, no dynamic_update_slice into
    it, and no value of one layer's pool shape (NB, bs, KV, hd), flat or
    not, anywhere in the step."""
    monkeypatch.setattr(layers, "use_pallas", lambda: step == "decode_kernel")
    cfg, model = _model(arch, DEPTH.get(arch, 3))
    params = model.init_shapes()
    cache = jax.eval_shape(lambda: model.init_paged_cache(ROWS, NB, BS))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    if step == "prefill":
        jaxpr = jax.make_jaxpr(model.prefill_paged)(
            params, i32(ROWS, CHUNK), i32(ROWS, CHUNK), i32(ROWS, MB), cache,
            None)
    else:
        jaxpr = jax.make_jaxpr(model.decode_step_paged)(
            params, i32(ROWS), i32(ROWS), i32(ROWS, MB), cache)
    stacked = cache["k"].shape
    tail = stacked[3:]
    pool_shapes = {stacked, (stacked[0] * NB * BS,) + tail}
    layer_shapes = {(NB, BS) + tail, (NB * BS,) + tail}
    seen = set()
    for eqn in _iter_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        seen.add(name)
        ins = {tuple(getattr(v.aval, "shape", ())) for v in eqn.invars}
        outs = {tuple(getattr(v.aval, "shape", ())) for v in eqn.outvars}
        if name in ("dynamic_slice", "dynamic_update_slice", "slice"):
            assert not ins & pool_shapes, (name, ins)
        assert not (ins | outs) & layer_shapes, (name, ins, outs)
    assert "scatter" in seen
    assert ("pallas_call" in seen) == (step == "decode_kernel")


def _laid_out(rows, tables, lens, fill):
    """Lay per-row contiguous values (B, S, ...) out in the pool's layout
    (NB, bs, ...): position p of row b at block tables[b, p // bs], offset
    p % bs; every other slot holds `fill`."""
    pool = np.full((NB, BS) + rows.shape[2:], fill, rows.dtype)
    for b, n in enumerate(lens):
        for p in range(n):
            pool[tables[b, p // BS], p % BS] = rows[b, p]
    return pool


def test_paged_steps_bitwise_equal_contiguous():
    """A 16-token prefill chunk (a padded row among them) and a decode
    step, paged over tables with unallocated entries, give logits and
    K/V/pos pools bitwise those of the contiguous cache."""
    cfg, model = _model("granite-3-8b")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    lens = [16, 11, 5]            # rows 1 and 2 carry padding (-1)
    toks = np.zeros((ROWS, CHUNK), np.int32)
    pos = np.full((ROWS, CHUNK), -1, np.int32)
    for b, n in enumerate(lens):
        toks[b, :n] = rng.randint(0, cfg.vocab_size, n)
        pos[b, :n] = np.arange(n)
    # non-monotone blocks; entries past a row's need stay -1
    tables = np.full((ROWS, MB), -1, np.int32)
    tables[0, :3] = [7, 2, 10]
    tables[1, :2] = [4, 0]
    tables[2, :1] = [9]
    max_ctx = MB * BS

    cache_c = model.init_cache(ROWS, max_ctx, prefill_chunk=CHUNK)
    lg_c, cache_c = model.prefill(params, jnp.asarray(toks), jnp.asarray(pos),
                                  cache_c, None)
    cache_p = model.init_paged_cache(ROWS, NB, BS)
    tbl = jnp.asarray(tables)
    lg_p, cache_p = model.prefill_paged(params, jnp.asarray(toks),
                                        jnp.asarray(pos), tbl, cache_p, None)
    np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_p))

    nxt = jnp.asarray([int(jnp.argmax(lg_c[b, n - 1]))
                       for b, n in enumerate(lens)], jnp.int32)
    cur = jnp.asarray(lens, jnp.int32)
    lg_c, cache_c = model.decode_step(params, nxt, cur, cache_c)
    lg_p, cache_p = model.decode_step_paged(params, nxt, cur, tbl, cache_p)
    np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_p))

    lens = [n + 1 for n in lens]
    for key in ("k", "v"):
        c = np.asarray(cache_c[key])
        want = np.stack([_laid_out(c[i], tables, lens, 0)
                         for i in range(cfg.num_layers)])
        np.testing.assert_array_equal(want, np.asarray(cache_p[key]))
    np.testing.assert_array_equal(
        _laid_out(np.asarray(cache_c["pos"]), tables, lens, -1),
        np.asarray(cache_p["pos"]))
