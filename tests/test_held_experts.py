"""The held-expert routing counter (DESIGN §17): each request of a paged
engine serving kimi-k2-1t-a32b `reduced` (2 of 4 experts held) carries,
after it finishes, the selections its tokens made of each held expert,
summed over the MoE layers — the same counts one uncut pass of the model
over its prompt and served tokens gives — at pipeline depth 0 and 1."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import ServeConfig
from repro.config.registry import get_config
from repro.models.model import build_model
from repro.serving import engine as E

_MODEL = {}


def setup_model():
    if not _MODEL:
        cfg = get_config("kimi-k2-1t-a32b", "reduced")
        m = build_model(cfg, dtype=jnp.float32)
        _MODEL.update(cfg=cfg, m=m, params=m.init(jax.random.PRNGKey(0)))
    return _MODEL["cfg"], _MODEL["m"], _MODEL["params"]


def one_pass_counts(m, params, tokens):
    """Held selections of `tokens` run as one prefill chunk."""
    bs = 16
    nb = -(-len(tokens) // bs)
    cache = m.init_paged_cache(1, nb, bs)
    _, cache = m.prefill_paged(
        params, jnp.asarray([tokens], jnp.int32),
        jnp.arange(len(tokens), dtype=jnp.int32)[None],
        jnp.arange(nb, dtype=jnp.int32)[None], cache)
    return np.asarray(cache["held"])[0, 0].tolist()


@pytest.mark.parametrize("depth", [0, 1])
def test_finished_requests_carry_their_held_selections(depth):
    cfg, m, params = setup_model()
    serve = ServeConfig(policy="static", b_max=4, max_new_tokens=6,
                        kv_pool_tokens=512, block_size=16,
                        chunked_prefill=True, chunk_budget_tokens=16,
                        n_prefill_lanes=2, paged_kv=True,
                        overlap_depth=depth)
    eng = E.Engine(m, params, serve, max_context=96, buckets=(1, 2, 4),
                   prefill_chunk=8)
    assert eng.held and "held_selections" in eng._span_args(rows=1)
    rng = np.random.RandomState(0)
    reqs = [eng.submit(list(map(int, rng.randint(0, cfg.vocab_size,
                                                 size=n))), max_new_tokens=6)
            for n in (20, 13, 30)]
    eng.run()
    total = 0
    for r in reqs:
        assert r.state.value == "finished" and len(r.output_tokens) == 6
        want = one_pass_counts(m, params,
                               r.prompt_tokens + r.output_tokens[:-1])
        assert r.held_selections == want
        total += sum(want)
    assert eng.held_selections_total == total > 0


def test_models_without_held_experts_count_nothing():
    cfg = get_config("granite-3-8b", "reduced")
    m = build_model(cfg, dtype=jnp.float32)
    eng = E.Engine(m, m.init(jax.random.PRNGKey(0)),
                   ServeConfig(policy="static", b_max=2, paged_kv=True,
                               kv_pool_tokens=256, block_size=16),
                   max_context=64, buckets=(1, 2))
    assert not eng.held and "held" not in eng.cache
    assert eng._span_args(rows=1) == {"rows": 1}
