"""The MLA + held-expert reference (`bench/references/mla_moe.py`) against
the program's own model on the CPU at kimi-k2-1t-a32b's `reduced` widths:
chunked paged prefill then paged decode through the latent pool, by the
jnp path and by the interpret-mode latent kernel, give the reference's
logits at every position; the held-expert shares add up to the whole
expert layer; the float8 control moves the logits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness.spec import Spec
from bench.tests.rehearse import REPO
from bench.tests.small_mla_moe import KIMI_SMALL

from repro.config.registry import get_config
from repro.models import layers
from repro.models.model import build_model

NAME = "kimi-k2-instruct-chip"
ARCH = "kimi-k2-1t-a32b"
# both sides compute in float32; they differ in association order (the
# program's absorbed q_nope W_uk . c and softmax . c then W_uv against the
# reference's per-head K and V), which moves logits by float32 rounding:
# 2.5e-6 of their range over these three layers, by either path
TOL = 1e-4


def small_config(**over):
    spec = Spec(REPO)
    c = spec.config_file(NAME)
    small = dict(KIMI_SMALL)
    small.pop("variant")
    c.update(small)
    c.update(over)
    return spec, c


def program_params(model, flat):
    shapes = model.init_shapes()
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(k.key) for k in p) for p, _ in leaves]
    return jax.tree_util.tree_unflatten(tree, [flat[p] for p in paths])


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_reference_matches_paged_prefill_and_decode(monkeypatch, kernel):
    monkeypatch.setattr(layers, "use_pallas", lambda: kernel)
    spec, c = small_config()
    ref = spec.reference(c["reference"])
    w = ref.make_weights(c, jax.random.PRNGKey(3), jnp.float32)
    model = build_model(get_config(ARCH, "reduced"), dtype=jnp.float32)
    params = program_params(model, w)
    bs, nb = 16, 8
    cache = model.init_paged_cache(1, nb, bs)
    assert "v" not in cache and cache["k"].shape == (3, nb, bs, 128)
    tables = jnp.arange(nb, dtype=jnp.int32)[None]
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, c["vocab_size"], 37).tolist()
    got = []
    for s in range(0, len(prompt), 16):          # 16-token chunks
        piece = prompt[s:s + 16]
        logits, cache = model.prefill_paged(
            params, jnp.asarray([piece], jnp.int32),
            jnp.arange(s, s + len(piece), dtype=jnp.int32)[None],
            tables, cache)
        got.append(np.asarray(logits[0]))
    seq = list(prompt)
    for _ in range(12):                           # greedy paged decode
        nxt = int(np.argmax(got[-1][-1]))
        logits, cache = model.decode_step_paged(
            params, jnp.asarray([nxt], jnp.int32),
            jnp.asarray([len(seq)], jnp.int32), tables, cache)
        seq.append(nxt)
        got.append(np.asarray(logits)[None][0])
    got = np.concatenate([g.reshape(-1, c["vocab_size"]) for g in got])
    want = ref.full_logits(w, c, seq)
    assert got.shape == want.shape
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < TOL, err
    # the served-gap reading of greedy tokens is 0 up to float32 rounding
    gaps, _ = ref.served_gaps(w, c, prompt, seq[len(prompt):])
    assert gaps.max() < TOL * np.abs(want).max()
    # every token's 2 selections in each of the 2 MoE layers, counted on
    # the 2 held experts of 4: some land here, never more than all
    held = np.asarray(cache["held"])[0, 0]
    assert held.shape == (2,) and 0 < held.sum() <= (len(seq) - 1) * 2 * 2


def test_held_shares_sum_to_the_whole_layer():
    """Each of the two 2-expert shares of the 4 experts computes its part
    of the expert layer; with the shared expert counted once they add up
    to the reference's layer that holds all 4, and their counts to every
    token's top-2."""
    spec, c = small_config(n_routed_experts=4)
    ref = spec.reference(c["reference"])
    w = ref.make_weights(c, jax.random.PRNGKey(5), jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(6), (64, c["hidden_size"]))
    whole = ref.expert_layer(w, c, 0, h)
    one = {k[len("layers/moe/"):]: v[0] for k, v in w.items()
           if k.startswith("layers/moe/")}
    shared = {k[len("shared/"):]: v for k, v in one.items()
              if k.startswith("shared/")}
    base = get_config(ARCH, "reduced")
    parts, counts = 0.0, 0
    for first in (0, 2):
        cfg = dataclasses.replace(base, moe=dataclasses.replace(
            base.moe, num_held_experts=2, first_held_expert=first))
        p = {"router": one["router"], "router_bias": one["router_bias"],
             "shared": shared}
        for k in ("w_gate", "w_up", "w_down"):
            p[k] = one[k][first:first + 2]
        y, n = layers.held_moe_apply(p, h[None], cfg)
        parts = parts + y[0] - layers.mlp(shared, h[None])[0]
        counts += int(n.sum())
    total = np.asarray(parts + layers.mlp(shared, h[None])[0])
    assert np.abs(total - whole).max() <= TOL * np.abs(whole).max()
    assert counts == 64 * 2


def test_control_differs_from_reference():
    """The float8 control moves the logits by far more than float32
    rounding, so it can fail a limit that sound runs pass."""
    spec, c = small_config()
    ref = spec.reference(c["reference"])
    w = ref.make_weights(c, jax.random.PRNGKey(4), jnp.float32)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, c["vocab_size"], 40).tolist()
    served = rng.integers(0, c["vocab_size"], 60).tolist()
    gs, gc = ref.served_gaps(w, c, prompt, served, control=True)
    assert gc.shape == gs.shape == (60,)
    assert gc.max() > 0.0


def test_routing_ties_are_left_out_of_the_gaps():
    """A served position whose routing of a held expert lies within
    ROUTE_TIE of the top-k boundary in some layer reads a gap of 0: with
    no margin no position is left out, with a margin past every score all
    are, and the default leaves some of each."""
    spec, c = small_config()
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, c["vocab_size"], 40).tolist()
    served = rng.integers(0, c["vocab_size"], 60).tolist()
    got = {}
    for tie in (-1.0, None, 10.0):
        ref = spec.reference(c["reference"])     # a fresh module: own jit
        if tie is not None:
            ref.ROUTE_TIE = tie
        w = ref.make_weights(c, jax.random.PRNGKey(4), jnp.float32)
        gs, gc = ref.served_gaps(w, c, prompt, served, control=True)
        got[tie] = (gs, gc)
    none, default, every = got[-1.0][0], got[None][0], got[10.0][0]
    assert (none > 0).all() and not every.any() and not got[10.0][1].any()
    kept = default > 0
    assert 0 < kept.sum() < len(kept)
    assert np.array_equal(default[kept], none[kept])
