"""The plain reference against the program's own model, on the CPU at the
test widths of both configurations: chunked paged prefill, then paged
decode steps, must give the reference's logits at every position."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness.spec import Spec
from bench.tests.rehearse import REPO, SMALL

from repro.config.base import ArchFamily, ModelConfig
from repro.models.model import build_model


def small_config(name):
    """The configuration file of `name`, cut to the test widths."""
    spec = Spec(REPO)
    cfgj = spec.config_file(name)
    small = dict(SMALL[name])
    small.pop("variant")
    cfgj.update(small)
    return spec, cfgj


def model_config(c):
    return ModelConfig(
        name="t", family=ArchFamily.DENSE,
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        rope_theta=c["rope_theta"], rms_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], dtype="float32")


def program_params(model, flat):
    shapes = model.init_shapes()
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(k.key) for k in p) for p, _ in leaves]
    return jax.tree_util.tree_unflatten(tree, [flat[p] for p in paths])


@pytest.mark.parametrize("name", sorted(SMALL))
def test_reference_matches_paged_prefill_and_decode(name):
    spec, c = small_config(name)
    ref = spec.reference(c["reference"])
    w = ref.make_weights(c, jax.random.PRNGKey(3), jnp.float32)
    mcfg = model_config(c)
    model = build_model(mcfg, dtype=jnp.float32)
    params = program_params(model, w)
    bs, nb = 16, 8
    cache = model.init_paged_cache(1, nb, bs)
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
    assert err < 1e-4, err
    # the served-gap reading of greedy tokens is 0 up to float32 rounding
    gaps, _ = ref.served_gaps(w, c, prompt, seq[len(prompt):])
    assert gaps.max() < 1e-4 * np.abs(want).max()


def test_control_differs_from_reference():
    """The float8 control moves the logits by far more than float32
    rounding, so it can fail a limit that sound runs pass."""
    spec, c = small_config("granite-3-8b-chip")
    ref = spec.reference(c["reference"])
    w = ref.make_weights(c, jax.random.PRNGKey(4), jnp.float32)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, c["vocab_size"], 40).tolist()
    served = rng.integers(0, c["vocab_size"], 60).tolist()
    gs, gc = ref.served_gaps(w, c, prompt, served, control=True)
    assert gc.shape == gs.shape == (60,)
    assert gc.max() > 0.0
