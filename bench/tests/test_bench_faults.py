"""With the timed path broken underneath, a run's `correct` comes out
false: once for each fault a served cell can have on one chip. (A cell on
one chip has no exchange between chips, and serving has no batch mean
to take over half the batch.)"""
import jax.numpy as jnp
import pytest

from bench.tests.cpu_run import run_cell

from repro.models import layers
from repro.serving import engine


def altered_token(monkeypatch):
    """Every decode token altered where it is produced."""
    def sample(logits, key, temperature=0.0, top_k=0):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (tok + 1) % logits.shape[-1]
    monkeypatch.setattr(engine, "sample", sample)


def state_unchanged(monkeypatch):
    """The step returns the KV pool it was given: nothing is written."""
    monkeypatch.setattr(layers, "_pool_write", lambda pool, flat, val: pool)


@pytest.mark.parametrize("fault", [altered_token, state_unchanged])
def test_fault_is_not_correct(tmp_path, capsys, monkeypatch, fault):
    fault(monkeypatch)
    last, err, _ = run_cell(tmp_path, capsys, "granite-chat",
                            rate_scale=6.0)
    assert last["correct"] is False
    gap = last["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"], gap
