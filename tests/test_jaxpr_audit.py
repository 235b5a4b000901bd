"""jaxpr trace auditor (DESIGN §13): detector unit tests plus a
representative per-family audit subset small enough for tier-1 (the CLI /
CI lint job audits every arch in the registry).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.jaxpr_audit import (_audit_closed, audit_arch,
                                        run_jaxpr_audit)

# one family per cache layout: dense GQA, pure SSM state, RG-LRU hybrid,
# MoE routing — the layouts with distinct prefill/decode/paged graphs
SUBSET = ["granite-3-8b", "mamba2-2.7b", "recurrentgemma-9b",
          "qwen2-moe-a2.7b"]


def test_detector_flags_float64():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.ones((2,), jnp.float64))
    fs = _audit_closed(closed, "t", "p.py")
    assert any(f.rule == "jaxpr-audit" and "float64" in f.message
               for f in fs)


def test_detector_flags_callbacks():
    def f(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    closed = jax.make_jaxpr(f)(jnp.ones((2,), jnp.float32))
    fs = _audit_closed(closed, "t", "p.py")
    assert any("pure_callback" in f.message for f in fs)
    assert all(f.rule == "jaxpr-audit" for f in fs)


def test_detector_recurses_sub_jaxprs():
    def f(x):
        def body(_, v):
            return jax.pure_callback(
                lambda u: u, jax.ShapeDtypeStruct(v.shape, v.dtype), v)
        return jax.lax.fori_loop(0, 3, body, x)
    closed = jax.make_jaxpr(f)(jnp.ones((2,), jnp.float32))
    fs = _audit_closed(closed, "t", "p.py")
    assert any("pure_callback" in f.message for f in fs)


def test_clean_step_produces_no_findings():
    closed = jax.make_jaxpr(lambda x: jnp.tanh(x) + 1)(
        jnp.ones((2,), jnp.float32))
    assert _audit_closed(closed, "t", "p.py") == []


@pytest.mark.parametrize("arch", SUBSET)
def test_family_serving_steps_audit_clean(arch):
    # recompile check (2 tiny jit compiles) only on the dense family;
    # trace-only audits keep the other layouts inside the tier-1 budget
    fs = audit_arch(arch, recompile=(arch == "granite-3-8b"))
    assert fs == [], "\n".join(str(f) for f in fs)


def test_run_jaxpr_audit_subset_paths_anchor_configs():
    fs = run_jaxpr_audit(archs=["granite-3-8b"], recompile=False)
    assert fs == []
