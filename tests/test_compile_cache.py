"""The persistent compile cache lives where `JAX_COMPILATION_CACHE_DIR`
says, and otherwise at one fixed directory inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_jax_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_env_var_is_honoured_and_nothing_set_in_code(monkeypatch, tmp_path,
                                                     restore_jax_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert compile_cache.cache_dir() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch,
                                                   restore_jax_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.cache_dir() == \
        compile_cache.enable_compile_cache()
    assert Path(first) == ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
