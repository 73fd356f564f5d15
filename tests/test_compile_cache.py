"""Where the persistent compilation cache goes."""
import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_config():
    names = ("jax_compilation_cache_dir",
             "jax_include_full_tracebacks_in_locations")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)


def test_follows_the_environment_variable(monkeypatch, tmp_path,
                                          restore_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_uses_a_fixed_path_in_the_repo_otherwise(monkeypatch, restore_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert not jax.config.jax_include_full_tracebacks_in_locations
