"""The one compile-cache helper run_sim, bench.py and chip_smoke.py share."""

import os

import jax
import pytest

from tpu_gossip.utils import compile_cache


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env", ["/some/dir", None])
def test_cache_dir_follows_the_environment(monkeypatch, cache_config, env):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX and nothing is
    set in code; otherwise the cache is the fixed <repo>/.jax_cache."""
    jax.config.update("jax_compilation_cache_dir", "/untouched")
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.use_compile_cache() == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        assert compile_cache.CACHE_DIR == os.path.join(repo, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert compile_cache.use_compile_cache() == env
        assert jax.config.jax_compilation_cache_dir == "/untouched"
