"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache:
the directory ``JAX_COMPILATION_CACHE_DIR`` names, else one fixed path in
the checkout."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_config():
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_enable_compilation_cache)
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_enable_compilation_cache", before[1])


def test_cache_goes_where_the_variable_says(monkeypatch, tmp_path,
                                            restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_one_fixed_path_in_the_checkout(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(REPO_CACHE_DIR) == enable_compile_cache()
    assert Path(path).parent == Path(__file__).resolve().parents[1]
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_enable_compilation_cache
