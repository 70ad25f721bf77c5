"""use_compile_cache: JAX's own setting wins; otherwise one fixed
in-checkout directory."""
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import use_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_env_setting_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                             restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_directory_in_the_checkout(monkeypatch,
                                                        restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = use_compile_cache()
    assert use_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    assert Path(first) == REPO / ".jax_cache"
