"""The persistent-compilation-cache helper: JAX_COMPILATION_CACHE_DIR,
when set, decides; otherwise one fixed directory in the checkout."""
import os
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import enable_compile_cache

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def restore_cache_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()


def test_env_var_leaves_configured_dir(monkeypatch, tmp_path,
                                       restore_cache_config):
    configured = str(tmp_path / "configured")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", configured)
    jax.config.update("jax_compilation_cache_dir", configured)
    assert enable_compile_cache() == configured
    assert jax.config.jax_compilation_cache_dir == configured


def test_default_is_one_fixed_dir_in_checkout(monkeypatch,
                                              restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    assert Path(first) == REPO / ".jax_cache"
    assert os.path.basename(first) in (REPO / ".gitignore").read_text()
