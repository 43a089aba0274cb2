"""The entry points' compile-cache placement (repro.compile_cache)."""

import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_honours_env_dir(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_falls_back_to_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    # a fixed path inside the checkout, which git ignores
    assert CHECKOUT_CACHE_DIR.name == ".jax_cache"
    root = CHECKOUT_CACHE_DIR.parent
    assert (root / "src" / "repro" / "compile_cache.py").is_file()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
