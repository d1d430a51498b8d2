"""Placement of JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_var_wins_and_code_sets_nothing(monkeypatch, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.use_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_fixed_dir_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    checkout = compile_cache.CHECKOUT_CACHE.parent
    assert path == str(checkout / ".jax_cache")
    assert (checkout / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: a moving cache never hits
    assert compile_cache.use_compile_cache() == path


def test_cache_dir_is_git_ignored():
    gitignore = compile_cache.CHECKOUT_CACHE.parent / ".gitignore"
    assert ".jax_cache/" in gitignore.read_text().split()

