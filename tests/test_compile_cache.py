"""The persistent compile cache is placed from outside: JAX's own
``JAX_COMPILATION_CACHE_DIR`` stands when set, else a fixed directory in
the checkout that git ignores."""
import jax
import pytest

from repro.launch.cache import DEFAULT_DIR, enable_compile_cache


@pytest.fixture
def cache_dir_setting():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_stands(monkeypatch, cache_dir_setting):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", "/elsewhere/cache")
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"


def test_default_dir_is_fixed_in_the_checkout_and_ignored(monkeypatch,
                                                          cache_dir_setting):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    root = DEFAULT_DIR.parent
    assert (root / "pyproject.toml").is_file()
    ignored = (root / ".gitignore").read_text().split()
    assert f"{DEFAULT_DIR.name}/" in ignored
