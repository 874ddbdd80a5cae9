"""Where the persistent caches land: the environment's choice, else one
fixed path inside the checkout."""
import jax
import pytest

from repro import caches
from repro.core import autotune


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_follows_the_environment(monkeypatch,
                                               cache_dir_config, tmp_path):
    monkeypatch.setenv(caches.COMPILE_CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert caches.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_the_checkout(monkeypatch,
                                                  cache_dir_config):
    monkeypatch.delenv(caches.COMPILE_CACHE_ENV, raising=False)
    path = caches.use_compile_cache()
    assert path == str(caches.CHECKOUT / ".cache" / "jax")
    assert jax.config.jax_compilation_cache_dir == path
    assert caches.use_compile_cache() == path          # fixed, not per-run
    assert (caches.CHECKOUT / "src" / "repro" / "caches.py").is_file()


def test_plan_store_defaults_into_the_checkout(monkeypatch):
    monkeypatch.delenv(autotune.PLAN_CACHE_ENV, raising=False)
    assert autotune.store_path() == caches.CACHE_DIR / "plans.json"
