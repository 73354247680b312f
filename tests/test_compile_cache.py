"""Placement of jax's persistent compilation cache
(:mod:`repro.launch.compile_cache`)."""
import pathlib

import pytest

jax = pytest.importorskip("jax")

from repro.launch import compile_cache  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Restore jax's cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # nothing set


def test_unset_env_uses_the_fixed_checkout_dir(monkeypatch,
                                               cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    assert got == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.use_compile_cache() == got      # stable


def test_checkout_cache_dir_is_gitignored():
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
