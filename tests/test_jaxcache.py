"""The persistent compile cache lives at one placeable path (PR 22).

The directory is part of the cache's key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` where it is set (and then no
directory is set in code), else ``<repo>/.jax_cache``.
"""

from pathlib import Path

import jax
import pytest

from backuwup_tpu.utils import jaxcache


@pytest.fixture
def cache_config():
    """Put the process's cache settings back after each case."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_env_directory_wins_and_code_sets_none(monkeypatch, tmp_path,
                                               cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    assert jaxcache.enable_compilation_cache() == tmp_path / "placed"
    # untouched: JAX reads the variable itself; no other directory appears
    assert jax.config.jax_compilation_cache_dir == "sentinel"
    assert not (tmp_path / "placed").exists()


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch,
                                                   cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("BACKUWUP_JAX_CACHE", raising=False)
    repo = Path(__file__).resolve().parent.parent
    first = jaxcache.enable_compilation_cache()
    assert first == repo / ".jax_cache"
    assert first.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(first)
    # no home directory, temporary name, pid or time in it: stable
    assert jaxcache.enable_compilation_cache() == first


def test_own_variable_is_gone(monkeypatch, tmp_path, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("BACKUWUP_JAX_CACHE", str(tmp_path / "old"))
    assert jaxcache.enable_compilation_cache() == jaxcache.REPO_CACHE_DIR
    assert not (tmp_path / "old").exists()
