"""The persistent compile cache: placed from outside, else at a fixed path."""

import jax
import pytest

from repro.runtime import compile_cache as CC


@pytest.fixture
def cache_dir_flag():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_placement_is_left_to_jax(monkeypatch, cache_dir_flag, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(CC.CACHE_ENV, str(tmp_path))
    assert CC.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # JAX reads the env


def test_default_is_a_fixed_directory_in_the_checkout(monkeypatch, cache_dir_flag):
    monkeypatch.delenv(CC.CACHE_ENV, raising=False)
    first = CC.enable_compile_cache()
    assert first == CC.enable_compile_cache() == jax.config.jax_compilation_cache_dir
    assert CC.DEFAULT_CACHE_DIR.parent.joinpath("src", "repro").is_dir()
    assert CC.DEFAULT_CACHE_DIR.name == ".jax_cache"
