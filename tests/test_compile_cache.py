"""``apex_tpu.compile_cache``: a cache that can be placed from outside."""

import os

import jax
import pytest

from apex_tpu import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_names_the_directory_and_code_sets_no_other(
        monkeypatch, tmp_path, restore_dir):
    # jax reads the variable itself (at import: in this process it was
    # unset then), so the helper has nothing to set
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == restore_dir


def test_unset_uses_the_one_fixed_path(monkeypatch, restore_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.enable() == compile_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR


def test_fixed_path_is_in_the_checkout_and_ignored():
    # the path is part of the cache's key: inside the checkout, the same
    # on every run, and never committed
    assert os.path.dirname(compile_cache.DEFAULT_DIR) == REPO
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = f.read().split()
    assert os.path.basename(compile_cache.DEFAULT_DIR) + "/" in ignored
