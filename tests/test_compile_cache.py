"""The launchers' persistent compile-cache placement (launch/compile_cache):
an externally set JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the
cache goes to one fixed directory inside the checkout, ignored by git."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_external_cache_dir_is_kept(monkeypatch, restore_cache_dir,
                                    tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # untouched


def test_default_cache_dir_is_fixed_inside_checkout(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable()
    assert first == compile_cache.enable() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
