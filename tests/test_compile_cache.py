"""The entry points' persistent compile cache and the chip smoke's guard.

``enable_compile_cache`` is never called here with the variable unset:
the tests keep JAX's persistent cache off.
"""
import os

import jax
import pytest

from repro.common import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_dir_is_fixed_and_ignored(monkeypatch):
    """Without the variable the cache goes to ONE fixed path inside the
    checkout (the path is part of the cache key), and git ignores it."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.compile_cache_dir()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert compile_cache.compile_cache_dir() == path
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f}
    assert ".jax_cache" in ignored


def test_env_dir_is_honoured(monkeypatch, tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR wins, and the helper then leaves
    JAX's own setting alone."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_refuses_without_tpu(capsys):
    """No accelerator: non-zero exit, and no result line on stdout."""
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is visible")
    import chip_smoke
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err
