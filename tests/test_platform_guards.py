"""The compile cache lands where it is told."""
import os

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch import compile_cache
    if from_env:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(REPO, ".jax_cache")
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_enable_compilation_cache)
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_enable_compilation_cache
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_enable_compilation_cache", before[1])
        cc.reset_cache()

