"""Guards that keep a run from measuring or serving on the wrong device:
the chip smoke refuses a host without a TPU, the forced-CPU serve bench row
refuses a TPU host, and the compile cache lands where it is told."""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


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


def test_sharded_serve_bench_refuses_tpu_host(monkeypatch, tmp_path):
    from benchmarks import serve_bench
    from repro.kernels import runtime
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="measure the CPU"):
        serve_bench._run_sharded_variant("base", [], trace_cli=[],
                                         tmpdir=str(tmp_path))
