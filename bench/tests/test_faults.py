"""Whole runs on the CPU at a small size, with the timed path broken
underneath: each fault must turn ``correct`` false, and the unbroken run
must stay correct."""
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.tests import small
from bench.tests.cpu_cell import CHECKOUT, run_small


def jacobi_run(tmp_path, **kw):
    return run_small(tmp_path, "jacobi-7209.paper", config=small.JACOBI,
                     traffic=small.JACOBI_MIX, seconds=0.5, **kw)


def broken_jacobi(monkeypatch, fault):
    import repro.apps.jacobi as app
    real = app.jacobi_hypar

    def jacobi_hypar(A, b, *, iters=500, **kw):
        if fault == "converged":          # sweeps stop once x repeats,
            r = real(A, b, iters=5, **kw)  # every iteration still reported
            r.iters = iters
            return r
        r = real(A, b, iters=iters - (fault == "short"), **kw)
        x = np.array(r.x)
        if fault == "unchanged":          # the step returns its state
            x[:] = 0.0
        elif fault == "half":             # half of the row chunks left out
            x[len(x) // 2:] = 0.0
        elif fault == "altered":          # one answer altered
            x[17] += 1e-3 * np.abs(x).max()
        r.x = x
        return r
    monkeypatch.setattr(app, "jacobi_hypar", jacobi_hypar)


def test_jacobi_sound(tmp_path):
    assert jacobi_run(tmp_path)["correct"] is True


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered", "short",
                                   "converged"])
def test_jacobi_fault(tmp_path, monkeypatch, fault):
    broken_jacobi(monkeypatch, fault)
    res = jacobi_run(tmp_path)
    assert res["correct"] is False
    if fault == "converged":
        # x is at its fixed point and the count is the mix's: only the
        # job report's missing sweeps give it away
        checks = res["checks"]
        assert checks["x_err"]["value"] <= checks["x_err"]["limit"]
        assert checks["iters_off"]["value"] == 0
        assert checks["sweep_jobs_off"]["value"] > 0


X4 = """
import sys, json
sys.path.insert(0, {checkout!r})
import repro.core as core
from bench.tests import small
from bench.tests.cpu_cell import run_small
if {broken!r}:
    real = core.VirtualCluster
    class OneChip(real):           # the exchange between chips left out
        def __init__(self, devices=None, **kw):
            super().__init__(list(devices)[:1], **kw)
    core.VirtualCluster = OneChip
res = run_small({tmp!r}, "jacobi-7209.x4", config=small.JACOBI,
                traffic=small.JACOBI_MIX, seconds=0.5)
print("RESULT", json.dumps(res["correct"]))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_jacobi_four_chips(tmp_path, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(CHECKOUT, "src"))
    code = X4.format(checkout=CHECKOUT, broken=broken, tmp=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert line, out.stderr[-3000:]
    assert line[0] == f"RESULT {'false' if broken else 'true'}"


def serve_run(tmp_path, workload="qwen2-1.5b.chat"):
    return run_small(tmp_path, workload, config=small.QWEN2,
                     traffic=small.SERVE_MIX, seconds=3.0)


def test_serve_sound(tmp_path):
    res = serve_run(tmp_path)
    assert res["correct"] is True
    assert res["checks"]["logit_gap"]["value"] <= small.QWEN2["limits"]["logit_gap"]


def test_serve_backlog_sound(tmp_path):
    res = run_small(tmp_path, "qwen2-1.5b.batch", config=small.QWEN2,
                    traffic=small.BACKLOG_MIX, seed=2**31 + 11, seconds=3.0)
    assert res["correct"] is True
    assert res["metrics"]["serve_out_tok_s"]["value"] > 0


def test_serve_token_altered(tmp_path, monkeypatch):
    from repro.serve.scheduler import ServeScheduler
    real = ServeScheduler._sample

    def _sample(self, logits):              # a token altered where produced
        ids = np.array(real(self, logits))
        return (ids + 1) % 512
    monkeypatch.setattr(ServeScheduler, "_sample", _sample)
    assert serve_run(tmp_path)["correct"] is False


def test_serve_prefill_state_dropped(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import PagedEngine
    real = PagedEngine.prefill_chunk

    def prefill_chunk(self, *a, **kw):      # the chunk's KV never lands
        self.ensure_batch()
        keep = jax.tree.map(jnp.copy, self.cache)
        out = real(self, *a, **kw)
        self.cache = keep
        return out
    monkeypatch.setattr(PagedEngine, "prefill_chunk", prefill_chunk)
    assert serve_run(tmp_path)["correct"] is False
