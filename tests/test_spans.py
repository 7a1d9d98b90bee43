"""The span recorder (``repro.core.spans``) and the spans the job model and
the serve loop record: nesting and self time, the ring's overflow, the
profiler trace's copy of the spans, one ``hypar.job`` per job, the serve
loop's queue and prefill intervals against TTFT, and (four forced CPU
devices, in a subprocess) bound inputs counted in ``moved_bytes``."""
import dataclasses
import glob
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.core import spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
X4_SCRIPT = os.path.join(REPO, "tests", "_jacobi_x4_bytes.py")


def _busy(seconds: float) -> None:
    t = time.perf_counter()
    while time.perf_counter() - t < seconds:
        pass


def test_nesting_self_time_and_interval():
    rec = spans.Recorder()
    t_lo = time.perf_counter()
    with rec.span("outer", "k0"):
        _busy(0.002)
        with rec.span("inner", 7):
            _busy(0.003)
        with rec.span("inner", 8):
            _busy(0.001)
    t0 = spans.now()
    rec.interval("wait", t0 - 5_000_000, t0, key=3)
    got = rec.spans_between(t_lo, time.perf_counter())
    assert [(s.name, s.key, s.parent) for s in got] == [
        ("inner", 7, "outer"), ("inner", 8, "outer"), ("outer", "k0", None),
        ("wait", 3, None)]
    assert got[-1].thread is None and got[0].thread is not None
    own = {(s.name, s.key): ns for s, ns in spans.self_time(got)}
    outer, in7, in8, wait = got[2], got[0], got[1], got[3]
    assert own[("outer", "k0")] == (outer.t1 - outer.t0
                                    - (in7.t1 - in7.t0) - (in8.t1 - in8.t0))
    assert own[("outer", "k0")] >= 2_000_000
    assert own[("inner", 7)] == in7.t1 - in7.t0 >= 3_000_000
    assert own[("wait", 3)] == 5_000_000
    # only spans that overlap the asked stretch come back
    assert rec.spans_between(t_lo - 10.0, t_lo - 5.0) == []


def test_ring_overflow_refuses_the_dropped_stretch():
    rec = spans.Recorder(capacity=4)
    t_lo = time.perf_counter()
    for i in range(6):
        with rec.span("s", i):
            pass
    t_mid = time.perf_counter()
    assert rec.spans_between(t_lo, t_mid) is None
    for i in range(4):
        with rec.span("late", i):
            pass
    got = rec.spans_between(t_mid, time.perf_counter())
    assert [s.key for s in got] == [0, 1, 2, 3]
    assert rec.spans_between(t_lo, time.perf_counter()) is None


def test_profiler_trace_holds_the_same_spans(tmp_path):
    """Aligned by one anchor span, the recorder's spans land on the
    profiler's copy of them (host plane of the ``.xplane.pb``)."""
    from jax.profiler import ProfileData
    t_lo = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("test.anchor"):
            _busy(0.001)
        for i in range(20):
            with spans.span("test.outer", i):
                _busy(0.0002)
                with spans.span("test.inner", i):
                    _busy(0.0003)
    finally:
        jax.profiler.stop_trace()
    mine = [s for s in spans.spans_between(t_lo, time.perf_counter())
            if s.name.startswith("test.")]
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    theirs = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("test."):
                        theirs.setdefault(ev.name, []).append(
                            (int(ev.start_ns), int(ev.duration_ns)))
    assert {k: len(v) for k, v in theirs.items()} == {
        "test.anchor": 1, "test.outer": 20, "test.inner": 20}
    anchor = next(s for s in mine if s.name == "test.anchor")
    offset = theirs["test.anchor"][0][0] - anchor.t0
    errs = []
    for name in ("test.outer", "test.inner"):
        ours = sorted((s.t0, s.t1) for s in mine if s.name == name)
        for (t0, t1), (start, dur) in zip(ours, sorted(theirs[name])):
            errs += [abs(start - offset - t0), abs(start + dur - offset - t1)]
    assert max(errs) < 100_000, max(errs)


def test_jacobi_records_one_job_span_per_job():
    from repro.apps.jacobi import jacobi_hypar, make_system
    A, b, _ = make_system(96)
    iters, n_chunks = 7, 3
    t_lo = time.perf_counter()
    r = jacobi_hypar(A, b, iters=iters, n_chunks=n_chunks)
    got = spans.spans_between(t_lo, time.perf_counter())
    jobs = [s for s in got if s.name == "hypar.job"]
    assert len(jobs) == iters * (n_chunks + 2)
    assert sorted(s.key for s in jobs) == sorted(r.extra["job_devices"])
    names = [s.name for s in got]
    assert names.count("hypar.run") == 1
    assert names.count("jacobi.setup") == names.count("jacobi.finish") == 1
    assert names.count("hypar.segment") == 3 * iters
    # one host copy per control job, one barrier per segment
    assert names.count("hypar.wait") == iters + 3 * iters
    # the report reads its own run back: self times that add up to its wall
    report = r.extra["report"]
    phases = report.host_phases()
    lo, hi = report.host_span
    assert sum(phases.values()) == pytest.approx(hi - lo, rel=0.05)
    assert all(t > 0 for t in phases.values())
    assert "plan=" in report.summary() and "wait=" in report.summary()


def test_serve_queue_and_prefill_intervals_split_ttft():
    from repro.configs import get_smoke_config
    from repro.models.transformer import init_params
    from repro.serve import (HyParRequestTracker, PagedEngine, SamplingParams,
                             ServeScheduler)
    cfg = dataclasses.replace(get_smoke_config("qwen2-1.5b"),
                              compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedEngine(cfg, params, batch=2, max_len=96, page_size=8,
                      num_pages=40, prefill_chunk=16)
    sched = ServeScheduler(eng, sp=SamplingParams(),
                           tracker=HyParRequestTracker(2))
    rng = np.random.default_rng(3)

    def drain():
        for n in (12, 40, 20, 33, 9):          # 5 requests for 2 slots
            sched.submit(rng.integers(1, cfg.vocab_size - 1, n)
                         .astype(np.int32), 4)
        return sched.run()

    # the first admission builds the page pool (between the pop and the
    # slot): warm the scheduler as a server is warmed, then measure
    drain()
    sched.reset_metrics()
    t_lo = time.perf_counter()
    results = drain()
    got = spans.spans_between(t_lo, time.perf_counter())
    assert len(results) == 5
    by = {}
    for s in got:
        if s.name in ("serve.queue", "serve.prefill"):
            assert (s.name, s.key) not in by
            by[(s.name, s.key)] = s
    for r in results:
        q, p = by[("serve.queue", r.rid)], by[("serve.prefill", r.rid)]
        accepted = q.t0 / 1e9
        split = (q.t1 - q.t0 + p.t1 - p.t0) / 1e9
        assert split == pytest.approx(r.ttft_s - (accepted - r.arrival_s),
                                      abs=1e-3)
        assert r.admitted_s - r.arrival_s == pytest.approx(
            (q.t1 - q.t0) / 1e9, abs=1e-3)
        assert r.token_s[0] - r.admitted_s == pytest.approx(
            (p.t1 - p.t0) / 1e9, abs=1e-3)
    names = [s.name for s in got]
    steps = [s for s in got if s.name == "serve.step"]
    assert len(steps) >= sched.n_steps
    assert names.count("serve.decode") == sched.n_steps
    # every decode's tokens are waited for inside its step
    waits = [s for s in got if s.name == "serve.wait"
             and s.parent == "serve.step"]
    assert len(waits) == sched.n_steps
    chunks = [s for s in got if s.name == "serve.prefill_chunk"]
    assert {s.key for s in chunks} == {r.rid for r in results}
    assert all(s.parent == "serve.step" for s in chunks)


def test_bound_inputs_count_in_moved_bytes_on_four_devices():
    r = subprocess.run(
        [sys.executable, X4_SCRIPT], capture_output=True, text=True,
        cwd=REPO, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
             "JAX_PLATFORMS": "cpu"})
    assert "X4_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
