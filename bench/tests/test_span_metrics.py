"""The readers of the program's spans (``bench/lib/spans.py`` and the
metrics that use it), checked on a hand-made recorder and trace summary:
self time per Jacobi iteration, the device idle time no span explains,
the serve loop's host time per step and its queue and prefill intervals,
and None wherever there is nothing to read."""
import sys
import types

import pytest

from bench.lib.cell import metric_reader
from repro.core import spans

MS = 1_000_000                  # nanoseconds
B = 1_000_000 * MS              # recorder clock at the window's opening
W = 7_000_000 * MS              # device-trace clock at the same moment


def cell(summary=None, **data):
    return types.SimpleNamespace(trace_span=(B / 1e9, (B + 10 * MS) / 1e9),
                                 summary=summary, data=data)


@pytest.fixture
def recorder(monkeypatch):
    """A fresh recorder in the program's place, filled by ``put``."""
    rec = spans.Recorder()
    monkeypatch.setattr(spans, "spans_between", rec.spans_between)

    def put(name, a, b, key=None, thread=1, parent=None):
        rec._ring.append((name, B + int(a * MS), B + int(b * MS), parent,
                          key, thread))
    return put


def read(name, c):
    return metric_reader(name).read(c)


def test_job_model_phases_per_iteration(recorder):
    recorder("hypar.plan", -3.0, -2.0)           # before the window
    recorder("hypar.run", 0.0, 9.0)
    recorder("hypar.segment", 0.1, 3.0, 0)
    recorder("hypar.plan", 0.2, 0.5)
    recorder("hypar.job", 0.5, 2.5, "C0")
    recorder("hypar.wait", 1.5, 2.0)             # the control's host copy
    recorder("hypar.wait", 2.6, 2.9)             # the segment barrier
    recorder("hypar.segment", 3.0, 8.0, 1)
    recorder("hypar.plan", 3.1, 3.3)
    recorder("hypar.job", 3.3, 5.3, "S1_0")
    recorder("hypar.job", 4.0, 4.5, "X0")        # recovery inside a job
    recorder("hypar.wait", 5.5, 7.5)
    c = cell(iterations=2)
    assert read("hypar_plan_ms_per_iter", c) == pytest.approx(0.5 / 2)
    assert read("hypar_dispatch_ms_per_iter", c) == pytest.approx(
        (1.5 + 1.5 + 0.5) / 2)
    assert read("hypar_wait_ms_per_iter", c) == pytest.approx(
        (0.5 + 0.3 + 2.0) / 2)
    assert read("hypar_wait_ms_per_iter", cell(iterations=0)) is None


def test_device_idle_no_span_explains(recorder):
    recorder("jacobi.setup", 0.0, 2.0)
    recorder("hypar.run", 3.0, 8.0)
    recorder("hypar.job", 4.0, 5.0)              # nested: no double count
    recorder("bench.solve", 0.0, 10.0)           # not the program's
    recorder("serve.step", 8.0, 10.0)            # not the job model's
    ops = [["fusion", W + int(2.5 * MS), int(0.3 * MS)],
           ["fusion", W + int(8.5 * MS), int(0.5 * MS)]]
    summary = {"window": [W, W + 10 * MS],
               "devices": {"/device:TPU:0": {"ops": ops, "modules": []},
                           "/device:TPU:1": {"ops": [], "modules": []}},
               "host": []}
    # idle and unspanned: [2, 2.5], [2.8, 3], [8, 8.5], [9, 10] ms
    assert read("device_idle_unspanned.jacobi", cell(summary)) == \
        pytest.approx(22.0)
    assert read("device_idle_unspanned.jacobi", cell()) is None


def test_serve_step_host_time(recorder):
    recorder("serve.step", 0.0, 4.0)
    recorder("serve.admit", 0.1, 0.3, parent="serve.step")
    recorder("serve.decode", 0.5, 0.7, parent="serve.step")
    recorder("serve.wait", 0.8, 3.5, parent="serve.step")
    recorder("serve.step", 4.0, 8.0)
    recorder("serve.prefill_chunk", 4.1, 5.0, 3, parent="serve.step")
    recorder("serve.wait", 4.5, 4.9, parent="serve.prefill_chunk")
    recorder("serve.decode", 5.1, 5.3, parent="serve.step")
    recorder("serve.wait", 5.4, 7.4, parent="serve.step")
    recorder("serve.wait", 5.0, 6.0, thread=2)   # another thread's
    recorder("serve.step", 8.0, 8.5)             # no decode: not a step
    recorder("serve.step", 9.0, 12.0)            # ends after the window
    recorder("serve.decode", 9.5, 9.6, parent="serve.step")
    assert read("step_host_ms.chat", cell()) == pytest.approx(
        ((4.0 - 2.7) + (4.0 - 0.4 - 2.0)) / 2)


def test_queue_and_prefill_intervals(recorder):
    recorder("serve.queue", -4.0, 1.0, key=1, thread=None)
    recorder("serve.queue", 2.0, 2.5, key=2, thread=None)
    recorder("serve.queue", 9.0, 11.0, key=3, thread=None)   # ends after
    recorder("serve.prefill", -2.0, 3.0, key=1, thread=None)
    recorder("serve.prefill", 2.6, 9.6, key=2, thread=None)
    c = cell(records=[{"due": (B - 5 * MS) / 1e9, "submit": (B - 4 * MS) / 1e9,
                       "token_s": [(B + 3 * MS) / 1e9]}])
    assert read("queue_wait_ms.chat", c) == pytest.approx((5.0 + 0.5) / 2)
    assert read("prefill_wait_ms.chat", c) == pytest.approx((5.0 + 7.0) / 2)


def test_nothing_to_read_gives_none(recorder, monkeypatch):
    names = ["hypar_plan_ms_per_iter", "hypar_dispatch_ms_per_iter",
             "hypar_wait_ms_per_iter", "device_idle_unspanned.jacobi",
             "step_host_ms.batch", "queue_wait_ms.chat",
             "prefill_wait_ms.chat"]
    empty = cell(iterations=3, records=[])
    assert all(read(n, empty) is None for n in names)
    untraced = cell(iterations=3)
    untraced.trace_span = None
    assert all(read(n, untraced) is None for n in names)
    # a ring that dropped spans from the window
    recorder("serve.queue", 1.0, 2.0, key=1, thread=None)
    monkeypatch.setattr(spans, "spans_between", lambda lo, hi: None)
    assert all(read(n, empty) is None for n in names)
    # an older program, without the recorder
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert all(read(n, empty) is None for n in names)
