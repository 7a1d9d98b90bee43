"""The reduction from trace to metrics, checked on a small recorded trace
(15 ms of the jacobi-7209.paper trace on one TPU v5e) against a plain
nanosecond timeline, and on a hand-made one."""
import json
import os

import numpy as np
import pytest

from bench.lib import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "jacobi_trace_excerpt.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


def timeline_busy(summary):
    """Busy nanoseconds of the first chip by marking a boolean timeline."""
    lo, hi = summary["window"]
    dev = summary["devices"][sorted(summary["devices"])[0]]
    busy = np.zeros(hi - lo, bool)
    for _, s, d in dev["ops"]:
        a, b = max(s, lo) - lo, min(s + d, hi) - lo
        if b > a:
            busy[a:b] = True
    return busy


def test_recorded_busy_and_idle(recorded):
    busy = timeline_busy(recorded)
    assert trace.busy_s(recorded) == pytest.approx(busy.sum() / 1e9, abs=1e-12)
    assert trace.window_s(recorded) == pytest.approx(0.015)
    idle = trace.idle_share(recorded)
    assert idle == pytest.approx(1 - busy.sum() / busy.size, abs=1e-12)
    assert 0.5 < idle < 1.0


def test_recorded_gaps_match_timeline(recorded):
    busy = timeline_busy(recorded)
    edges = np.flatnonzero(np.diff(np.concatenate([[1], busy.astype(int),
                                                   [1]])))
    runs = sorted((edges[1::2] - edges[0::2]) / 1e9, reverse=True)
    gaps = trace.idle_gaps(recorded, n=5)
    assert [g[1] for g in gaps] == pytest.approx(runs[:5], abs=1e-12)
    assert all(g[0] == "bench.solve" for g in gaps)


def test_recorded_module_time(recorded):
    evs = trace.events_matching(recorded, "modules", ["jit_kernel"])
    assert len(evs) == 15
    assert trace.total_s(evs) == pytest.approx(
        sum(e[2] for e in evs) / 1e9)
    assert trace.top_ops(recorded, n=1)[0][0] == "%multiply_reduce_fusion"


def test_handmade_union_and_chip_mean():
    s = {"window": [0, 100],
         "devices": {"/device:TPU:0": {"ops": [["a", 10, 20], ["b", 30, 10],
                                               ["c", 90, 30]],
                                       "modules": []},
                     "/device:TPU:1": {"ops": [["a", 0, 50]], "modules": []}},
         "host": [["bench.window", 0, 100], ["bench.step", 0, 60]]}
    # chip 0: [10, 40) and [90, 100) -> 40 ns; chip 1: 50 ns
    assert trace.busy_s(s) == pytest.approx(45e-9)
    assert trace.idle_share(s) == pytest.approx(0.55)
    assert trace.idle_gaps(s) == [["idle", pytest.approx(50e-9)],
                                  ["bench.step", pytest.approx(10e-9)]]
    assert trace.top_ops(s, n=2)[0] == ["a", pytest.approx(35e-9)]


def test_self_time_of_nested_ops():
    ops = [["while", 0, 100], ["body1", 10, 30], ["inner", 15, 5],
           ["body2", 50, 40], ["after", 100, 10]]
    got = dict(trace.self_times(ops))
    assert got == {"while": pytest.approx(30e-9),
                   "body1": pytest.approx(25e-9),
                   "inner": pytest.approx(5e-9),
                   "body2": pytest.approx(40e-9),
                   "after": pytest.approx(10e-9)}


def test_sweep_runs_counts_sweep_sized_programs(recorded):
    """The Jacobi check's device witness: on the recorded excerpt, the 15
    sweep programs (72 us each) pass half the HBM time of a row chunk at a
    v5e's peak, and the 3 us residual programs do not; a sweep dropped
    from the trace is a sweep fewer."""
    from bench.drivers import jacobi_solve_loop as jac
    min_s = 0.5 * min(jac.sweep_bytes(7209, 4)) / 819e9
    assert jac.sweep_runs(recorded, min_s) == 15
    thinned = json.loads(json.dumps(recorded))
    dev = thinned["devices"]["/device:TPU:0"]
    first = next(m for m in dev["modules"] if m[0].startswith("jit_kernel"))
    dev["modules"].remove(first)
    assert jac.sweep_runs(thinned, min_s) == 14
