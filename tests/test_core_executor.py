"""Executor behaviour: scheduling, locality, co-scheduling, dynamic jobs,
fault recovery, stragglers (paper §3 + DESIGN.md §6)."""
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import (ChaosLocalExecutor, ChunkedData, ChunkRef,
                        FaultInjector, FunctionRegistry, Job, JobGraph,
                        LocalExecutor, ParallelSegment, VirtualCluster)


def max_registry():
    reg = FunctionRegistry()

    @reg.chunkwise(1)
    def search_max(chunk):
        return jnp.max(chunk)

    @reg.whole(2)
    def combine(*cds):
        vals = [a for cd in cds for a in cd.arrays()]
        return ChunkedData.from_arrays([jnp.max(jnp.stack(vals))])

    return reg


def paper_max_graph(A, split=60, k1=6, k2=4):
    g = JobGraph()
    g.add_segment([Job("J1", 1, 0), Job("J2", 1, 0)])
    g.add_segment([Job("J3", 2, 1, (ChunkRef("J1"), ChunkRef("J2")))])
    g.bind_input("J1", A[:split], n_chunks=k1)
    g.bind_input("J2", A[split:], n_chunks=k2)
    return g


@given(st.integers(10, 300), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_paper_max_example_correct(n, seed):
    """Paper §2.2's motivating example returns the true maximum for any
    data and any chunking."""
    rng = np.random.default_rng(seed)
    A = rng.standard_normal(n).astype(np.float32)
    split = max(1, min(n - 1, n * 3 // 5))
    g = paper_max_graph(A, split=split,
                        k1=min(6, split), k2=min(4, n - split))
    ex = LocalExecutor(VirtualCluster(n_schedulers=2, max_workers=4),
                       max_registry())
    res, _ = ex.run(g)
    assert float(res["J3"].to_array()) == pytest.approx(float(A.max()))


def test_no_send_back_keeps_results_on_worker():
    reg = FunctionRegistry()

    @reg.chunkwise(1)
    def double(c):
        return c * 2

    @reg.whole(2)
    def total(cd):
        return ChunkedData.from_arrays([sum(jnp.sum(a) for a in cd.arrays())])

    g = JobGraph()
    g.add_segment([Job("P", 1, 0, no_send_back=True)])
    g.add_segment([Job("Q", 2, 1, (ChunkRef("P"),))])
    g.bind_input("P", np.arange(8, dtype=np.float32), n_chunks=4)
    cluster = VirtualCluster(n_schedulers=1, max_workers=2)
    ex = LocalExecutor(cluster, reg)
    res, rep = ex.run(g)
    rec = ex.store.get("P")
    assert not rec.sent_back and rec.owner_worker is not None
    assert cluster.workers[rec.owner_worker].retained.get("P") is not None
    assert float(res["Q"].to_array()) == pytest.approx(2 * np.arange(8).sum())


def test_locality_aware_placement():
    """A consumer of a retained result is placed on the producing worker
    (zero moved bytes on one device, local bytes accounted)."""
    reg = FunctionRegistry()

    @reg.chunkwise(1)
    def ident(c):
        return c

    g = JobGraph()
    g.add_segment([Job("A", 1, 0, no_send_back=True)])
    g.add_segment([Job("B", 1, 1, (ChunkRef("A"),))])
    g.bind_input("A", np.ones(16, np.float32), n_chunks=2)
    ex = LocalExecutor(VirtualCluster(n_schedulers=1, max_workers=3), reg)
    _, rep = ex.run(g)
    seg1 = rep.segments[1]
    assert seg1.local_bytes > 0 and seg1.moved_bytes == 0
    # the report records where each job ran: B followed A onto its worker
    assert set(rep.job_devices) == {"A", "B"}
    assert rep.job_devices["B"] == rep.job_devices["A"] == jax.devices()[0]


def test_co_scheduling_same_function_jobs():
    """Paper §3.3: two jobs wanting 2 threads each share one 4-core worker."""
    reg = FunctionRegistry()

    @reg.chunkwise(7)
    def f(c):
        return c + 1

    g = JobGraph()
    g.add_segment([Job("J1", 7, 2), Job("J2", 7, 2)])
    g.bind_input("J1", np.zeros(4, np.float32), n_chunks=2)
    g.bind_input("J2", np.zeros(4, np.float32), n_chunks=2)
    ex = LocalExecutor(VirtualCluster(n_schedulers=1, cores_per_worker=4,
                                      max_workers=4), reg)
    _, rep = ex.run(g)
    assert rep.segments[0].co_scheduled, "expected co-scheduling event"


def test_dynamic_jobs_iterate_to_convergence():
    """Paper §3.3/§4: a control job re-enqueues work until a condition —
    the Jacobi pattern."""
    reg = FunctionRegistry()

    @reg.chunkwise(1)
    def halve(c):
        return c / 2

    state = {"last": "H0", "iters": 0}

    @reg.control(9)
    def check(cd, ctx):
        v = float(np.max(np.abs(np.asarray(cd.get_data_chunk(0).data))))
        if v > 1.0:
            state["iters"] += 1
            nxt = f"H{state['iters']}"
            ctx.add_job(Job(nxt, 1, 0, (ChunkRef(state["last"]),)), 1)
            ctx.add_job(Job(f"C{state['iters']}", 9, 1, (ChunkRef(nxt),)), 2)
            state["last"] = nxt
        return cd

    g = JobGraph()
    g.add_segment([Job("H0", 1, 0)])
    g.add_segment([Job("C0", 9, 1, (ChunkRef("H0"),))])
    g.bind_input("H0", np.array([64.0]), n_chunks=1)
    ex = LocalExecutor(VirtualCluster(n_schedulers=1, max_workers=2), reg)
    res, _ = ex.run(g)
    # H0 already halves (64 -> 32); C_k re-enqueues until the value hits 1.0:
    # 32,16,8,4,2,1 -> five dynamic re-adds
    assert state["iters"] == 5
    final = float(np.asarray(res[state["last"]].to_array()).reshape(-1)[0])
    assert final <= 1.0


def test_fault_recovery_recomputes_lost_results():
    reg = FunctionRegistry()
    calls = {"n": 0}

    @reg.chunkwise(1)
    def produce(c):
        calls["n"] += 1
        return c * c

    @reg.whole(2)
    def consume(cd):
        return ChunkedData.from_arrays([sum(jnp.sum(a) for a in cd.arrays())])

    g = JobGraph()
    g.add_segment([Job("P", 1, 0, no_send_back=True)])
    g.add_segment([Job("Q", 2, 1, (ChunkRef("P"),))])
    g.bind_input("P", np.arange(6, dtype=np.float32), n_chunks=3)
    inj = FaultInjector().kill_after_jobs(worker=0, n=1)
    ex = ChaosLocalExecutor(VirtualCluster(n_schedulers=1, max_workers=3),
                            reg, inj)
    res, rep = ex.run(g)
    assert rep.recovered_jobs == ["P"]
    assert inj.killed == [0]
    assert float(res["Q"].to_array()) == pytest.approx(float((np.arange(6) ** 2).sum()))


def test_sent_back_results_survive_worker_loss():
    """Results sent back to the scheduler (default) are NOT lost when the
    worker dies — only retained (no_send_back) ones are (paper §3.1)."""
    reg = FunctionRegistry()

    @reg.chunkwise(1)
    def f(c):
        return c + 1

    @reg.whole(2)
    def g_(cd):
        return ChunkedData.from_arrays([sum(jnp.sum(a) for a in cd.arrays())])

    g = JobGraph()
    g.add_segment([Job("P", 1, 0)])  # send back (default)
    g.add_segment([Job("Q", 2, 1, (ChunkRef("P"),))])
    g.bind_input("P", np.zeros(4, np.float32), n_chunks=2)
    inj = FaultInjector().kill_after_jobs(worker=0, n=1)
    ex = ChaosLocalExecutor(VirtualCluster(n_schedulers=1, max_workers=2),
                            reg, inj)
    res, rep = ex.run(g)
    assert rep.recovered_jobs == []            # nothing to recompute
    assert float(res["Q"].to_array()) == pytest.approx(4.0)   # 4 x (0+1)


def test_straggler_speculation():
    reg = FunctionRegistry()

    @reg.chunkwise(1)
    def f(c):
        return c

    g = JobGraph()
    g.add_segment([Job("A", 1, 1)])
    g.add_segment([Job("B", 1, 1, (ChunkRef("A"),))])
    g.bind_input("A", np.zeros(2, np.float32), n_chunks=1)
    cluster = VirtualCluster(n_schedulers=1, max_workers=2)
    w0 = cluster.spawn_worker()
    w1 = cluster.spawn_worker()
    w0.slowdown = 10.0                          # degraded worker
    ex = LocalExecutor(cluster, reg, speculative_slowdown_threshold=2.0)
    _, rep = ex.run(g)
    assert any(s.speculated_jobs for s in rep.segments)


def test_release_consumed_results():
    reg = FunctionRegistry()

    @reg.chunkwise(1)
    def f(c):
        return c

    g = JobGraph()
    g.add_segment([Job("A", 1, 0, no_send_back=True)])
    g.add_segment([Job("B", 1, 0, (ChunkRef("A"),))])
    g.bind_input("A", np.zeros(4, np.float32), n_chunks=2)
    ex = LocalExecutor(VirtualCluster(n_schedulers=1, max_workers=2), reg)
    res, _ = ex.run(g, release_consumed=True)
    assert ex.store.records["A"].data is None   # released after consumption
    assert "B" in res
