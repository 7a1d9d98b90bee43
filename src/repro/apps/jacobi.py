"""The paper's §4 validation workload: a parallel Jacobi solver for
``A·x = b`` built three ways.

1. ``jacobi_hypar``   — the paper's decomposition: job J1 computes the
   update sweep over row chunks, J2 applies updates + computes the
   residual, J3 (a control job) checks convergence and *re-enqueues*
   J1/J2 — the exact dynamic-job mechanism of paper §3.3.  Runs on the
   LocalExecutor (scheduler/worker dispatch cost included).
2. ``jacobi_tailored`` — the 'tailored MPI implementation' stand-in: a
   hand-written jitted ``lax.while_loop`` (zero framework overhead).
3. ``jacobi_spmd``     — beyond-paper: the HyPar iterative segment fused to
   one on-device ``while_loop`` by the SpmdExecutor (framework
   expressiveness at tailored speed).

All three variants use the **fused-residual sweep**: each iteration
performs exactly ONE ``A``-matvec — the residual it convergence-tests is
``‖b - A·x_{k-1}‖``, already resident in the sweep's accumulator, instead
of a second matvec against the fresh iterate (the sweep jobs J1 emit the
squared-residual partials alongside their x' rows and J2 only reduces
them).  The tested residual is therefore lagged by one iteration — for a
fixed-iteration run (the paper's setup, tol=0) the iterates are
identical, and the exact final residual is recomputed once outside the
timed loop for reporting.

Paper's claim (Fig. 3): the framework stays within ~10 % (mean) of the
tailored runtime at sizes 2709/4209/7209, 500 iterations.  The benchmark
cell ``jacobi-7209.paper`` (``bench/``) times this solver at the largest
size on the chip.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ChunkedData, ChunkRef, DataChunk, FunctionRegistry,
                        Job, JobGraph, LocalExecutor, IterativeSpec,
                        SpmdExecutor, VirtualCluster)
from repro.core.spans import span
from repro.kernels.jacobi_sweep.ops import jacobi_sweep_residual
from repro.kernels.runtime import on_tpu
from repro.kernels.tuning import calibrated_cost_params, get_tuner

__all__ = ["make_system", "jacobi_tailored", "jacobi_hypar", "jacobi_spmd",
           "JacobiResult"]


@dataclasses.dataclass
class JacobiResult:
    x: np.ndarray
    iters: int
    residual: float
    seconds: float
    extra: dict = dataclasses.field(default_factory=dict)


def make_system(n: int, seed: int = 0):
    """Diagonally-dominant dense system with a known solution."""
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((n, n)).astype(np.float32) / n
    np.fill_diagonal(A, 3.0)
    x_true = rng.standard_normal(n).astype(np.float32)
    b = (A @ x_true).astype(np.float32)
    return jnp.asarray(A), jnp.asarray(b), x_true


# ---------------------------------------------------------------------------
# 1. tailored ("efficient MPI implementation" stand-in)
# ---------------------------------------------------------------------------


def jacobi_tailored(A, b, *, iters: int = 500, tol: float = 0.0,
                    kernel: bool = False) -> JacobiResult:
    diag = jnp.diag(A)

    def sweep(x):
        """(x', ‖b - A·x‖) in ONE matvec (fused-residual sweep)."""
        if kernel:
            return jacobi_sweep_residual(A, x, b, diag)
        r = b - A @ x
        return x + r / diag, jnp.linalg.norm(r)

    def cond(state):
        i, x, res = state
        return jnp.logical_and(i < iters, res > tol)

    def body(state):
        i, x, _ = state
        x2, res = sweep(x)          # res is ‖b - A·x‖, lagged one iteration
        return i + 1, x2, res

    run = jax.jit(lambda x0: jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), x0, jnp.asarray(jnp.inf))))
    x0 = jnp.zeros_like(b)
    run(x0)[1].block_until_ready()          # compile outside the timing
    t0 = time.perf_counter()
    i, x, _ = run(x0)
    x.block_until_ready()
    dt = time.perf_counter() - t0
    res = float(jnp.linalg.norm(b - A @ x))   # exact, outside the timed loop
    return JacobiResult(np.asarray(x), int(i), res, dt)


# ---------------------------------------------------------------------------
# 2. HyPar job graph (paper-faithful: J1 sweep, J2 residual, J3 dynamic)
# ---------------------------------------------------------------------------


def jacobi_hypar(A, b, *, iters: int = 500, tol: float = 0.0,
                 n_chunks: int = 4, cluster: VirtualCluster | None = None,
                 mode: str = "sync", strategy: str = "greedy") -> JacobiResult:
    with span("jacobi.setup"):
        ex, graph, state = _hypar_graph(A, b, iters=iters, tol=tol,
                                        n_chunks=n_chunks, cluster=cluster,
                                        mode=mode, strategy=strategy)
    t0 = time.perf_counter()
    results, report = ex.run(graph)
    dt = time.perf_counter() - t0
    with span("jacobi.finish"):
        k = state["iter"]
        x = np.asarray(results[f"X{k}"].get_data_chunk(0).data)
        # exact residual: outside ex.run's clock, inside the caller's
        res = float(jnp.linalg.norm(b - A @ jnp.asarray(x)))
        return JacobiResult(x, k + 1, res, dt,
                            extra={"report": report, "mode": mode,
                                   "moved_bytes": report.moved_bytes,
                                   "job_devices": report.job_devices})


def _hypar_graph(A, b, *, iters: int, tol: float, n_chunks: int,
                 cluster: VirtualCluster | None, mode: str, strategy: str):
    """``jacobi_hypar``'s per-call set-up: the registry, the iteration-0
    graph with its bound row chunks, the executor, and warm user kernels.
    Returns ``(executor, graph, state)``; ``state["iter"]`` is the last
    iteration J3 enqueued."""
    n = b.shape[0]
    diag = jnp.diag(A)
    reg = FunctionRegistry()
    A_rows = ChunkedData.from_array(A, n_chunks)          # row-chunked A
    b_c = ChunkedData.from_array(b, n_chunks)
    d_c = ChunkedData.from_array(diag, n_chunks)
    bounds = np.cumsum([0] + [c.data.shape[0] for c in A_rows])

    # J1: one fused-residual sweep over a row chunk.  Row chunk i needs
    # x[rows_i] for the diagonal correction; the row offset is
    # closure-specialised per chunk.  Each sweep emits its x' rows AND the
    # chunk's squared-residual partial Σ(b_i - A_i·x)² — the residual of
    # the incoming iterate is free once A_i·x is computed, so J2 never
    # pays a second matvec.
    # Whole-fn contract: args are ChunkedData — cds[0] is the bound static
    # data (A_i, b_i, d_i [, x0]), cds[1] (if present) is R_{X_{k-1}}.
    # The array work is jitted (the paper's users register *compiled*
    # functions; eager per-op dispatch is not part of the framework cost).
    def make_sweep(lo, hi):
        @jax.jit
        def kernel(A_chunk, b_chunk, d_chunk, x_full):
            xi = jax.lax.dynamic_slice(x_full, (lo,), (hi - lo,))
            r = b_chunk - A_chunk @ x_full        # incl. the diagonal term
            return xi + r / d_chunk, jnp.sum(r * r)

        def sweep(*cds):
            st = cds[0]
            x_full = (cds[1].get_data_chunk(0).data if len(cds) > 1
                      else st.get_data_chunk(3).data)
            x2, rsq = kernel(st.get_data_chunk(0).data,
                             st.get_data_chunk(1).data,
                             st.get_data_chunk(2).data, x_full)
            return ChunkedData.from_arrays([x2, rsq])
        return sweep

    state = {"iter": 0}

    @jax.jit
    def _residual_kernel(xs, rsqs):
        # reduce the sweeps' partials: no matvec here — the residual is the
        # lagged ‖b - A·x_{k-1}‖ the sweeps computed for free
        return jnp.concatenate(xs), jnp.sqrt(jnp.sum(jnp.stack(rsqs)))

    def residual_fn(*cds):
        # one ChunkedData per sweep job; chunk 0 = x' rows, chunk 1 = Σr²
        x_new, res = _residual_kernel(
            [cd.get_data_chunk(0).data for cd in cds],
            [cd.get_data_chunk(1).data for cd in cds])
        return ChunkedData.from_arrays([x_new, res])

    reg.register("residual", residual_fn, kind="whole")

    def check_fn(cd, ctx):
        res = float(np.asarray(cd.get_data_chunk(1).data))
        state["res"] = res
        if res > tol and state["iter"] < iters - 1:
            state["iter"] += 1
            _enqueue_iteration(ctx)
        return cd

    reg.register("check", check_fn, kind="control")
    for i in range(n_chunks):
        reg.register(f"sweep{i}", make_sweep(int(bounds[i]), int(bounds[i + 1])),
                     kind="whole")

    graph = JobGraph()
    xc = ChunkedData.from_arrays([jnp.zeros_like(b)])

    def _sweep_jobs(k: int, x_ref: str | None):
        jobs = []
        for i in range(n_chunks):
            name = f"S{k}_{i}"
            inputs = (ChunkRef(x_ref),) if x_ref else ()
            rows = float(bounds[i + 1] - bounds[i])
            jobs.append(Job(name, f"sweep{i}", 0, inputs, no_send_back=True,
                            cost_hint=2.0 * rows * n))
        return jobs

    def _enqueue_iteration(ctx):
        k = state["iter"]
        seg = ctx.current_segment
        jobs = _sweep_jobs(k, f"X{k - 1}")
        for j in jobs:
            ctx.add_job(j, 1)
        ctx.add_job(Job(f"X{k}", "residual", 1,
                        tuple(ChunkRef(j.name) for j in jobs),
                        cost_hint=float(n)), 2)
        ctx.add_job(Job(f"C{k}", "check", 1, (ChunkRef(f"X{k}"),)), 3)

    # initial iteration 0 (bound inputs: A/b/diag per chunk + x0)
    jobs0 = _sweep_jobs(0, None)
    graph.add_segment(jobs0)
    for i, j in enumerate(jobs0):
        graph.bind_input(j.name, ChunkedData([
            A_rows.get_data_chunk(i), b_c.get_data_chunk(i),
            d_c.get_data_chunk(i), xc.get_data_chunk(0)]))
    graph.add_segment([Job("X0", "residual", 1,
                           tuple(ChunkRef(j.name) for j in jobs0),
                           cost_hint=float(n))])
    graph.add_segment([Job("C0", "check", 1, (ChunkRef("X0"),))])

    # tuned timings -> scheduler: seed the master's per-function time table
    # from the autotune cache (full-sweep time scaled by the chunk's row
    # share) and calibrate the cost model with observed kernel rates, so
    # strategy="cost" prices jobs with measurements instead of roofline
    # guesses (DESIGN.md §7).  Only on TPU: there the cache holds real
    # kernel timings; off-TPU it holds interpret-mode proxies, orders of
    # magnitude slower than the jitted jnp kernels these jobs execute.
    observed = {}
    if on_tpu():
        t_full = get_tuner().observed_s("jacobi_sweep", (n, n), b.dtype,
                                        nearest=True)
        if t_full is not None and t_full > 0:
            for i in range(n_chunks):
                rows = float(bounds[i + 1] - bounds[i])
                observed[f"sweep{i}"] = t_full * rows / n
    cost_params = (calibrated_cost_params() if strategy == "cost" and on_tpu()
                   else None)

    cluster = cluster or VirtualCluster(n_schedulers=1, max_workers=n_chunks)
    ex = LocalExecutor(cluster, reg, mode=mode, strategy=strategy,
                       cost_params=cost_params,
                       observed_fn_times=observed or None)

    # warm the jitted user kernels (compile outside the timed region, as for
    # the tailored baseline)
    x_w = jnp.zeros_like(b)
    parts = []
    for i in range(n_chunks):
        rf = reg[f"sweep{i}"]
        parts.append(rf.fn(ChunkedData([A_rows.get_data_chunk(i),
                                        b_c.get_data_chunk(i),
                                        d_c.get_data_chunk(i),
                                        DataChunk(x_w)])))
    _residual_kernel([p.get_data_chunk(0).data for p in parts],
                     [p.get_data_chunk(1).data for p in parts]
                     )[1].block_until_ready()

    # bind per-chunk static inputs for dynamically added sweep jobs as they
    # appear: the executor reads bound_inputs at dispatch; pre-bind for all
    # possible iterations lazily via a hook on add_dynamic
    orig_add = graph.add_dynamic

    def add_dynamic(job, seg_idx, *, current):
        orig_add(job, seg_idx, current=current)
        if job.fn and str(job.fn).startswith("sweep"):
            i = int(str(job.fn)[5:])
            graph.bind_input(job.name, ChunkedData([
                A_rows.get_data_chunk(i), b_c.get_data_chunk(i),
                d_c.get_data_chunk(i)]))
    graph.add_dynamic = add_dynamic
    return ex, graph, state


# ---------------------------------------------------------------------------
# 3. SPMD-fused iterative segment (beyond-paper)
# ---------------------------------------------------------------------------


def jacobi_spmd(A, b, *, iters: int = 500, tol: float = 0.0,
                mesh=None) -> JacobiResult:
    diag = jnp.diag(A)

    def body(carry):
        x, _ = carry
        r = b - A @ x                 # the iteration's ONLY matvec
        return x + r / diag, jnp.linalg.norm(r)

    def cond(carry):
        return carry[1] > tol         # lagged residual ‖b - A·x_{k-1}‖

    if mesh is None:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    ex = SpmdExecutor(mesh, FunctionRegistry())
    spec = IterativeSpec(body=lambda c: body(c), cond=cond, max_iters=iters)
    x0 = (jnp.zeros_like(b), jnp.asarray(jnp.inf))
    # warmup/compile
    ex.run_iterative(spec, x0)
    t0 = time.perf_counter()
    (x, _), n_it = ex.run_iterative(spec, x0)
    x.block_until_ready()
    dt = time.perf_counter() - t0
    res = float(jnp.linalg.norm(b - A @ x))   # exact, outside the timed loop
    return JacobiResult(np.asarray(x), n_it, res, dt)
