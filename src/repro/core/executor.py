"""Executors — how job graphs actually run.

All executors implement the :class:`BaseExecutor` contract
(``run(graph) -> (results, ExecutionReport)``) so launchers, benchmarks and
apps never special-case the runtime (DESIGN.md §2).

* :class:`LocalExecutor` — the *paper-faithful* runtime.  Workers are pinned
  to individual JAX devices; chunk transfers between devices are explicit
  (and accounted), ``no_send_back`` results stay on their worker's device.
  Worker failures lose retained results, which are recovered by re-executing
  the producing jobs from the graph (lineage recovery).  Dynamic jobs
  (control functions) are handled on the host, exactly like the paper's
  master re-enqueueing mechanism.

  Three dispatch modes (DESIGN.md §2.3):

  - ``mode="sync"`` — the paper's loop: placements execute one by one on the
    host thread; ``block_per_job=True`` additionally waits for each job's
    device work (precise per-worker timing, e.g. straggler experiments).
  - ``mode="pipelined"`` — per-worker dispatch queues: every placement of a
    segment is issued without host-side blocking (JAX async dispatch
    overlaps ``device_put`` input transfers with compute); the host waits
    once at the paper's segment barrier.  Control jobs drain on the host as
    their inputs complete.
  - ``mode="dataflow"`` — the barrier relaxed to true dataflow: a job in
    segment *k+1* whose inputs are all available is dispatched before
    segment *k* fully drains (the paper's strict barrier becomes an opt-in
    strictness level).

* :class:`SpmdExecutor` — the *beyond-paper* runtime for TPU pods.  A whole
  parallel segment is fused into one SPMD computation: same-function
  chunkwise jobs are batched over a stacked chunk axis and sharded across
  the mesh (the generalisation of the paper's worker co-scheduling), and
  GSPMD inserts the collectives the paper's schedulers would have sent as
  messages.  Self-re-enqueueing iterative patterns (the Jacobi J3) are fused
  into a single on-device ``lax.while_loop``.
"""
from __future__ import annotations

import abc
import concurrent.futures
import dataclasses
import threading
import time
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from .job import ChunkedData, DataChunk, GraphValidationError, Job, JobGraph
from .registry import ControlContext, FunctionKind, FunctionRegistry
from .scheduler import (CostModelParams, MasterScheduler, Placement,
                        ResultStore, VirtualCluster, Worker)
from .spans import self_time, span, spans_between

__all__ = [
    "ExecutionReport",
    "BaseExecutor",
    "LocalExecutor",
    "SpmdExecutor",
    "IterativeSpec",
]


# ---------------------------------------------------------------------------
# Reporting / monitoring (paper future work §5: "basic monitoring")
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SegmentReport:
    index: int
    jobs: list[str] = dataclasses.field(default_factory=list)
    moved_bytes: int = 0
    local_bytes: int = 0
    co_scheduled: list[tuple[str, ...]] = dataclasses.field(default_factory=list)
    recovered_jobs: list[str] = dataclasses.field(default_factory=list)
    speculated_jobs: list[str] = dataclasses.field(default_factory=list)
    # jobs served from a durable JobStore instead of executing (ProcessExecutor)
    memoised_jobs: list[str] = dataclasses.field(default_factory=list)
    # device each executed job ran on (job name -> worker device)
    job_devices: dict[str, Any] = dataclasses.field(default_factory=dict)


#: the job model's host phases, in the order ``summary()`` prints them
PHASES = ("hypar.run", "hypar.segment", "hypar.plan", "hypar.job",
          "hypar.wait")


@dataclasses.dataclass
class ExecutionReport:
    segments: list[SegmentReport] = dataclasses.field(default_factory=list)
    mode: str = "sync"
    # time.perf_counter() at the run's start and end (LocalExecutor): the
    # stretch whose recorded spans summary() reads
    host_span: tuple[float, float] | None = None

    @property
    def moved_bytes(self) -> int:
        return sum(s.moved_bytes for s in self.segments)

    @property
    def local_bytes(self) -> int:
        return sum(s.local_bytes for s in self.segments)

    @property
    def recovered_jobs(self) -> list[str]:
        return [j for s in self.segments for j in s.recovered_jobs]

    @property
    def memoised_jobs(self) -> list[str]:
        return [j for s in self.segments for j in s.memoised_jobs]

    @property
    def job_devices(self) -> dict[str, Any]:
        return {j: d for s in self.segments for j, d in s.job_devices.items()}

    def host_phases(self) -> dict[str, float] | None:
        """Host seconds of the run in each ``hypar.*`` phase, as self time
        (a phase's spans less the spans nested in them), so the phases add
        up to the run's wall time.  None where the run recorded no spans or
        the span recorder has already dropped some of them."""
        if self.host_span is None:
            return None
        spans = spans_between(*self.host_span)
        if spans is None:
            return None
        out = dict.fromkeys(PHASES, 0.0)
        for s, ns in self_time([s for s in spans if s.name in out]):
            out[s.name] += ns / 1e9
        return out

    def summary(self) -> str:
        phases = self.host_phases()
        host = ("n/a" if phases is None else " ".join(
            f"{name[6:]}={t:.4f}s" for name, t in phases.items()))
        return (f"mode={self.mode} segments={len(self.segments)} "
                f"moved={self.moved_bytes}B "
                f"local={self.local_bytes}B "
                f"recovered={len(self.recovered_jobs)} host: {host}")


# ---------------------------------------------------------------------------
# Unified executor contract
# ---------------------------------------------------------------------------


class BaseExecutor(abc.ABC):
    """What every runtime must provide: execute a JobGraph, return the
    results directory plus an :class:`ExecutionReport`.

    Implementations differ in *where* jobs run (per-device workers, SPMD
    mesh, …) but not in the contract, so ``launch/``, ``benchmarks/`` and
    ``apps/`` code is runtime-agnostic.
    """

    registry: FunctionRegistry

    @abc.abstractmethod
    def run(self, graph: JobGraph, **kwargs
            ) -> tuple[dict[str, Any], ExecutionReport]:
        """Execute the whole graph; returns (results by job name, report)."""


# ---------------------------------------------------------------------------
# Local (paper-faithful) executor
# ---------------------------------------------------------------------------

MODES = ("sync", "pipelined", "dataflow")


class LocalExecutor(BaseExecutor):
    """Dispatch jobs to per-device workers following the placement plan."""

    def __init__(self, cluster: VirtualCluster, registry: FunctionRegistry, *,
                 speculative_slowdown_threshold: float = 2.0,
                 block_per_job: bool = False,
                 mode: str = "sync",
                 strategy: str = "greedy",
                 cost_params: CostModelParams | None = None,
                 observed_fn_times: dict[Any, float] | None = None):
        if mode not in MODES:
            raise ValueError(f"unknown dispatch mode {mode!r}; pick from {MODES}")
        self.cluster = cluster
        self.registry = registry
        self.store = ResultStore(cluster)
        self.speculative_slowdown_threshold = speculative_slowdown_threshold
        # paper semantics: the barrier is at SEGMENT granularity — jobs are
        # dispatched asynchronously and the scheduler waits once per segment
        # (block_per_job=True restores per-job waits for precise worker
        # timing, e.g. in straggler experiments)
        self.block_per_job = block_per_job
        self.mode = mode
        self.strategy = strategy
        self.cost_params = cost_params
        # per-function wall-time seed for the master's queue-term EWMA
        # (e.g. tuned kernel timings, repro.kernels.tuning)
        self.observed_fn_times = observed_fn_times
        self._jit_cache: dict[Any, Callable] = {}
        # serialises store/report/graph mutation when worker queues dispatch
        # from threads; reentrant because lineage recovery recurses into
        # _execute_on
        self._lock = threading.RLock()
        self._queues: dict[int, concurrent.futures.ThreadPoolExecutor] = {}
        self._inflight: dict[int, int] = {}
        self._master: MasterScheduler | None = None

    # -- plumbing ----------------------------------------------------------------
    def _jitted(self, fid) -> Callable:
        with self._lock:
            if fid not in self._jit_cache:
                fn = self.registry[fid].fn
                # already-jitted user functions are reused as-is so their
                # compile cache survives across executors (the paper's users
                # register *compiled* functions)
                self._jit_cache[fid] = fn if hasattr(fn, "lower") else jax.jit(fn)
            return self._jit_cache[fid]

    def _queue(self, wid: int) -> concurrent.futures.ThreadPoolExecutor:
        """One single-threaded dispatch queue per worker: jobs placed on a
        worker issue in placement order, workers issue concurrently."""
        q = self._queues.get(wid)
        if q is None:
            q = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix=f"hypar-w{wid}")
            self._queues[wid] = q
        return q

    def _shutdown_queues(self) -> None:
        for q in self._queues.values():
            q.shutdown(wait=True)
        self._queues.clear()
        self._inflight.clear()

    def _resolve_inputs(self, job: Job, graph: JobGraph, report: SegmentReport,
                        worker: Worker) -> list[ChunkedData]:
        """Fetch each input ref, moving chunks to the worker's device.

        Lost results (dead worker + no_send_back) trigger lineage recovery:
        the producing job is re-executed (paper §3.1 names exactly this
        recompute cost as the drawback of result retention).
        """
        inputs: list[ChunkedData] = []
        for ref in job.inputs:
            rec = self.store.records.get(ref.job)
            if rec is None or rec.data is None:
                self._recover(ref.job, graph, report)
                rec = self.store.get(ref.job)
            sel = ref.select(rec.data)
            moved = []
            for c in sel:
                src_dev = (c.data.devices().pop()
                           if isinstance(c.data, jax.Array) and c.data.devices() else None)
                if src_dev is not None and src_dev != worker.device:
                    report.moved_bytes += c.nbytes
                    moved.append(DataChunk(jax.device_put(c.data, worker.device)))
                else:
                    report.local_bytes += c.nbytes
                    moved.append(c)
            inputs.append(ChunkedData(moved))
        if job.name in graph.bound_inputs:
            # bound inputs are sent to the worker again for every job that
            # reads them (the Jacobi sweeps' rows of A, each iteration)
            data = graph.bound_inputs[job.name]
            moved = []
            for c in data:
                on_dev = (isinstance(c.data, jax.Array) and c.data.devices()
                          and c.data.devices().pop() == worker.device)
                if on_dev:
                    report.local_bytes += c.nbytes
                    moved.append(c)
                else:
                    report.moved_bytes += c.nbytes
                    moved.append(DataChunk(jax.device_put(c.data, worker.device)))
            inputs.insert(0, ChunkedData(moved))
        return inputs

    def _recover(self, name: str, graph: JobGraph, report: SegmentReport) -> None:
        """Re-execute a job whose result was lost (recursively)."""
        job = graph.job(name)
        # choose any alive worker (fresh placement — the original is dead)
        alive = self.cluster.alive_workers()
        if not alive:
            worker = self.cluster.spawn_worker()
        else:
            worker = min(alive, key=lambda w: w.jobs_done)
        report.recovered_jobs.append(name)
        self._execute_on(job, worker, graph, report)

    # -- execution ----------------------------------------------------------------
    def _execute_on(self, job: Job, worker: Worker, graph: JobGraph,
                    report: SegmentReport,
                    ctx: ControlContext | None = None) -> ChunkedData:
        """Resolve inputs, run the registered function, record the result.

        The dispatch lock is held only around shared-state access (store
        reads + recovery + report counters, then store.put + feedback) and
        for the whole control branch (graph mutation); chunkwise/whole
        dispatch itself runs unlocked so worker queues overlap transfers
        and compiled-function dispatch.
        """
        rf = self.registry[job.fn]
        with span("hypar.job", job.name):
            with self._lock:
                inputs = self._resolve_inputs(job, graph, report, worker)
                report.job_devices[job.name] = worker.device
            t0 = time.perf_counter()
            if rf.kind == FunctionKind.CHUNKWISE:
                if not inputs:
                    raise GraphValidationError(
                        f"{job.name}: chunkwise function {job.fn!r} needs input chunks")
                fn = self._jitted(job.fn)
                zipped = list(zip(*[cd.arrays() for cd in inputs]))
                out_chunks = [DataChunk(fn(*args)) for args in zipped]
                out = ChunkedData(out_chunks)
            elif rf.kind == FunctionKind.WHOLE:
                out = rf.fn(*inputs)
                if not isinstance(out, ChunkedData):
                    out = ChunkedData.from_arrays(
                        out if isinstance(out, (list, tuple)) else [out])
            elif rf.kind == FunctionKind.CONTROL:
                with self._lock:
                    if ctx is None:
                        ctx = ControlContext(graph, job.segment)
                    with span("hypar.wait"):
                        host_inputs = [ChunkedData([DataChunk(np.asarray(c.data))
                                                    for c in cd]) for cd in inputs]
                    out = rf.fn(*host_inputs, ctx)
                    if out is None:
                        out = ChunkedData([])
                    elif not isinstance(out, ChunkedData):
                        out = ChunkedData.from_arrays(
                            out if isinstance(out, (list, tuple)) else [out])
                    for new_job, seg_idx in ctx.added:
                        graph.add_dynamic(new_job, seg_idx, current=job.segment)
            else:  # pragma: no cover
                raise GraphValidationError(f"unknown kind {rf.kind}")
            if self.block_per_job:
                with span("hypar.wait"):
                    for c in out:
                        if isinstance(c.data, jax.Array):
                            c.data.block_until_ready()
            elapsed = time.perf_counter() - t0
            with self._lock:
                worker.jobs_done += 1
                self.store.put(job, out, worker)
                if self._master is not None:
                    self._master.observe(job.fn, elapsed)
            return out

    def _maybe_speculate(self, p: Placement, sreport: SegmentReport) -> Worker:
        """Straggler mitigation: speculatively duplicate on a faster worker
        when the chosen one is degraded."""
        worker = p.worker
        if (worker.slowdown >= self.speculative_slowdown_threshold
                and len(self.cluster.alive_workers()) > 1):
            fast = min((w for w in self.cluster.alive_workers()
                        if w.wid != worker.wid),
                       key=lambda w: w.slowdown)
            if fast.slowdown < worker.slowdown:
                sreport.speculated_jobs.append(p.job.name)
                worker = fast
        return worker

    def _segment_barrier(self, names: Iterable[str]) -> None:
        """The paper's segment barrier: wait for every job of the segment."""
        with span("hypar.wait"):
            for name in names:
                rec = self.store.records.get(name)
                if rec is not None and rec.data is not None:
                    for c in rec.data:
                        if isinstance(c.data, jax.Array):
                            c.data.block_until_ready()

    def run(self, graph: JobGraph, *, release_consumed: bool = False
            ) -> tuple[dict, ExecutionReport]:
        """Execute the whole graph; returns (results by job name, report).

        ``release_consumed`` — after a segment completes, release results
        whose every consumer has already run (the paper's scheduler "signals
        them the data is no longer required").
        """
        report = ExecutionReport(mode=self.mode)
        self._master = MasterScheduler(graph, self.cluster,
                                       strategy=self.strategy,
                                       cost_params=self.cost_params,
                                       observed_fn_times=self.observed_fn_times)
        t0 = time.perf_counter()
        try:
            with span("hypar.run"):
                if self.mode == "sync":
                    self._run_sync(graph, report, release_consumed)
                elif self.mode == "pipelined":
                    self._run_pipelined(graph, report, release_consumed)
                else:
                    self._run_dataflow(graph, report, release_consumed)
        finally:
            self._shutdown_queues()
            self._master = None
        report.host_span = (t0, time.perf_counter())
        results = {name: rec.data for name, rec in self.store.records.items()
                   if rec.data is not None}
        return results, report

    # -- mode: sync (the paper's dispatch loop) --------------------------------
    def _run_sync(self, graph: JobGraph, report: ExecutionReport,
                  release_consumed: bool) -> None:
        master = self._master
        seg_idx = 0
        while seg_idx < len(graph.segments):
            with span("hypar.segment", seg_idx):
                segment = graph.segments[seg_idx]
                sreport = SegmentReport(index=seg_idx)
                executed: set[str] = set()
                # fixpoint over same-segment dynamic additions: control jobs
                # may add to the *current* segment, which needs a re-plan pass
                pending = list(segment.jobs)
                while pending:
                    placements = master.plan_segment(pending, self.store)
                    for p in placements:
                        if p.co_scheduled_with:
                            sreport.co_scheduled.append(
                                (p.job.name,) + p.co_scheduled_with)
                        worker = self._maybe_speculate(p, sreport)
                        ctx = ControlContext(graph, seg_idx)
                        self._execute_on(p.job, worker, graph, sreport, ctx)
                        executed.add(p.job.name)
                    pending = [j for j in segment.jobs if j.name not in executed]
                if not self.block_per_job:
                    self._segment_barrier(executed)
                sreport.jobs = list(segment.names())
                report.segments.append(sreport)
                if release_consumed:
                    self._release_dead_results(graph, seg_idx)
            seg_idx += 1

    # -- mode: pipelined (per-worker queues, strict segment barrier) -----------
    def _run_pipelined(self, graph: JobGraph, report: ExecutionReport,
                       release_consumed: bool) -> None:
        master = self._master
        seg_idx = 0
        while seg_idx < len(graph.segments):
            with span("hypar.segment", seg_idx):
                segment = graph.segments[seg_idx]
                sreport = SegmentReport(index=seg_idx)
                executed: set[str] = set()
                pending = list(segment.jobs)
                while pending:
                    placements = master.plan_segment(pending, self.store)
                    futures: list[concurrent.futures.Future] = []
                    for p in placements:
                        if p.co_scheduled_with:
                            sreport.co_scheduled.append(
                                (p.job.name,) + p.co_scheduled_with)
                        worker = self._maybe_speculate(p, sreport)
                        executed.add(p.job.name)
                        if self.registry[p.job.fn].kind == FunctionKind.CONTROL:
                            # host job: all deps live in earlier (drained)
                            # segments, so it runs immediately on the host
                            # thread while device queues fill
                            ctx = ControlContext(graph, seg_idx)
                            self._execute_on(p.job, worker, graph, sreport, ctx)
                        else:
                            futures.append(self._queue(worker.wid).submit(
                                self._execute_on, p.job, worker, graph, sreport))
                    for fut in futures:
                        fut.result()  # re-raises worker exceptions
                    pending = [j for j in segment.jobs if j.name not in executed]
                self._segment_barrier(executed)
                sreport.jobs = list(segment.names())
                report.segments.append(sreport)
                if release_consumed:
                    self._release_dead_results(graph, seg_idx)
            seg_idx += 1

    # -- mode: dataflow (relaxed barrier, FIRST_COMPLETED draining) ------------
    def _run_dataflow(self, graph: JobGraph, report: ExecutionReport,
                      release_consumed: bool) -> None:
        """Dependency-driven dispatch across segment boundaries.

        A job is dispatchable once every producer it references has finished
        *dispatching* (its result handle exists; device compute may still be
        in flight — JAX chains the data dependency).  Control jobs run on
        the host as their inputs complete, orco-style: the driver drains
        whichever future finishes first rather than a whole segment.
        Segments interleave here, so no ``hypar.segment`` span is recorded.
        """
        master = self._master
        futures: dict[str, tuple[concurrent.futures.Future, Worker, int]] = {}
        done: set[str] = set()          # device jobs with completed dispatch
        host_done: set[str] = set()     # executed control jobs
        seg_reports: dict[int, SegmentReport] = {}

        def sreport_for(seg: int) -> SegmentReport:
            if seg not in seg_reports:
                seg_reports[seg] = SegmentReport(index=seg)
            return seg_reports[seg]

        def harvest() -> None:
            for name, (fut, worker, seg) in list(futures.items()):
                if name in done or not fut.done():
                    continue
                fut.result()
                with self._lock:
                    self._inflight[worker.wid] = max(
                        0, self._inflight.get(worker.wid, 0) - 1)
                done.add(name)

        while True:
            harvest()
            finished = done | host_done
            pending = [j for j in graph.jobs()
                       if j.name not in futures and j.name not in host_done]
            waiting = [f for n, (f, _, _) in futures.items() if n not in done]
            if not pending:
                # drain before declaring done: only harvest() observes
                # results, so a future completing between harvest() and
                # here must not be skipped (it may hold an exception)
                if not waiting:
                    break
                concurrent.futures.wait(
                    waiting, return_when=concurrent.futures.FIRST_COMPLETED)
                continue
            ready = [j for j in pending
                     if all(d in finished for d in j.deps())]
            if not ready:
                if not waiting:  # pragma: no cover - valid graphs always progress
                    raise GraphValidationError(
                        f"dataflow deadlock: {[j.name for j in pending]} not ready")
                concurrent.futures.wait(
                    waiting, return_when=concurrent.futures.FIRST_COMPLETED)
                continue
            controls = [j for j in ready
                        if self.registry[j.fn].kind == FunctionKind.CONTROL]
            device_jobs = [j for j in ready if j not in controls]
            if device_jobs:
                with self._lock:
                    loads = dict(self._inflight)
                    placements = master.plan_segment(device_jobs, self.store,
                                                     loads=loads)
                for p in placements:
                    sr = sreport_for(p.job.segment)
                    if p.co_scheduled_with:
                        sr.co_scheduled.append((p.job.name,) + p.co_scheduled_with)
                    worker = self._maybe_speculate(p, sr)
                    with self._lock:
                        self._inflight[worker.wid] = \
                            self._inflight.get(worker.wid, 0) + 1
                    fut = self._queue(worker.wid).submit(
                        self._execute_on, p.job, worker, graph, sr)
                    futures[p.job.name] = (fut, worker, p.job.segment)
            for job in sorted(controls, key=lambda j: (j.segment, j.name)):
                sr = sreport_for(job.segment)
                worker = (self.cluster.alive_workers()
                          or [self.cluster.spawn_worker()])[0]
                ctx = ControlContext(graph, job.segment)
                self._execute_on(job, worker, graph, sr, ctx)
                host_done.add(job.name)

        # final barrier: everything must be device-complete before results
        # are handed back
        self._segment_barrier(done | host_done)
        for seg, sr in sorted(seg_reports.items()):
            sr.jobs = (graph.segments[seg].names()
                       if seg < len(graph.segments) else [])
            report.segments.append(sr)
        if release_consumed:
            for seg in range(len(graph.segments)):
                self._release_dead_results(graph, seg)

    def _release_dead_results(self, graph: JobGraph, done_segment: int) -> None:
        for name, rec in self.store.records.items():
            if rec.data is None:
                continue
            consumers = graph.consumers(name)
            if consumers and all(c.segment <= done_segment and
                                 c.name in self.store.records for c in consumers):
                self.store.release(name)


# ---------------------------------------------------------------------------
# SPMD (fused) executor — beyond-paper optimisation
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class IterativeSpec:
    """A self-re-enqueueing segment group (the paper's dynamic-job loop),
    declared explicitly so it can be fused to ``lax.while_loop``.

    ``body``  — f(carry) -> carry, the fused body of the repeated segments
    ``cond``  — f(carry) -> bool scalar
    ``max_iters`` — safety bound (the paper requires a *finite* number of
                    dynamic additions)
    """

    body: Callable
    cond: Callable
    max_iters: int = 10_000


class SpmdExecutor(BaseExecutor):
    """Fuse segments into SPMD computations over a device mesh.

    Same-function chunkwise job groups in a segment are stacked over the
    chunk axis and executed as ONE sharded computation (`vmap` over chunks,
    chunk axis sharded over the mesh's data axes).  ``no_send_back`` keeps
    outputs sharded in place; sent-back results are gathered (replicated) —
    exactly the communication the paper's workers would perform, but
    expressed as collectives that XLA can schedule/overlap.
    """

    def __init__(self, mesh: jax.sharding.Mesh, registry: FunctionRegistry, *,
                 chunk_axes: tuple[str, ...] | None = None):
        self.mesh = mesh
        self.registry = registry
        # chunk axis = all mesh axes by default (fully sharded chunk axis)
        self.chunk_axes = chunk_axes if chunk_axes is not None else tuple(mesh.axis_names)
        self.results: dict[str, Any] = {}     # job name -> stacked array(s)
        self._compiled: dict[Any, Callable] = {}

    # -- sharding helpers --------------------------------------------------------
    def _chunk_sharding(self, n_chunks: int):
        from jax.sharding import NamedSharding, PartitionSpec as P
        axes = []
        size = 1
        for a in self.chunk_axes:
            s = self.mesh.shape[a]
            if n_chunks % (size * s) == 0:
                axes.append(a)
                size *= s
            else:
                break
        spec = P(tuple(axes)) if axes else P()
        return NamedSharding(self.mesh, spec)

    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())

    # -- execution ----------------------------------------------------------------
    def _stacked_input(self, job: Job, graph: JobGraph) -> list[Any]:
        arrs = []
        if job.name in graph.bound_inputs:
            cd = graph.bound_inputs[job.name]
            arrs.append(jnp.stack(cd.arrays()))
        for ref in job.inputs:
            if ref.job not in self.results:
                raise GraphValidationError(f"{job.name}: missing result {ref.job}")
            val = self.results[ref.job]
            if not ref.whole:
                val = val[ref.lo:ref.hi]
            arrs.append(val)
        return arrs

    def _fused_chunkwise(self, fid, n_chunks: int, send_back: bool):
        key = (fid, n_chunks, send_back)
        if key not in self._compiled:
            fn = self.registry[fid].fn
            out_sh = self._replicated() if send_back else self._chunk_sharding(n_chunks)
            self._compiled[key] = jax.jit(
                jax.vmap(fn),
                in_shardings=None,   # let GSPMD propagate from operands
                out_shardings=out_sh)
        return self._compiled[key]

    def run(self, graph: JobGraph) -> tuple[dict[str, Any], ExecutionReport]:
        report = ExecutionReport(mode="spmd")
        for seg_idx, segment in enumerate(graph.segments):
            sreport = SegmentReport(index=seg_idx)
            # group same-function chunkwise jobs (worker co-scheduling,
            # generalised: ONE sharded call executes the whole group)
            groups: dict[Any, list[Job]] = {}
            singles: list[Job] = []
            for job in segment.jobs:
                rf = self.registry[job.fn]
                if rf.kind == FunctionKind.CHUNKWISE:
                    groups.setdefault(job.fn, []).append(job)
                else:
                    singles.append(job)
            for fid, jobs in groups.items():
                if len(jobs) > 1:
                    sreport.co_scheduled.append(tuple(j.name for j in jobs))
                ins = [self._stacked_input(j, graph) for j in jobs]
                counts = [i[0].shape[0] for i in ins]
                stacked = [jnp.concatenate([i[k] for i in ins], axis=0)
                           for k in range(len(ins[0]))]
                send_back = not all(j.no_send_back for j in jobs)
                fused = self._fused_chunkwise(fid, int(sum(counts)), send_back)
                out = fused(*stacked)
                # split the fused result back to per-job results
                off = 0
                for j, c in zip(jobs, counts):
                    self.results[j.name] = out[off:off + c]
                    off += c
            for job in singles:
                rf = self.registry[job.fn]
                ins = self._stacked_input(job, graph)
                if rf.kind == FunctionKind.WHOLE:
                    out = rf.fn(*[ChunkedData.from_arrays(list(a)) for a in ins])
                    self.results[job.name] = jnp.stack(out.arrays())
                elif rf.kind == FunctionKind.CONTROL:
                    ctx = ControlContext(graph, seg_idx)
                    host_ins = [ChunkedData.from_arrays([np.asarray(x) for x in a])
                                for a in ins]
                    out = rf.fn(*host_ins, ctx)
                    self.results[job.name] = (jnp.stack(out.arrays())
                                              if out is not None and len(out) else jnp.zeros((0,)))
                    for new_job, tgt in ctx.added:
                        graph.add_dynamic(new_job, tgt, current=seg_idx)
                else:  # pragma: no cover
                    raise GraphValidationError(f"unsupported kind {rf.kind}")
            sreport.jobs = list(segment.names())
            report.segments.append(sreport)
        return dict(self.results), report

    # -- iterative fusion (beyond-paper: dynamic-job loop -> while_loop) --------
    def run_iterative(self, spec: IterativeSpec, carry):
        """Fuse a convergence loop on device.

        The paper expresses iteration by letting a control job re-enqueue the
        body segments; host round-trips per iteration are the price.  On TPU
        we fuse body+condition into one ``lax.while_loop`` so the loop never
        leaves the device.  Both paths are benchmarked in
        ``benchmarks/jacobi_paper.py``.
        """
        key = ("iterative", id(spec))
        if key not in self._compiled:
            it = jnp.zeros((), jnp.int32)

            def cond(state):
                i, c = state
                return jnp.logical_and(i < spec.max_iters, spec.cond(c))

            def body(state):
                i, c = state
                return i + 1, spec.body(c)

            self._compiled[key] = jax.jit(
                lambda c: jax.lax.while_loop(cond, body, (it, c)))
        n_iters, final = self._compiled[key](carry)
        return final, int(n_iters)
