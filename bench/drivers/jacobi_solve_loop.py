"""Jacobi solve loop: whole solves of the paper's Jacobi through the HyPar
job graph (``repro.apps.jacobi.jacobi_hypar``), back to back.

Set-up draws ``A`` and ``b`` on the device from the seed and runs one short
solve, which compiles every program a solve runs.  The window then calls
``jacobi_hypar`` with the mix's iteration count until ``--seconds`` have
passed; each solve is timed from the call to ``x`` on the host, so it
includes the per-call set-up a user pays.  The window ends with the last
whole solve.  A ``--trace 1`` run profiles a window of ``trace_s`` seconds
(its first solve).

Correct: every solve ran exactly the mix's iterations, its ``x`` is within
the configuration's limit of the plain float32 numpy reference, and (more
than one chip) its sweep jobs ran on every chip.  ``x`` reaches its float32
fixed point within a handful of iterations, so two more witnesses hold the
solve to all of its sweeps: the job report must name every sweep job
``S<k>_<i>`` of every iteration and row chunk, and in a traced run the
device must have executed as many sweep-sized programs (ones that last at
least half the time the smallest row chunk of ``A`` takes to stream from
HBM at the chip's peak; nothing shorter can have read it).

In a control run (``cell.control``, set only by ``bench/tools/controls.py``)
the ``x`` compared is the control's: the plain iteration on the device with
``A`` stored in bfloat16 (``control_solve`` of the configuration's reference,
``bench/reference/jacobi.py``)."""
from __future__ import annotations

import gc
import time

from bench import reference
from bench.lib import device
from bench.lib.cell import log


def sweep_devices(job_devices: dict) -> set:
    """Devices the sweep jobs (``S<iteration>_<chunk>``) ran on."""
    return {str(dev) for name, dev in job_devices.items()
            if name.startswith("S")}


def sweep_jobs_off(job_devices: dict, iters: int, n_chunks: int) -> int:
    """Sweep jobs missing from, or extra to, one per iteration and row
    chunk in a solve's job report."""
    got = {name for name in job_devices if name.startswith("S")}
    want = {f"S{k}_{i}" for k in range(iters) for i in range(n_chunks)}
    return len(got ^ want)


def sweep_runs(summary: dict, min_s: float) -> int:
    """Device program executions in the traced window that last at least
    ``min_s`` (on all chips): the sweeps, whatever their programs' names."""
    lo, hi = summary["window"]
    return sum(1 for d in summary["devices"].values()
               for _, s, dur in d["modules"]
               if lo <= s < hi and dur >= min_s * 1e9)


class PauseClock:
    """Seconds the garbage collector held the process, by generation."""

    def __init__(self):
        self.s = [0.0, 0.0, 0.0]
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.s[info["generation"]] += time.perf_counter() - self._t0

    def total(self) -> float:
        return sum(self.s)

    def stop(self) -> None:
        gc.callbacks.remove(self._on)


def run(cell, jax) -> None:
    import numpy as np

    from repro.apps.jacobi import jacobi_hypar
    from repro.core import VirtualCluster

    cfg, mix = cell.config, cell.mix
    ref = reference.find(cfg["system"])
    n, iters, tol = int(cfg["n"]), int(mix["iterations"]), float(mix["tol"])
    n_chunks = int(cfg["n_chunks"])
    devs = cell.devices

    def solve(k: int):
        cluster = VirtualCluster(devs, max_workers=int(cfg["max_workers"]))
        return jacobi_hypar(A, b, iters=k, tol=tol, n_chunks=n_chunks,
                            cluster=cluster, mode=cfg["mode"],
                            strategy=cfg["strategy"])

    A, b = ref.make_system(jax, n, cell.seed, device=devs[0])
    A.block_until_ready()
    solve(int(mix.get("warm_iterations", 3)))

    pauses = PauseClock()
    t_open = cell.open_window()
    solves = []
    cell.trace_begin(jax)
    while time.perf_counter() - t_open < cell.window_len:
        with jax.profiler.TraceAnnotation("bench.solve"):
            gc0, cpu0 = pauses.total(), time.thread_time()
            t0 = time.perf_counter()
            r = solve(iters)
            t1 = time.perf_counter()
        jd = r.extra["job_devices"]
        solves.append({"wall_s": t1 - t0, "graph_s": r.seconds,
                       "iters": r.iters, "x": r.x,
                       "gc_s": pauses.total() - gc0,
                       "cpu_s": time.thread_time() - cpu0,
                       "devices": sweep_devices(jd),
                       "jobs_off": sweep_jobs_off(jd, iters, n_chunks)})
    t_close = time.perf_counter()
    pauses.stop()
    cell.trace_end(jax)
    cell.close_window()
    cell.reduce_trace()
    cell.device_info = device.device_record(devs)
    cell.attempted = len(solves)
    per_chunk = sweep_bytes(n, n_chunks)
    cell.data.update({
        "window_s": t_close - t_open,
        "iterations": sum(s["iters"] for s in solves),
        "graph_s": sum(s["graph_s"] for s in solves),
        "solves": len(solves),
        "sweep_bytes": per_chunk,
        "n": n,
    })
    log(f"jacobi: {len(solves)} solves in {t_close - t_open:.3f} s, "
        f"walls {[round(s['wall_s'], 4) for s in solves]}, graph "
        f"{[round(s['graph_s'], 4) for s in solves]}, main-thread cpu "
        f"{[round(s['cpu_s'], 4) for s in solves]}, gc pauses "
        f"{[round(s['gc_s'], 4) for s in solves]} (window by generation "
        f"{[round(t, 4) for t in pauses.s]})")

    xs = [s["x"] for s in solves]
    if cell.control:
        x_ctl = np.asarray(ref.control_solve(jax, A, b, iters))
        xs = [x_ctl]
    # the reference runs once the window has closed, on the host
    A_host, b_host = np.asarray(A), np.asarray(b)
    del A, b
    gc.collect()
    x_ref = ref.solve(A_host, b_host, iters)
    if cell.control:
        log(f"control: program x_err "
            f"{max(ref.rel_err(s['x'], x_ref) for s in solves)!r}")
    cell.check("x_err", max(ref.rel_err(x, x_ref) for x in xs),
               float(cfg["limits"]["x_err"]))
    cell.check("iters_off", float(max(abs(s["iters"] - iters)
                                      for s in solves)), 0.0)
    cell.check("sweep_jobs_off", float(max(s["jobs_off"] for s in solves)),
               0.0)
    if cell.summary is not None and cell.summary["devices"]:
        min_s = 0.5 * min(per_chunk) / cell.peaks["hbm_bytes_per_s"]
        want = iters * n_chunks * len(solves)
        got = sweep_runs(cell.summary, min_s)
        log(f"jacobi: {got} sweep-sized device runs (>= {min_s * 1e6:.1f} "
            f"us) for {want} sweeps")
        cell.check("sweep_runs_missing", float(max(0, want - got)), 0.0)
    if len(devs) > 1:
        cell.check("chips_unused", float(len(devs) - min(
            len(s["devices"]) for s in solves)), 0.0)


def sweep_bytes(n: int, n_chunks: int) -> list[int]:
    """HBM bytes one sweep program must move per row chunk: its rows of
    ``A``, the whole iterate, its ``b`` and diagonal rows, its new rows
    (float32).  Chunk sizes follow ``np.array_split``."""
    import numpy as np
    rows = [len(c) for c in np.array_split(np.arange(n), n_chunks)]
    return [4 * (r * n + n + 3 * r) for r in rows]
