"""One run of one cell: finds the cell's configuration, traffic mix,
driver and metric readers by name, and prints the result line.

Everything that belongs to one configuration, mix, driver or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json`` — the configuration as it is run;
* ``bench/traffic/<traffic>.json`` — the mix's parameters and ``driver``;
* ``bench/drivers/<driver>.py`` — ``run(cell)``: set-up, window, check;
* ``bench/metrics/<metric>.py`` — ``read(cell) -> float | None``; a name
  with a dot (``decode_step_ms.chat``) falls back to the reader of the part
  before the first dot (``decode_step_ms.py``).
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import device, trace, traffic

BENCH_DIR = device.BENCH_DIR


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    for stem in (name, name.split(".")[0]):
        path = os.path.join(bench_dir, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return load_module(path, f"bench_metric_{stem.replace('.', '_')}")
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{bench_dir}/metrics")


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


class Cell:
    """What a driver gets: the cell's files, the run's arguments, and the
    places to put what it measured (``data``), its window, its trace and
    the numbers its correctness check compared."""

    def __init__(self, bench: dict, workload: str, *, seed: int,
                 seconds: float, trace_on: bool, t_start: float,
                 control: bool = False, bench_dir: str = BENCH_DIR):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: "
                             f"{sorted(cells)}")
        self.bench = bench
        self.workload = cells[workload]
        self.name = workload
        conf = {c["name"]: c for c in bench["configs"]}[self.workload["config"]]
        with open(os.path.join(device.CHECKOUT, conf["file"])) as f:
            self.config = json.load(f)
        self.mix = traffic.load(self.workload["traffic"],
                                os.path.join(bench_dir, "traffic"))
        self.chips = int(self.workload["chips"])
        self.seed, self.seconds, self.trace_on = seed, seconds, trace_on
        self.t_start = t_start
        # a control run puts the cell's lower-precision control in the
        # program's place in the check (bench/tools/controls.py only)
        self.control = control
        self.t_open: float | None = None
        self.data: dict = {}
        self.summary: dict | None = None
        self.checks: list[tuple[str, float, float, bool]] = []
        self.attempted = 0
        self.failed = 0
        self.devices: list = []
        self.peaks: dict = {}
        self.compile_setup: dict = {}
        self.compile_window: dict = {}
        self.device_info: dict = {}
        self._trace_dir: str | None = None
        self.trace_span: tuple[float, float] | None = None
        self._trace_ann = None
        self._trace_t0 = 0.0
        self.watch = None              # device.CompileWatch, set by run.py

    # -- set-up / window -------------------------------------------------------
    @property
    def window_len(self) -> float:
        """Seconds the window lasts: ``--seconds``, or in a ``--trace 1``
        run the mix's ``trace_s`` where that is shorter (the whole window is
        traced, and the trace stops only after the window has closed)."""
        if self.trace_on:
            return min(self.seconds, float(self.mix.get("trace_s",
                                                         self.seconds)))
        return self.seconds

    def open_window(self) -> float:
        """Set-up ends here: every shape is warm."""
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - self.t_start
        self.compile_setup = self.watch.snapshot()
        return self.t_open

    def close_window(self) -> None:
        self.compile_window = device.CompileWatch.delta(
            self.compile_setup, self.watch.snapshot())

    def trace_begin(self, jax) -> None:
        """Start the profiler (only in a ``--trace 1`` run) and open the
        ``bench.window`` span that bounds the traced window."""
        if not self.trace_on or self._trace_dir is not None:
            return
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        trace.start(jax, self._trace_dir)
        self._trace_ann = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
        self._trace_ann.__enter__()
        self._trace_t0 = time.perf_counter()

    def trace_end(self, jax) -> None:
        if self._trace_ann is None:
            return
        self._trace_ann.__exit__(None, None, None)
        self._trace_ann = None
        self.trace_span = (self._trace_t0, time.perf_counter())
        jax.profiler.stop_trace()

    def reduce_trace(self) -> None:
        """Read the trace into its summary and delete the files."""
        if self._trace_dir is not None:
            try:
                self.summary = trace.summarize(self._trace_dir)
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)
                self._trace_dir = None

    # -- correctness -----------------------------------------------------------
    def check(self, name: str, value: float, limit: float) -> bool:
        """One compared number: correct while ``value <= limit``."""
        ok = (value is not None and not math.isnan(value)
              and value <= limit)
        self.checks.append((name, value, limit, ok))
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c[3] for c in self.checks)

    # -- result ------------------------------------------------------------------
    def metrics(self) -> dict:
        key = "per_layer" if self.trace_on else "end_to_end"
        out = {}
        for m in self.bench[key]:
            if not applies(m, self.name):
                continue
            value = metric_reader(m["name"]).read(self)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def result(self) -> dict:
        res = {"correct": self.correct, "attempted": int(self.attempted),
               "failed": int(self.failed), "metrics": self.metrics(),
               "device": dict(self.device_info)}
        if self.summary is not None:
            res["device"]["busy_s"] = trace.busy_s(self.summary)
            res["device"]["window_s"] = trace.window_s(self.summary)
            res["breakdown"] = {"device_ops": trace.top_ops(self.summary),
                                "idle_gaps": trace.idle_gaps(self.summary)}
        res["checks"] = {name: {"value": v, "limit": lim}
                         for name, v, lim, _ in self.checks}
        return res


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)
