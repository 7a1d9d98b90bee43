#!/usr/bin/env python3
"""How far the program's recorded spans lie from the profiler's copy of
them, over one traced Jacobi solve.

    python3 bench/tools/span_align.py [--n 7209] [--iters 500] [--seed 1]

The program records each ``hypar.*`` and ``jacobi.*`` span twice: as a
``TraceAnnotation`` in the profiler's trace and in its in-memory ring
(``repro.core.spans``).  The harness places the ring's spans on the
trace's clock by one anchor, the opening of its ``bench.window``
annotation (``bench/lib/spans.py``).  This tool traces one whole solve
under that anchor, matches each recorded span to the trace's event of the
same name in order, and prints the median and the largest distance of
their starts and ends, in microseconds, and the solve's host time per
iteration by span and the span that holds it.  Without ``--cpu`` it needs
a TPU, and the distances are the chip host's."""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import statistics
import sys
import tempfile
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PREFIXES = ("hypar.", "jacobi.")


def trace_events(log_dir: str, names) -> dict[str, list[tuple[int, int]]]:
    """``name -> [(start_ns, end_ns), ...]`` of the host events whose
    names start with one of ``names``, or equal ``bench.window``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out: dict[str, list[tuple[int, int]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(names) or ev.name == "bench.window":
                    s = int(ev.start_ns)
                    out.setdefault(ev.name, []).append(
                        (s, s + int(ev.duration_ns)))
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=7209)
    ap.add_argument("--iters", type=int, default=500)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cpu", action="store_true",
                    help="run on JAX's first device whatever it is")
    args = ap.parse_args(argv)
    sys.path.insert(0, CHECKOUT)
    from bench.lib import device, trace
    caches = device.configure_caches()
    import jax
    device.enable_jax_cache(jax, caches["jax_cache"])
    from bench.reference import jacobi as ref
    from repro.apps.jacobi import jacobi_hypar
    from repro.core.spans import self_time, spans_between

    devs = jax.devices()[:1] if args.cpu else device.require_chip(jax, 1)
    A, b = ref.make_system(jax, args.n, args.seed, device=devs[0])
    jacobi_hypar(A, b, iters=3, n_chunks=args.chunks)
    log_dir = tempfile.mkdtemp(prefix="span_align_")
    try:
        trace.start(jax, log_dir)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            t_open = time.perf_counter()
            jacobi_hypar(A, b, iters=args.iters, n_chunks=args.chunks)
            t_close = time.perf_counter()
        jax.profiler.stop_trace()
        theirs = trace_events(log_dir, PREFIXES)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    mine = [s for s in spans_between(t_open, t_close)
            if s.name.startswith(PREFIXES)]
    offset = theirs[trace.WINDOW_SPAN][0][0] - int(round(t_open * 1e9))
    errs, counts = [], {}
    for name in sorted({s.name for s in mine}):
        ours = sorted((s.t0, s.t1) for s in mine if s.name == name)
        got = sorted(theirs.get(name, []))
        counts[name] = (len(ours), len(got))
        for (t0, t1), (a, z) in zip(ours, got):
            errs += [abs(a - offset - t0), abs(z - offset - t1)]
    # the solve's host time by phase and by what holds the span, in self
    # milliseconds per iteration (the barrier's wait apart from J3's read);
    # jobs by kind: S sweeps (J1), X residual (J2), C check (J3)
    split: dict[str, float] = {}
    for s, ns in self_time(mine):
        kind = f" {s.key[0]}" if s.name == "hypar.job" else ""
        key = f"{s.name}{kind} in {s.parent}"
        split[key] = split.get(key, 0.0) + ns / 1e6 / args.iters
    res = {"device": devs[0].device_kind, "spans": counts,
           "median_us": statistics.median(errs) / 1e3 if errs else None,
           "max_us": max(errs) / 1e3 if errs else None,
           "self_ms_per_iter": {k: round(v, 4) for k, v in
                                sorted(split.items())}}
    print(res, flush=True)
    return res


if __name__ == "__main__":
    main()
