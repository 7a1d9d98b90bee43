#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, driver and metric readers are
found by name from ``BENCHMARK.json`` (see ``bench/lib/cell.py``).  Set-up
(loading, warming every shape the cell uses, compiling where the cache
misses) ends when the measured window opens; the window lasts ``--seconds``.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` runs the
profiler over the window and reports its per-layer metrics.  Every run
checks what the timed path produced against the plain reference and prints
each compared number beside its limit, on standard error and last in the
result line.  The last line of standard output is the result, as JSON.

Exits 3, printing no result, where JAX finds no TPU or fewer chips than the
cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, chip_check: bool = True, peaks_override=None,
         bench_path: str | None = None, control: bool = False) -> dict:
    """Run the cell; returns the result dict (also printed).  ``chip_check``,
    ``peaks_override`` and ``bench_path`` exist for the benchmark's own
    tests, which drive a run on the CPU at a small size; the command line
    always checks for the chip.  ``control`` puts the cell's control in the
    program's place in the check (``bench/tools/controls.py``): such a run
    must come out not correct."""
    args = parse(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")
    if CHECKOUT not in sys.path:
        sys.path.insert(0, CHECKOUT)
    from bench.lib import cell as cellmod
    from bench.lib import device
    from bench.lib.peaks import peaks

    caches = device.configure_caches()
    with open(bench_path or os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    c = cellmod.Cell(bench, args.workload, seed=args.seed,
                     seconds=args.seconds, trace_on=bool(args.trace),
                     t_start=T_START, control=control)
    import jax
    device.enable_jax_cache(jax, caches["jax_cache"])
    c.watch = device.CompileWatch(jax)
    try:
        c.devices = (device.require_chip(jax, c.chips) if chip_check
                     else jax.devices()[:c.chips])
    except device.NoChip as e:
        cellmod.log(f"bench: {e.msg}")
        raise
    d = c.devices[0]
    cellmod.log(f"device: platform={d.platform} kind={d.device_kind} "
                f"count={len(c.devices)} jax={jax.__version__}")
    c.peaks = peaks_override if peaks_override is not None else peaks(
        d.device_kind)
    driver = cellmod.load_module(
        os.path.join(cellmod.BENCH_DIR, "drivers", f"{c.mix['driver']}.py"),
        f"bench_driver_{c.mix['driver']}")
    driver.run(c, jax)
    c.reduce_trace()
    res = c.result()
    cs, cw = c.compile_setup, c.compile_window
    cellmod.log(f"setup: {c.setup_s:.3f} s, compile {cs['compile_s']:.3f} s, "
                f"cache misses {cs['misses']} {cs['missed']}, cache loads "
                f"{cs['hits']}")
    cellmod.log(f"window: compiles {cw['misses']} (cache misses) "
                f"{cw['missed']}, cache loads {cw['hits']}, trace/lower/"
                f"compile {cw['compile_s']:.3f} s")
    for name, value, limit, ok in c.checks:
        cellmod.log(f"check {name} {value!r} limit {limit!r} "
                    f"{'ok' if ok else 'FAIL'}")
    print(json.dumps(res), flush=True)
    return res


if __name__ == "__main__":
    main()
