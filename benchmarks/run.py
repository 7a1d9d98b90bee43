"""Benchmark harness entry point — one CLI over every sub-benchmark.

    python -m benchmarks.run [--suite kernels|jacobi|hypar|all]
                             [--paper] [--smoke]

Each suite writes a ``BENCH_<suite>.json`` file at the repo root with a
stable schema (the perf trajectory the ROADMAP tracks)::

    {"schema_version": 1,
     "rows": [{"name": ..., "backend": ..., "shape": [...], "dtype": ...,
               "median_s": ..., "bytes": ..., "flops": ..., ...}, ...]}

Suites:

  kernels — per-kernel reference timings + the autotune pass
            (``kernel_bench``): populates the persistent tuning cache, so
            a second run reuses tuned configs without re-timing (rows
            carry ``cache: hit|miss``).  -> BENCH_kernels.json
  jacobi  — the paper's Fig. 3 (framework vs tailored Jacobi, fused
            single-matvec iterations; ``--paper`` for the full
            2709/4209/7209 × 500 table).  -> BENCH_jacobi.json
  hypar   — framework-vs-tailored on the LM training workload.
            -> BENCH_hypar.json
  serve   — request-level continuous batching (Poisson trace, mixed
            prompt lengths) for --engine direct AND hypar: tok/s, TTFT,
            p50/p95 per-token latency, slot occupancy.
            -> BENCH_serve.json

``--smoke`` shrinks every suite to CI-sized shapes (used by the
benchmark-smoke CI step, which uploads the BENCH_*.json artifacts).
With ``--suite all`` the dry-run roofline table
(``benchmarks/results/dryrun.jsonl``, if present) is summarised as well.
"""
from __future__ import annotations

import argparse
import json
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEMA_VERSION = 1


def _write(filename: str, rows: list[dict]) -> None:
    path = os.path.join(REPO_ROOT, filename)
    with open(path, "w") as f:
        json.dump({"schema_version": SCHEMA_VERSION, "rows": rows}, f,
                  indent=1)
    print(f"-> wrote {path} ({len(rows)} rows)")


def suite_kernels(*, smoke: bool = False) -> list[dict]:
    print("== kernels (ref timings + autotune) ==")
    from . import kernel_bench
    rows = kernel_bench.run(smoke=smoke)
    for r in rows:
        extra = (f"  config={r['config']} cache={r['cache']}"
                 if "config" in r else "")
        print(f"  {r['name']:>28}: {r['median_s'] * 1e6:10.1f} us{extra}")
    _write("BENCH_kernels.json", rows)
    return rows


def suite_jacobi(*, paper: bool = False, smoke: bool = False) -> list[dict]:
    print("== jacobi_fig3 (paper Fig. 3, fused-residual sweeps) ==")
    from . import jacobi_paper
    if smoke:
        jrows = jacobi_paper.run(sizes=(256,), iters=50)
    else:
        jrows = jacobi_paper.main(quick=not paper)
    rows = jacobi_paper.bench_rows(jrows)
    _write("BENCH_jacobi.json", rows)
    return rows


def suite_hypar(*, smoke: bool = False) -> list[dict]:
    print("== hypar_lm (framework vs tailored, LM training) ==")
    from . import hypar_overhead
    from .kernel_bench import bench_row
    h = hypar_overhead.run(steps=2 if smoke else 4)
    rows = [bench_row(f"hypar_lm_{k}", (), "float32", h[f"{k}_s"],
                      overhead_pct=h["overhead_pct"] if k == "hypar" else 0.0)
            for k in ("tailored", "hypar")]
    print("== hypar_proc (process-worker vs thread dispatch) ==")
    p = hypar_overhead.run_proc_dispatch(
        **(dict(depth=4, dim=128, repeats=2) if smoke else {}))
    rows.append(bench_row("hypar_proc", (), "float64", p["proc_s"],
                          thread_s=p["thread_s"],
                          proc_vs_thread_pct=p["proc_vs_thread_pct"],
                          n_jobs=p["n_jobs"]))
    _write("BENCH_hypar.json", rows)
    return rows


def suite_serve(*, smoke: bool = False) -> list[dict]:
    print("== serve (request-level continuous batching, direct vs hypar) ==")
    from . import serve_bench
    rows = serve_bench.run(smoke=smoke)
    for r in rows:
        print(f"  {r['name']:>14}: {r['tok_per_s']:8.1f} tok/s  "
              f"ttft p50 {r['ttft_p50_s'] * 1e3:7.1f} ms  "
              f"lat p50/p95 {r['lat_p50_s'] * 1e3:6.1f}/"
              f"{r['lat_p95_s'] * 1e3:6.1f} ms  "
              f"occ {r['occupancy'] * 100:4.0f}%")
    _write("BENCH_serve.json", rows)
    return rows


def print_roofline() -> None:
    """Summarise the dry-run roofline table if present (produced by
    ``python -m repro.launch.dryrun --all``) — print-only, no BENCH file."""
    results = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "dryrun.jsonl")
    if not os.path.exists(results):
        return
    print("== roofline (from dry-run) ==")
    with open(results) as f:
        recs = [json.loads(l) for l in f if l.strip()]
    for r in recs:
        step_ms = max(r["compute_s"], r["memory_s"], r["collective_s"]) * 1e3
        print(f"  roofline_{r['arch']}_{r['cell']}_{r['mesh']}: "
              f"{step_ms:.1f} ms/step dom={r['dominant']} "
              f"frac={r['roofline_fraction'] * 100:.1f}%")


SUITES = {"kernels": suite_kernels, "jacobi": suite_jacobi,
          "hypar": suite_hypar, "serve": suite_serve}


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(prog="benchmarks.run", description=__doc__)
    p.add_argument("--suite", choices=[*SUITES, "all"], default="all")
    p.add_argument("--paper", action="store_true",
                   help="full paper sizes (2709/4209/7209 x 500 iters)")
    p.add_argument("--smoke", action="store_true",
                   help="CI-sized shapes for every suite")
    args = p.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.suite in ("kernels", "all"):
        suite_kernels(smoke=args.smoke)
    if args.suite in ("jacobi", "all"):
        suite_jacobi(paper=args.paper, smoke=args.smoke)
    if args.suite in ("hypar", "all"):
        suite_hypar(smoke=args.smoke)
    if args.suite in ("serve", "all"):
        suite_serve(smoke=args.smoke)
    if args.suite == "all":
        print_roofline()


if __name__ == "__main__":
    main()
