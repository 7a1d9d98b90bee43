#!/usr/bin/env python3
"""Knee sweep of an open-loop serve mix, on the chip.

    python3 bench/tools/knee.py --workload qwen2-1.5b.chat --rates 2 4 6 8 --seconds 20

Runs the cell once per rate (the mix's ``rate_per_s`` replaced), in one
process, and prints one JSON line per rate: requests due, the share that
got a first token, TTFT p50/p95 over the window's first and second halves,
output tokens per second, and the requests still waiting for a first token
at the close.  A rate is sustained where the second half's TTFT tail does
not run away from the first half's and few requests wait at the close;
the knee is the highest such rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, CHECKOUT)
    from bench import run as bench_run
    from bench.lib import traffic, window

    real_load = traffic.load
    for rate in args.rates:
        def load(name, directory=traffic.TRAFFIC_DIR, _rate=rate):
            mix = real_load(name, directory)
            mix["rate_per_s"] = _rate
            return mix
        traffic.load = load
        import bench.lib.cell as cellmod
        seen = {}
        real_reduce = cellmod.Cell.reduce_trace

        def keep(self, _seen=seen):
            _seen["cell"] = self
            real_reduce(self)
        cellmod.Cell.reduce_trace = keep
        res = bench_run.main(["--workload", args.workload, "--seed",
                              str(args.seed), "--seconds", str(args.seconds),
                              "--trace", "0"])
        cellmod.Cell.reduce_trace = real_reduce
        d = seen["cell"].data
        lo, hi = d["open"], d["close"]
        mid = (lo + hi) / 2
        recs = d["records"]
        first = window.ttfts(recs, lo, mid)
        second = window.ttfts([r for r in recs if r["due"] >= mid], mid, hi)
        due = window.due_in_window(recs, lo, hi)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "first_token_share": sum(1 for r in due if r["token_s"])
            / max(len(due), 1),
            "waiting_at_close": sum(1 for r in due if not r["token_s"]),
            "ttft_p50_first_half_ms": window.percentile(first, 50) * 1e3,
            "ttft_p95_first_half_ms": window.percentile(first, 95) * 1e3,
            "ttft_p50_second_half_ms": window.percentile(second, 50) * 1e3,
            "ttft_p95_second_half_ms": window.percentile(second, 95) * 1e3,
            "serve_out_tok_s": res["metrics"]["serve_out_tok_s"]["value"],
            "itl_p95_ms": res["metrics"]["itl_p95_ms"]["value"],
            "correct": res["correct"]}), flush=True)


if __name__ == "__main__":
    main()
