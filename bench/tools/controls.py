#!/usr/bin/env python3
"""The controls, through the harness, on the chip.

    python3 bench/tools/controls.py --workload <name> --seeds 1 2 3 [--seconds 10]

For each seed, one whole run of the cell (``--seconds`` long) in which the
check compares the cell's control in the program's place: for the Jacobi
cells the plain iteration with ``A`` stored in bfloat16, on the device;
for the serve cells, at each position of the
served tokens, the token that the reference with float8 e4m3 weights,
computed in bfloat16, puts first.  Each run must print ``correct: false``;
its standard error also gives the program's own reading on the same run
(``control: program ...``), the lower reading beside the control's upper.

Runs every seed in one process, since a chip belongs to one process at a
time."""
from __future__ import annotations

import argparse
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, CHECKOUT)
    from bench import run as bench_run
    for seed in args.seeds:
        bench_run.main(["--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds), "--trace", "0"],
                       control=True)


if __name__ == "__main__":
    main()
