"""Drive a whole benchmark run on the CPU at a small size: the cell's files
are copied with the configuration's sizes overridden, and the run skips
the chip check.  For the benchmark's tests and rehearsals only."""
from __future__ import annotations

import copy
import json
import os

from bench import run as bench_run
from bench.tests import small

CHECKOUT = bench_run.CHECKOUT
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 16e9}


def run_small(tmp_path, workload: str, *, config: dict = None,
              traffic: dict = None, seed: int = 7, seconds: float = 1.0,
              trace: int = 0, control: bool = False) -> dict:
    """Run ``workload`` with its configuration updated by ``config`` and
    its traffic mix by ``traffic`` (``control``: the control in the
    program's place in the check); returns the result dict."""
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench = copy.deepcopy(bench)
    if workload in small.CELLS and not any(
            w["name"] == workload for w in bench["workloads"]):
        conf, cell = small.CELLS[workload]
        if not any(c["name"] == conf["name"] for c in bench["configs"]):
            bench["configs"].append(dict(conf))
        bench["workloads"].append(dict(cell))
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(CHECKOUT, conf["file"])) as f:
        cfg = json.load(f)
    cfg.update(config or {})
    cfg_path = os.path.join(str(tmp_path), "config.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    conf["file"] = cfg_path
    if traffic:
        with open(os.path.join(CHECKOUT, "bench", "traffic",
                               cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        mix.update(traffic)
        name = "small_" + cell["traffic"]
        tdir = os.path.join(CHECKOUT, "bench", "traffic")
        # traffic files are found by name in bench/traffic; the test copy
        # lives beside the configuration and is looked up through a link
        cell["traffic"] = os.path.relpath(os.path.join(str(tmp_path), name),
                                          tdir)
        with open(os.path.join(str(tmp_path), name + ".json"), "w") as f:
            json.dump(mix, f)
    bench_path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    return bench_run.main(argv, chip_check=False, peaks_override=CPU_PEAKS,
                          bench_path=bench_path, control=control)
