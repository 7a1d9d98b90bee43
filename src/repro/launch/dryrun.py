"""Placement preview of a paper-format job file (§3.3 grammar).

Parses the file and runs the master scheduler segment by segment without
executing anything, printing each job's worker.

Usage::

    python -m repro.launch.dryrun --jobfile jobs.txt --workers 4 --strategy cost
"""
import argparse


def plan_jobfile(path: str, *, n_workers: int = 4, cores: int = 1,
                 strategy: str = "cost", verbose: bool = True) -> list:
    """Placement dry-run for a paper-format job file (§3.3 grammar).

    Parses the text, then runs the MasterScheduler segment by segment
    *without executing anything* — a static preview of worker assignment,
    co-scheduling, spawning, and (with ``strategy="cost"``) the cost-model
    estimates.  No results exist yet, so locality terms are zero; what the
    preview shows is the queue/co-schedule structure.
    """
    from repro.core import (MasterScheduler, ResultStore, VirtualCluster,
                            parse_job_file)

    graph = parse_job_file(path)
    cluster = VirtualCluster(n_schedulers=1, cores_per_worker=cores,
                             max_workers=n_workers)
    master = MasterScheduler(graph, cluster, strategy=strategy)
    store = ResultStore(cluster)
    plans = []
    for i, seg in enumerate(graph.segments):
        placements = master.plan_segment(seg.jobs, store)
        plans.append(placements)
        if verbose:
            print(f"S{i}:")
            for p in placements:
                co = (f" co={','.join(p.co_scheduled_with)}"
                      if p.co_scheduled_with else "")
                est = f" est={p.est_cost_s * 1e6:.1f}us" if strategy == "cost" else ""
                print(f"  {p.job.name} -> worker {p.worker.wid} "
                      f"(seq={p.n_sequences}){co}{est}")
    return plans


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobfile", required=True,
                    help="placement dry-run of a paper-format job file")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--strategy", choices=["greedy", "cost"], default="cost")
    args = ap.parse_args(argv)
    return plan_jobfile(args.jobfile, n_workers=args.workers,
                        strategy=args.strategy)


if __name__ == "__main__":
    main()
