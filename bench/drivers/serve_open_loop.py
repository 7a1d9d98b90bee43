"""Serve open loop: requests arrive on a fixed schedule (Poisson at the
mix's rate, drawn from the seed), whatever the system's state.

Set-up builds the system from the configuration, warms the decode program
and the chunk buckets the mix's prompts reach, then replays ``lead_in_s``
of traffic so the window opens on a loaded system.  The window lasts
``--seconds``: the harness submits each request once its due time passes
(``bench.submit``) and steps the scheduler (``bench.step``) in between.
TTFT and its censoring are timed from the due time.  A ``--trace 1`` run
profiles a window of ``trace_s`` seconds.

Correct: the widest gap by which a served token's float32 reference logit
lies below the reference's best, over a seeded sample of the requests the
window finished (``serve_common.check``)."""
from __future__ import annotations

import time
from collections import deque

from bench.drivers import serve_common
from bench.lib import device, traffic
from bench.lib.cell import log


def run(cell, jax) -> None:
    mix = cell.mix
    srv = serve_common.Server(cell, jax)
    reqs = traffic.requests(mix, cell.seed, vocab=srv.cfg.vocab_size,
                            horizon_s=cell.window_len)
    srv.warm(len(r["prompt"]) for r in reqs)
    rec = serve_common.Recorder(srv.sched)
    sched, clock = srv.sched, time.perf_counter
    lead = float(mix.get("lead_in_s", 0.0))

    pending = deque(reqs)
    subs: dict[int, dict] = {}
    unsubmitted = []
    # due time 0 is the window's opening; the window's requests and tokens
    # are counted from there, though the loop notices it up to a step late
    base = clock() + lead
    opened = False
    while True:
        now = clock()
        if not opened and now >= base:
            cell.open_window()
            cell.trace_begin(jax)
            opened = True
        if now >= base + cell.window_len:
            break
        while pending and base + pending[0]["due_s"] <= now:
            r = pending.popleft()
            due = base + r["due_s"]
            t_sub = clock()
            with jax.profiler.TraceAnnotation("bench.submit"):
                rid = sched.submit(r["prompt"], r["max_new"], arrival_s=due)
            if rid is None:
                unsubmitted.append({"due": due, "submit": t_sub,
                                    "prompt": r["prompt"], "tokens": [],
                                    "token_s": [], "finished": False})
            else:
                subs[rid] = {"due": due, "submit": t_sub,
                             "prompt": r["prompt"]}
        with jax.profiler.TraceAnnotation("bench.step"):
            busy = sched.step()
        if not busy and pending:
            time.sleep(max(0.0, min(base + pending[0]["due_s"] - clock(),
                                    0.002)))
    t_open, t_close = base, clock()
    cell.trace_end(jax)
    cell.close_window()
    cell.device_info = device.device_record(cell.devices)

    records = srv.records(subs) + unsubmitted
    # requests due in the window that the loop never reached
    for r in pending:
        due = base + r["due_s"]
        if due < t_close:
            records.append({"due": due, "submit": None, "prompt": r["prompt"],
                            "tokens": [], "token_s": [], "finished": False})
    window = [r for r in records if t_open <= r["due"] < t_close]
    cell.attempted = len(window)
    cell.failed = (srv.n_failed([rid for rid, s in subs.items()
                                 if t_open <= s["due"] < t_close])
                   + sum(1 for r in unsubmitted if t_open <= r["due"] < t_close))
    cell.data.update({"records": records, "open": t_open, "close": t_close,
                      "recorder": rec, "cfg": srv.cfg,
                      "body": srv.body,
                      "page_size": srv.engine.page_size,
                      "slots": srv.engine.batch,
                      "max_pages": srv.engine.max_pages,
                      "occupancy": None})
    log(f"serve: {len(window)} requests due in {t_close - t_open:.3f} s, "
        f"{sum(r['finished'] for r in window)} finished, "
        f"{len(rec.decodes)} decodes, {len(rec.chunks)} chunks")

    sample = serve_common.pick_sample(
        [r for r in records if t_open <= r["due"] < t_close], cell.seed,
        tokens=int(mix["check"]["tokens"]),
        max_requests=int(mix["check"]["max_requests"]))
    del sched
    srv.release()
    serve_common.check(cell, jax, sample)
