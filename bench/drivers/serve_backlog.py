"""Serve backlog: offline generation.  Every request of the mix is due at
once, more than the window can finish.

Set-up builds and warms the system, submits the whole backlog and steps
the scheduler until the first admission wave has prefilled (the batch is
full).  The first ``in_flight`` requests of the mix are part-way through
their outputs, so the window opens on a batch in mid-life, as a backlog's
batch is in its steady state.  The window then steps for ``--seconds``.
Where a request finishes inside the window, the next one of the backlog is
admitted and prefilled in its place.  A ``--trace 1`` run profiles a window
of ``trace_s`` seconds.

Correct: as for the open loop (``serve_common.check``)."""
from __future__ import annotations

import time

import numpy as np

from bench.drivers import serve_common
from bench.lib import device, traffic
from bench.lib.cell import log


def run(cell, jax) -> None:
    mix = cell.mix
    srv = serve_common.Server(cell, jax)
    reqs = traffic.requests(mix, cell.seed, vocab=srv.cfg.vocab_size,
                            horizon_s=0.0)
    srv.warm(len(r["prompt"]) for r in reqs)
    rec = serve_common.Recorder(srv.sched)
    sched, clock = srv.sched, time.perf_counter

    subs: dict[int, dict] = {}
    t0 = clock()
    for r in reqs:
        with jax.profiler.TraceAnnotation("bench.submit"):
            rid = sched.submit(r["prompt"], r["max_new"], arrival_s=t0)
        if rid is None:
            raise RuntimeError(f"backlog request {r['i']} refused: the mix "
                               f"does not fit the configuration")
        subs[rid] = {"due": t0, "submit": t0, "prompt": r["prompt"]}
    sched.step()
    while any(st.prefilling for st in sched.slots):
        sched.step()
    live = sum(1 for st in sched.slots if st.request is not None)
    kv = sum(st.pos for st in sched.slots if st.request is not None)
    log(f"serve: opening on {live} live slots, {kv} tokens of KV written "
        f"({100.0 * kv / (srv.engine.num_pages * srv.engine.page_size):.1f}%"
        f" of the pool)")
    steps0, occ0 = sched.n_steps, sched.occupied_slot_steps

    t_open = cell.open_window()
    cell.trace_begin(jax)
    while True:
        if clock() >= t_open + cell.window_len:
            break
        with jax.profiler.TraceAnnotation("bench.step"):
            if not sched.step():
                raise RuntimeError("the backlog ran dry inside the window")
    t_close = clock()
    cell.trace_end(jax)
    cell.close_window()
    cell.device_info = device.device_record(cell.devices)

    n_steps = sched.n_steps - steps0
    occupancy = ((sched.occupied_slot_steps - occ0)
                 / (n_steps * srv.engine.batch) if n_steps else None)
    records = srv.records(subs)
    started = [r for r in records if r["token_s"]]
    cell.attempted = len(started)
    cell.failed = srv.n_failed(list(subs))
    cell.data.update({"records": records, "open": t_open, "close": t_close,
                      "recorder": rec, "cfg": srv.cfg,
                      "body": srv.body,
                      "page_size": srv.engine.page_size,
                      "slots": srv.engine.batch,
                      "max_pages": srv.engine.max_pages,
                      "occupancy": occupancy})
    log(f"serve: batch of {live} live at the opening, {n_steps} steps in "
        f"{t_close - t_open:.3f} s, {sum(r['finished'] for r in records)} "
        f"finished, occupancy {occupancy}")
    # the time between decodes, so that a stall in the window shows where
    # it fell and how long it lasted
    _, decodes = rec.in_span(t_open, t_close)
    marks = np.array([t_open] + [t for t, _ in decodes])
    if len(marks) > 1:
        gaps = np.diff(marks)
        slow = np.argsort(gaps)[::-1][:3]
        log(f"serve: step gaps median {1e3 * np.median(gaps):.1f} ms, "
            f"longest " + ", ".join(f"{1e3 * gaps[i]:.1f} ms at "
                                    f"{marks[i + 1] - t_open:.2f} s"
                                    for i in slow))

    sample = serve_common.pick_sample(
        records, cell.seed, tokens=int(mix["check"]["tokens"]),
        max_requests=int(mix["check"]["max_requests"]))
    del sched
    srv.release()
    serve_common.check(cell, jax, sample)
