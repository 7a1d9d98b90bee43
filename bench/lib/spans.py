"""The program's own host spans (``repro.core.spans``) in a traced window.

The program records a span around each phase of its job model and serve
loop, into the profiler's trace and into an in-memory ring on the clock of
``time.perf_counter_ns``.  The readers here take the ring's spans that
overlap the traced window (``cell.trace_span``) and place them on the
device trace's clock: the harness opens its ``bench.window`` annotation
and reads ``time.perf_counter`` at the same moment, so the window's first
nanosecond in the trace (``summary["window"][0]``) less ``trace_span[0]``
is the offset between the two clocks.

Every reader returns None, without raising, where there is nothing to
read: no traced window, a program without the recorder (an older commit),
a ring that dropped spans from the window, or no span of the kind asked
for."""
from __future__ import annotations


def window_spans(cell):
    """The recorder's spans that overlap the traced window, or None."""
    if cell.trace_span is None:
        return None
    try:
        from repro.core.spans import spans_between
    except ImportError:
        return None
    return spans_between(*cell.trace_span)


def offset_ns(cell) -> int | None:
    """Device-trace nanoseconds less recorder nanoseconds, from the
    window's opening on both clocks; None without a device trace."""
    if cell.summary is None or cell.trace_span is None:
        return None
    return int(cell.summary["window"][0]) - int(round(cell.trace_span[0]
                                                      * 1e9))


def ends_inside(cell, spans_) -> list:
    """The spans that end inside the traced window."""
    lo, hi = (int(t * 1e9) for t in cell.trace_span)
    return [s for s in spans_ if lo <= s.t1 <= hi]


def job_model_ms_per_iter(cell, name: str) -> float | None:
    """Self milliseconds of the program's ``name`` spans (a ``hypar.*``
    phase) over the traced window's Jacobi iterations: spans of the job
    model nested in them (``hypar.wait`` in ``hypar.job``) are left out."""
    from bench.lib.cell import log
    got = window_spans(cell)
    iters = cell.data.get("iterations")
    if not got or not iters:
        return None
    from repro.core.spans import self_time
    own = [ns for s, ns in self_time([s for s in got
                                      if s.name.startswith("hypar.")])
           if s.name == name]
    if not own:
        return None
    log(f"spans: {len(own)} {name} spans in {iters} iterations")
    return sum(own) / iters / 1e6


def intervals_ms(cell, name: str) -> list[float] | None:
    """Lengths (ms) of the program's ``name`` intervals that end inside
    the traced window."""
    got = window_spans(cell)
    if got is None:
        return None
    return [(s.t1 - s.t0) / 1e6 for s in ends_inside(cell, got)
            if s.name == name]
