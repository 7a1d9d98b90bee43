"""Host spans of the program's hot paths: a flight recorder that is always on.

``span(name, key)`` times a block of host code twice: as a
``jax.profiler.TraceAnnotation``, so a profiler trace shows it beside the
device ops on the device trace's clock (TensorBoard, Perfetto), and as a
record in a bounded in-memory ring, which readers take with
``spans_between``.  ``interval`` records a span that starts and ends in
different calls (a request's wait in a queue); it goes to the ring only.

A record is ``Span(name, t0, t1, parent, key, thread)``: start and end in
``time.perf_counter_ns()`` nanoseconds, the name of the span open around it
on its thread (None at the top, and for an interval), the caller's key (a
job name, a request id, a segment index) and the recording thread's id
(None for an interval).  The ring keeps the newest ``CAPACITY`` records;
``spans_between`` returns None for a stretch of time the ring has already
dropped records from, so a reader never counts short.

Names are constants at each call site.  With the profiler off a span
costs a few microseconds of host time (the annotation is made only while a
trace runs), so a span per job or per scheduler phase is cheap; a span per
slot per step is not.
"""
from __future__ import annotations

import collections
import threading
import time
from threading import get_ident
from typing import NamedTuple

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Recorder", "RECORDER", "CAPACITY", "now", "span",
           "interval", "spans_between", "self_time"]

CAPACITY = 1 << 16

now = time.perf_counter_ns
_tracing = TraceAnnotation.is_enabled


class Span(NamedTuple):
    name: str
    t0: int
    t1: int
    parent: str | None
    key: object
    thread: int | None


class _Open:
    """One open span (the context manager ``Recorder.span`` returns)."""

    __slots__ = ("rec", "name", "key", "ann", "stack", "parent", "t0")

    def __init__(self, rec: "Recorder", name: str, key):
        self.rec, self.name, self.key = rec, name, key

    def __enter__(self):
        # an annotation records nothing unless a profiler trace runs, and
        # making one costs about as much as the rest of the span
        self.ann = TraceAnnotation(self.name) if _tracing() else None
        if self.ann is not None:
            self.ann.__enter__()
        local = self.rec._local
        try:
            stack = local.stack
        except AttributeError:
            stack = local.stack = []
        self.stack = stack
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.t0 = now()
        return self

    def __exit__(self, *exc):
        t1 = now()
        self.stack.pop()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        rec = self.rec
        with rec._lock:
            ring = rec._ring
            if len(ring) == ring.maxlen:
                rec._dropped(ring[0][2])
            ring.append((self.name, self.t0, t1, self.parent, self.key,
                         get_ident()))
        return False


class Recorder:
    """A ring of the newest ``capacity`` spans, appended as each ends."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: collections.deque[Span] = collections.deque(
            maxlen=capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        # end of the latest span the ring has dropped: no reader may trust
        # a stretch of time that reaches back to it
        self._dropped_until: int | None = None

    def _dropped(self, t1: int) -> None:
        if self._dropped_until is None or t1 > self._dropped_until:
            self._dropped_until = t1

    def span(self, name: str, key=None) -> _Open:
        return _Open(self, name, key)

    def interval(self, name: str, t0: int, t1: int, key=None) -> None:
        """Record a span timed by the caller (``now()`` nanoseconds)."""
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped(self._ring[0][2])
            self._ring.append((name, t0, t1, None, key, None))

    def spans_between(self, lo_s: float, hi_s: float) -> list[Span] | None:
        """The spans that overlap ``[lo_s, hi_s]`` (``time.perf_counter``
        seconds), oldest end first; None where the ring has dropped a span
        that could lie in that stretch."""
        lo, hi = int(lo_s * 1e9), int(hi_s * 1e9)
        with self._lock:
            if self._dropped_until is not None and self._dropped_until >= lo:
                return None
            return [Span._make(s) for s in self._ring
                    if s[2] >= lo and s[1] <= hi]


def self_time(spans) -> list[tuple[Span, int]]:
    """``(span, self nanoseconds)`` for each span: its duration less the
    part its direct children on the same thread cover.  Intervals (no
    thread) have no children."""
    out = []
    by_thread: dict = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    for thread, group in by_thread.items():
        if thread is None:
            out.extend((s, s.t1 - s.t0) for s in group)
            continue
        group = sorted(group, key=lambda s: (s.t0, -s.t1))
        own = [s.t1 - s.t0 for s in group]
        stack: list[int] = []
        for i, s in enumerate(group):
            while stack and s.t0 >= group[stack[-1]].t1:
                stack.pop()
            if stack:
                own[stack[-1]] -= s.t1 - s.t0
            stack.append(i)
        out.extend(zip(group, own))
    return out


#: the process's recorder: what the program's spans go to
RECORDER = Recorder()
span = RECORDER.span
interval = RECORDER.interval
spans_between = RECORDER.spans_between
