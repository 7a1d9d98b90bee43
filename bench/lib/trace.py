"""Profiler trace capture and its reduction to numbers.

:func:`start` runs the profiler (the caller stops it); :func:`summarize`
reads the ``.xplane.pb`` it wrote into a small summary (device op and
module intervals per chip, the harness's host spans); the functions below
that take a summary are the whole reduction from trace to metrics, so
they can be checked on a small recorded summary (``bench/tests``).

Summary layout::

    {"window": [start_ns, end_ns],
     "devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

Device planes are ``/device:TPU:<n>``; their "XLA Ops" line holds one event
per executed HLO op (a Pallas kernel is one op), "XLA Modules" one per
program execution.  Host spans are the ``bench.*`` annotations the harness
puts around its calls into the system."""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def start(jax, log_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the Python tracer costs every call
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def summarize(log_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: dict[str, dict] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            rec = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                rec[key].extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                                for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
    win = [e for e in host if e[0] == WINDOW_SPAN]
    if win:
        window = [win[0][1], win[0][1] + win[0][2]]
    else:
        starts = [e[1] for d in devices.values() for e in d["ops"]]
        ends = [e[1] + e[2] for d in devices.values() for e in d["ops"]]
        window = [min(starts), max(ends)] if starts else [0, 0]
    return {"window": window, "devices": devices, "host": host}


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def _clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    out = []
    for _, s, d in intervals:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return sorted(out)


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def window_s(summary: dict) -> float:
    lo, hi = summary["window"]
    return (hi - lo) / 1e9


def busy_s(summary: dict) -> float | None:
    """Seconds in which some op ran on the device, averaged over chips."""
    lo, hi = summary["window"]
    devs = summary["devices"]
    if not devs:
        return None
    per = [sum(b - a for a, b in union(_clip(d["ops"], lo, hi)))
           for d in devs.values()]
    return sum(per) / len(per) / 1e9


def idle_share(summary: dict) -> float | None:
    busy, win = busy_s(summary), window_s(summary)
    if busy is None or win <= 0:
        return None
    return 1.0 - busy / win


def events_matching(summary: dict, kind: str, needles) -> list:
    """``kind`` ("ops" | "modules") events inside the window whose name
    contains any of ``needles``, over all chips."""
    lo, hi = summary["window"]
    out = []
    for d in summary["devices"].values():
        for e in d[kind]:
            if any(n in e[0] for n in needles) and lo <= e[1] < hi:
                out.append(e)
    return out


def total_s(events) -> float:
    return sum(e[2] for e in events) / 1e9


def short_name(op: str) -> str:
    """An HLO op's instruction name (the text before `` = ``), marked where
    it is a Pallas kernel (``tpu_custom_call``)."""
    name = op.split(" = ")[0]
    if 'custom_call_target="tpu_custom_call"' in op:
        name += " (tpu_custom_call)"
    return name


def self_times(ops) -> list[tuple[str, float]]:
    """``(op, self seconds)`` of each op event: ops nest on the "XLA Ops"
    line (a ``while`` holds its body's ops), so each event's time less its
    direct children's."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    self_ns = [e[2] for e in evs]
    stack: list[int] = []
    for i, (_, s, d) in enumerate(evs):
        while stack and s >= evs[stack[-1]][1] + evs[stack[-1]][2]:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= d
        stack.append(i)
    return [(e[0], t / 1e9) for e, t in zip(evs, self_ns)]


def top_ops(summary: dict, n: int = 10) -> list:
    """The ``n`` device ops with the most self time in the window, by
    instruction name, averaged over chips: ``[[name, seconds], ...]``."""
    lo, hi = summary["window"]
    acc: dict[str, float] = {}
    for d in summary["devices"].values():
        inside = [e for e in d["ops"] if lo <= e[1] < hi]
        for op, t in self_times(inside):
            name = short_name(op)
            acc[name] = acc.get(name, 0.0) + t
    k = max(len(summary["devices"]), 1)
    return [[name, t / k] for name, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(summary: dict, n: int = 10) -> list:
    """The ``n`` longest idle gaps of the first chip, each labelled with
    the innermost harness span that held the host at the gap's middle
    (``idle`` where none did): ``[[label, seconds], ...]``."""
    lo, hi = summary["window"]
    if not summary["devices"]:
        return []
    first = summary["devices"][sorted(summary["devices"])[0]]
    busy = union(_clip(first["ops"], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [e for e in summary["host"] if e[0] != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) // 2
        holding = [e for e in spans if e[1] <= mid < e[1] + e[2]]
        label = min(holding, key=lambda e: e[2])[0] if holding else "idle"
        out.append([label, (b - a) / 1e9])
    return out
