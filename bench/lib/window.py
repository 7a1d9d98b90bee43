"""Window arithmetic on host-clock request records.

A request record is a dict with ``due`` (absolute host-clock due time),
``submit`` (when the harness handed it to the system, or None),
``token_s`` (completion time of each output token) and ``finished``.
The window is ``[open, close]`` on the same clock."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile (numpy's default); None if empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def due_in_window(reqs, open_s: float, close_s: float) -> list[dict]:
    return [r for r in reqs if open_s <= r["due"] < close_s]


def ttfts(reqs, open_s: float, close_s: float) -> list[float]:
    """Time to first token of every request due in the window, from its due
    time.  A request with no first token by the close counts with its wait
    so far (``close - due``), so a stall cannot drop out of the tail."""
    out = []
    for r in due_in_window(reqs, open_s, close_s):
        first = r["token_s"][0] if r["token_s"] else None
        out.append((first if first is not None else close_s) - r["due"])
    return out


def tokens_in_window(reqs, open_s: float, close_s: float) -> int:
    """Output tokens completed inside the window, of every request,
    finished or not."""
    return sum(1 for r in reqs for t in r["token_s"] if open_s <= t <= close_s)


def itl_gaps(reqs, open_s: float, close_s: float) -> list[float]:
    """Gaps between consecutive output tokens of one request, both inside
    the window."""
    out = []
    for r in reqs:
        ts = [t for t in r["token_s"] if open_s <= t <= close_s]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def submit_lags(reqs, open_s: float, close_s: float) -> list[float]:
    """How late the harness submitted each request due in the window."""
    return [r["submit"] - r["due"] for r in due_in_window(reqs, open_s, close_s)
            if r["submit"] is not None]
