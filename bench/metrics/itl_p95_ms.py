"""p95 of the gaps between consecutive output tokens of one request, both
inside the window (host clock)."""
from bench.lib import window


def read(cell):
    d = cell.data
    if "records" not in d:
        return None
    p = window.percentile(window.itl_gaps(d["records"], d["open"],
                                          d["close"]), 95)
    return None if p is None else p * 1e3
