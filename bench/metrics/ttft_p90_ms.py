"""p90 time to first token over every request due in the window, timed
from its due time; a request with no first token at the close counts with
its wait so far (host clock).  p90, not p95: at the chat mix's rate a
51 s window holds about 80 requests, so p95 would rest on four samples
beyond it."""
from bench.lib import window


def read(cell):
    d = cell.data
    if "records" not in d:
        return None
    p = window.percentile(window.ttfts(d["records"], d["open"], d["close"]),
                          90)
    return None if p is None else p * 1e3
