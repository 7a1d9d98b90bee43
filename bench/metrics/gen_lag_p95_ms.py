"""How late the load generator ran: p95 of submit time minus due time over
the requests due in the window (host clock)."""
from bench.lib import window


def read(cell):
    d = cell.data
    if "records" not in d:
        return None
    p = window.percentile(window.submit_lags(d["records"], d["open"],
                                             d["close"]), 95)
    return None if p is None else p * 1e3
