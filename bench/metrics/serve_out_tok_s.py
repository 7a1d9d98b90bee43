"""Output tokens completed inside the window, of finished and unfinished
requests alike, over the window's length (host clock)."""
from bench.lib import window


def read(cell):
    d = cell.data
    if "records" not in d:
        return None
    n = window.tokens_in_window(d["records"], d["open"], d["close"])
    return n / (d["close"] - d["open"])
