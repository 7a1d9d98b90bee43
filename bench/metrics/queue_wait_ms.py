"""Time requests waited in the serve scheduler's queue: the mean of the
program's ``serve.queue`` intervals (from the queue's acceptance to the
pop that gave the request a slot) that end inside the traced window.  A
mean and not a tail: a traced chat window of 5 s admits a handful."""
from bench.lib import spans
from bench.lib.cell import log


def read(cell):
    waits = spans.intervals_ms(cell, "serve.queue")
    if waits is None:
        return None
    log(f"spans: {len(waits)} requests admitted in the traced window")
    return sum(waits) / len(waits) if waits else None
