"""Time from a request's slot to its first token (chunked prefill, one
chunk a step with the live batch's decode between): the mean of the
program's ``serve.prefill`` intervals that end inside the traced window.
A mean and not a tail: a traced chat window of 5 s holds a handful.

Also logs how far the harness's TTFT of each request whose first token
falls in the window lies from generator lag + ``serve.queue`` +
``serve.prefill`` (the TTFT split the three should add up to)."""
from bench.lib import spans
from bench.lib.cell import log


def read(cell):
    got = spans.window_spans(cell)
    if got is None:
        return None
    ends = spans.ends_inside(cell, got)
    prefills = [s for s in ends if s.name == "serve.prefill"]
    log(f"spans: {len(prefills)} first tokens in the traced window")
    if not prefills:
        return None
    queued = {s.key: s for s in got if s.name == "serve.queue"}
    lo, hi = cell.trace_span
    residuals = []
    for r in cell.data.get("records", []):
        if not r["token_s"] or not lo <= r["token_s"][0] <= hi:
            continue
        first = r["token_s"][0] * 1e9
        p = min(prefills, key=lambda s: abs(s.t1 - first))
        q = queued.get(p.key)
        if abs(p.t1 - first) > 1e6 or q is None or r["submit"] is None:
            continue
        parts = (r["submit"] - r["due"]) + (q.t1 - q.t0 + p.t1 - p.t0) / 1e9
        residuals.append((r["token_s"][0] - r["due"] - parts) * 1e3)
    if residuals:
        log(f"spans: TTFT less lag + queue + prefill, {len(residuals)} "
            f"requests: {[round(x, 3) for x in residuals]} ms")
    return sum((s.t1 - s.t0) / 1e6 for s in prefills) / len(prefills)
