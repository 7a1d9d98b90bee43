"""Host time per decode step of the serve loop: the mean, over the
program's ``serve.step`` spans that end inside the traced window and hold
a ``serve.decode``, of the step's length less its ``serve.wait`` spans
(the host blocked on the device's tokens and logits)."""
from bench.lib import spans
from bench.lib.cell import log


def read(cell):
    got = spans.window_spans(cell)
    if not got:
        return None
    inner = [s for s in got if s.name in ("serve.decode", "serve.wait")]
    host = []
    for step in spans.ends_inside(cell, got):
        if step.name != "serve.step":
            continue
        mine = [s for s in inner if s.thread == step.thread
                and step.t0 <= s.t0 and s.t1 <= step.t1]
        if not any(s.name == "serve.decode" for s in mine):
            continue
        wait = sum(s.t1 - s.t0 for s in mine if s.name == "serve.wait")
        host.append((step.t1 - step.t0 - wait) / 1e6)
    log(f"spans: {len(host)} decode steps in the traced window")
    return sum(host) / len(host) if host else None
