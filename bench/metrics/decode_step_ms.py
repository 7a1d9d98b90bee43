"""Device time per decode program call in the traced window: the
``PagedEngine`` decode program is the XLA module ``jit__decode``."""
from bench.lib import trace

MODULE = "jit__decode"


def read(cell):
    if cell.summary is None:
        return None
    evs = [e for e in trace.events_matching(cell.summary, "modules", [MODULE])
           if e[0].split("(")[0] == MODULE]
    return trace.total_s(evs) / len(evs) * 1e3 if evs else None
