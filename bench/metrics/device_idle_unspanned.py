"""Share of the traced window in which the first chip is idle and no span
of the job model (``hypar.*``) or of ``jacobi_hypar`` (``jacobi.*``) is
open on the host: the device idle time the program's spans leave
unexplained.  The program's spans are placed on the device trace's clock
by the window's opening (``bench.lib.spans.offset_ns``)."""
from bench.lib import spans, trace
from bench.lib.cell import log

PREFIXES = ("hypar.", "jacobi.")


def read(cell):
    got = spans.window_spans(cell)
    off = spans.offset_ns(cell)
    if not got or off is None or not cell.summary["devices"]:
        return None
    held = [s for s in got if s.name.startswith(PREFIXES)]
    if not held:
        return None
    lo, hi = cell.summary["window"]
    chip = cell.summary["devices"][sorted(cell.summary["devices"])[0]]
    busy = trace._clip(chip["ops"], lo, hi)
    busy += trace._clip([[s.name, s.t0 + off, s.t1 - s.t0] for s in held],
                        lo, hi)
    covered = sum(b - a for a, b in trace.union(busy))
    log(f"spans: {len(held)} job model spans in the traced window")
    return (1.0 - covered / (hi - lo)) * 100.0
