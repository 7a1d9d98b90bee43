"""Share of the HBM roofline reached by the J1 sweep programs of the
traced solve: the bytes each sweep must move (its rows of A, the iterate,
its b and diagonal rows, its new rows; ``jacobi_solve_loop.sweep_bytes``)
at the chip's HBM bandwidth, over the device time of those programs.

The sweep is the jitted ``kernel`` closure inside ``jacobi_hypar``
(XLA module ``jit_kernel``); a matvec does 2 flops per 4-byte element, so
bandwidth bounds it."""
from bench.lib import trace

MODULE = "jit_kernel"


def read(cell):
    if cell.summary is None:
        return None
    evs = [e for e in trace.events_matching(cell.summary, "modules", [MODULE])
           if e[0].split("(")[0] == MODULE]
    if not evs:
        return None
    per_chunk = cell.data["sweep_bytes"]
    bytes_moved = len(evs) * sum(per_chunk) / len(per_chunk)
    t = trace.total_s(evs)
    return bytes_moved / cell.peaks["hbm_bytes_per_s"] / t * 100.0
