"""Host time per Jacobi iteration blocked on device results: the
program's ``hypar.wait`` spans in the traced window (a control job's host
copy of its inputs, J3's residual read, and the segment barrier), over the
window's iterations."""
from bench.lib import spans


def read(cell):
    return spans.job_model_ms_per_iter(cell, "hypar.wait")
