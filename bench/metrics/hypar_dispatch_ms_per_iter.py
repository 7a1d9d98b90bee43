"""Host time per Jacobi iteration in job dispatch: the self time of the
program's ``hypar.job`` spans in the traced window (input resolution, the
registered function's call, ``store.put`` and the master's feedback; the
``hypar.wait`` spans inside them left out), over the window's
iterations."""
from bench.lib import spans


def read(cell):
    return spans.job_model_ms_per_iter(cell, "hypar.job")
