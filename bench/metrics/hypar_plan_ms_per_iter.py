"""Host time per Jacobi iteration in the master's placement planning: the
self time of the program's ``hypar.plan`` spans
(``MasterScheduler.plan_segment``) in the traced window, over the window's
iterations."""
from bench.lib import spans


def read(cell):
    return spans.job_model_ms_per_iter(cell, "hypar.plan")
