"""Job-model time per iteration: ``JacobiResult.seconds`` (the program's
own clock around ``LocalExecutor.run``) over the window's iterations.  The
gap to ``jacobi_ms_per_iter`` is the per-solve set-up of ``jacobi_hypar``."""


def read(cell):
    d = cell.data
    if not d.get("iterations"):
        return None
    return d["graph_s"] / d["iterations"] * 1e3
