"""Jacobi time per iteration: the whole window (whole solves back to back,
each from the ``jacobi_hypar`` call to ``x`` on the host) over all its
iterations (host clock)."""


def read(cell):
    d = cell.data
    if not d.get("iterations"):
        return None
    return d["window_s"] / d["iterations"] * 1e3
