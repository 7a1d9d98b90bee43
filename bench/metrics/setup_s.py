"""Set-up: from process start to the window's opening (host clock)."""


def read(cell):
    return getattr(cell, "setup_s", None)
