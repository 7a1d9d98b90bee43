"""Device idle share of the traced window: 1 - (union of the device op
intervals / window), averaged over the chips the cell uses."""
from bench.lib import trace


def read(cell):
    if cell.summary is None:
        return None
    idle = trace.idle_share(cell.summary)
    return None if idle is None else idle * 100.0
