"""The window's longest sweep over its median one, by the records' own
``wall_s``: 1.0-1.1 in a steady window, 2 and more in one that held a
stall (whose record is then marked ``slow``)."""

import statistics

from benchmark import sweep_account


def read(run):
    records = sweep_account.window(run)
    if not records:
        return None
    walls = [r["wall_s"] for r in records]
    return max(walls) / statistics.median(walls)
