"""Share of the sweep in which no weight upload was in flight: what a fuller
pipeline could win."""

from benchmark import sweep_account


def read(run):
    return sweep_account.median_of(run, lambda r: 100.0 * (1.0 - r["upload_busy_s"] / r["wall_s"]))
