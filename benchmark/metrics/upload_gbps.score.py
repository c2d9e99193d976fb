"""GB/s the link carries WHILE it carries: a sweep's streamed bytes over the
union of its ``upload`` intervals (each from the ``device_put`` call to where
the program's completion thread saw the bytes arrive). Beside the rate probed
in set-up it says whether the host memory path is the limit."""

from benchmark import sweep_account


def read(run):
    return sweep_account.median_of(run, lambda r: r["upload_bytes"] / 1e9 / r["upload_busy_s"])
