"""What the ``minicpm_sala`` per-layer readers under ``benchmark/metrics/``
share: the lightning kernel's device time in the traced batches, and the rows
the window's sweeps dispatched to the kernel and to the XLA op."""

from __future__ import annotations

from benchmark import sweep_account

KERNEL = "pallas:lightning_attention"


def lightning_kernel_s(run) -> float | None:
    """Device seconds of the ``pallas:lightning_attention`` ops in the traced
    batches (``trace_reduce.op_label`` names a Pallas kernel ``pallas:`` and
    its own ``name``), or None where the trace has none: a program without
    the kernel, or a run that fell back to the XLA op."""
    tr = run.get("trace")
    if not tr:
        return None
    s = sum(sec for label, sec in tr["device_ops"] if label.rsplit("/", 1)[-1] == KERNEL)
    return s or None


def linear_rows(run) -> tuple[int, int] | None:
    """(rows x layers dispatched with the kernel, with the XLA op) summed
    over the window's sweeps, or None where the account has none (a program
    without the counters, or a window the account does not match)."""
    records = sweep_account.window(run)
    if not records or any("linear_rows_kernel" not in r for r in records):
        return None
    kernel = sum(r["linear_rows_kernel"] for r in records)
    xla = sum(r["linear_rows_xla"] for r in records)
    return (kernel, xla) if kernel + xla else None
