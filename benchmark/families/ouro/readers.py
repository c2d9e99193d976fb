"""What the ``ouro`` per-layer readers under ``benchmark/metrics/`` share: the
flash attention kernels' device time in the traced batches, and the window's
decoder-layer visits by where their weights came from."""

from __future__ import annotations

from benchmark import sweep_account

FLASH = "pallas:flash_"


def flash_kernel_s(run) -> float | None:
    """Device seconds of the ``flash_*`` attention kernels in the traced
    batches: the ops ``trace_reduce.op_label`` names ``pallas:flash_...``
    (the kernel's own ``name``), and no other Pallas kernel (PERF.md section
    7j). None where the trace has none: no trace, or a run with
    ``use_pallas`` off."""
    tr = run.get("trace")
    if not tr:
        return None
    s = sum(sec for label, sec in tr["device_ops"]
            if label.rsplit("/", 1)[-1].startswith(FLASH))
    return s or None


def visits(run) -> tuple[int, int] | None:
    """(decoder-layer visits served from a seat of the residency tier, all
    decoder-layer visits) summed over the window's sweeps, or None where the
    account has none (a program without the counters, or a window the account
    does not match)."""
    records = sweep_account.window(run)
    if not records or any("visits_pinned" not in r or "layer_visits" not in r for r in records):
        return None
    pinned = sum(r["visits_pinned"] for r in records)
    total = sum(r["layer_visits"] for r in records)
    return (pinned, total) if total else None
