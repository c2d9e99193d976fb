"""What the ``glm5_next_text`` per-layer readers under ``benchmark/metrics/``
share: the KDA kernel's and the latent layers' flash kernels' device time in
the traced batches, the rows the window's sweeps dispatched to the KDA kernel
and to the XLA op, and the held experts' share of the real tokens'
assignments."""

from __future__ import annotations

from benchmark import sweep_account
from benchmark.families.glm5_next_text import flops, weights

KERNEL = "pallas:kda_chunk"
FLASH = "pallas:flash_"


def kda_kernel_s(run) -> float | None:
    """Device seconds of the ``pallas:kda_chunk`` ops in the traced batches
    (``trace_reduce.op_label`` names a Pallas kernel ``pallas:`` and its own
    ``name``), or None where the trace has none: a program without the
    kernel, or a run that fell back to the XLA op."""
    tr = run.get("trace")
    if not tr:
        return None
    s = sum(sec for label, sec in tr["device_ops"] if label.rsplit("/", 1)[-1] == KERNEL)
    return s or None


def flash_kernel_s(run) -> float | None:
    """Device seconds of the ``flash_*`` attention kernels (the latent
    layers' causal and prefix-shared calls) in the traced batches, and no
    other Pallas kernel; None where the trace has none."""
    tr = run.get("trace")
    if not tr:
        return None
    s = sum(sec for label, sec in tr["device_ops"]
            if label.rsplit("/", 1)[-1].startswith(FLASH))
    return s or None


def kda_rows(run) -> tuple[int, int] | None:
    """(rows x KDA layers dispatched with the kernel, with the XLA op) summed
    over the window's sweeps, or None where the account has none (a program
    without the counters, or a window the account does not match)."""
    records = sweep_account.window(run)
    if not records or any("kda_rows_kernel" not in r for r in records):
        return None
    kernel = sum(r["kda_rows_kernel"] for r in records)
    xla = sum(r["kda_rows_xla"] for r in records)
    return (kernel, xla) if kernel + xla else None


def held_assignments_per_batch(run) -> float | None:
    """Token-expert pairs a batch's REAL tokens send to held experts: the
    account counts every row the expert layers computed, padding included, so
    its hit share is applied to the real tokens' assignments."""
    records = sweep_account.window(run)
    if not records or any("routed_assignments" not in r for r in records):
        return None
    hits = sum(r["held_expert_hits"] for r in records)
    routed = sum(r["routed_assignments"] for r in records)
    if not routed:
        return None
    model, traffic = run["ctx"]["model"], run["ctx"]["traffic"]
    pre, suf = flops.batch_lengths(traffic)
    real = ((sum(pre) + sum(suf)) * int(model["num_experts_per_tok"])
            * flops.n_layers(model, weights.is_moe_layer))
    return real * hits / routed
