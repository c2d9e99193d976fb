"""What the four ``mimo_v2_flash`` per-layer readers under
``benchmark/metrics/`` share: the window's records of the program's sweep
account with the expert counts this family adds to them, and the flash
kernels' device time in the traced batches."""

from __future__ import annotations

from benchmark import sweep_account
from benchmark.families.mimo_v2_flash import flops, weights


def expert_counts(run) -> tuple[int, int] | None:
    """(assignments on held experts, all assignments) summed over the
    window's sweeps, or None where the account has none (a program without
    the counters, or a window the account does not match)."""
    records = sweep_account.window(run)
    if not records or any("routed_assignments" not in r for r in records):
        return None
    hits = sum(r["held_expert_hits"] for r in records)
    routed = sum(r["routed_assignments"] for r in records)
    return (hits, routed) if routed else None


def held_assignments_per_batch(run) -> float | None:
    """Token-expert pairs a batch's REAL tokens send to held experts: the
    account counts every row the expert layers computed, padding included, so
    its hit share is applied to the real tokens' assignments."""
    counts = expert_counts(run)
    if counts is None:
        return None
    model, traffic = run["ctx"]["model"], run["ctx"]["traffic"]
    pre, suf = flops.batch_lengths(traffic)
    n_moe = sum(weights.is_moe_layer(model, i) for i in range(int(model["num_hidden_layers"])))
    real = (sum(pre) + sum(suf)) * int(model["num_experts_per_tok"]) * n_moe
    return real * counts[0] / counts[1]


def flash_kernel_s(run) -> float | None:
    """Device seconds of the Pallas kernels in the traced batches (the
    program's only Pallas kernels are the three ``flash_*`` attention ones:
    ``trace_reduce.op_label`` marks them ``pallas:``)."""
    tr = run.get("trace")
    if not tr:
        return None
    s = sum(sec for label, sec in tr["device_ops"] if "pallas:" in label)
    return s or None
