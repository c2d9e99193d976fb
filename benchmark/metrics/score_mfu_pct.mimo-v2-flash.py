"""Whole step's share of the chip's peak for ``mimo_v2_flash``: FLOPs the
scored tokens NEED (projections by layer kind, the held experts the router
actually sent them to, window-clipped attention, the head on the scored rows
over the vocabulary held: ``families/mimo_v2_flash/flops.py``) per second of
the window, over the chip's bf16 peak."""

from benchmark.families.mimo_v2_flash import flops, readers


def read(run):
    ctx, c = run["ctx"], run["counters"]
    held = readers.held_assignments_per_batch(run)
    if ctx["peaks"] is None or held is None or not c.get("window_s"):
        return None
    need = flops.needed_flops(ctx["model"], ctx["traffic"], held)
    return 100.0 * need * c["batches"] / c["window_s"] / ctx["peaks"]["bf16_flops"]
