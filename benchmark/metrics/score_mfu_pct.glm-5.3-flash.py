"""Whole step's share of the chip's peak for ``glm5_next_text``: FLOPs the
scored tokens NEED (each mixer's projections, mHC, the latent layers' causal
scores, the KDA layers' recurrence at its own count, the dense MLPs, the
shared expert and the held experts the router actually sent tokens to, the
head on the scored rows over the vocabulary held:
``families/glm5_next_text/flops.py``) per second of the window, over the
chip's bf16 peak."""

from benchmark.families.glm5_next_text import flops, readers


def read(run):
    ctx, c = run["ctx"], run["counters"]
    held = readers.held_assignments_per_batch(run)
    if ctx["peaks"] is None or held is None or not c.get("window_s"):
        return None
    need = flops.needed_flops(ctx["model"], ctx["traffic"], held)
    return 100.0 * need * c["batches"] / c["window_s"] / ctx["peaks"]["bf16_flops"]
