"""Whole step's share of the chip's peak for ``ouro``: FLOPs the scored tokens
NEED (every one of the ``total_ut_steps`` x layers visits' projections, SwiGLU
and causal attention, the head on the scored rows:
``families/ouro/flops.py``) per second of the window, over the chip's bf16
peak."""

from benchmark.families.ouro import flops


def read(run):
    ctx, c = run["ctx"], run["counters"]
    if ctx["peaks"] is None or not c.get("window_s") or not c.get("batches"):
        return None
    need = flops.needed_flops(ctx["model"], ctx["traffic"])
    return 100.0 * need * c["batches"] / c["window_s"] / ctx["peaks"]["bf16_flops"]
