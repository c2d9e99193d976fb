"""Whole step's share of the chip's peak for ``minicpm_sala``: FLOPs the
scored tokens NEED (each layer kind's projections and gate, the SwiGLU, the
softmax layers' causal attention, the linear layers' recurrence, the head on
the scored rows: ``families/minicpm_sala/flops.py``) per second of the
window, over the chip's bf16 peak."""

from benchmark.families.minicpm_sala import flops


def read(run):
    ctx, c = run["ctx"], run["counters"]
    if ctx["peaks"] is None or not c.get("window_s") or not c.get("batches"):
        return None
    need = flops.needed_flops(ctx["model"], ctx["traffic"])
    return 100.0 * need * c["batches"] / c["window_s"] / ctx["peaks"]["bf16_flops"]
