"""The lightning-attention kernel's share of its roofline in the traced
batches: the least time the chip could take for the calls the batches need,
counted as the recurrence whatever chunk size implements it (per call the
larger of needed FLOPs over the bf16 peak and needed bytes over the HBM rate:
``families/minicpm_sala/flops.py``), over the ``pallas:lightning_attention``
ops' device time."""

from benchmark.families.minicpm_sala import flops, readers


def read(run):
    ctx = run["ctx"]
    kernel_s = readers.lightning_kernel_s(run)
    traced = run["counters"].get("traced_batches")
    if ctx["peaks"] is None or not kernel_s or not traced:
        return None
    need_s = traced * flops.lightning_roofline_s(ctx["model"], ctx["traffic"], ctx["peaks"])
    return 100.0 * need_s / kernel_s
