"""The flash attention kernels' share of their roofline in the traced
batches: the least time the chip could take for the calls the batches need
(per call the larger of needed FLOPs over the bf16 peak and needed bytes over
the HBM rate, by layer kind: ``families/mimo_v2_flash/flops.py``) over the
``pallas:`` ops' device time."""

from benchmark.families.mimo_v2_flash import flops, readers


def read(run):
    ctx = run["ctx"]
    kernel_s = readers.flash_kernel_s(run)
    traced = run["counters"].get("traced_batches")
    if ctx["peaks"] is None or not kernel_s or not traced:
        return None
    need_s = traced * flops.attention_roofline_s(ctx["model"], ctx["traffic"], ctx["peaks"])
    return 100.0 * need_s / kernel_s
