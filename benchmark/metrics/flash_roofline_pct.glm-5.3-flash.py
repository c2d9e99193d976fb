"""The latent layers' flash attention kernels' share of their roofline in the
traced batches: the least time the chip could take for the calls the batches'
latent layers need at 64 heads of 256 / 256 (per call the larger of needed
FLOPs over the bf16 peak and q, k, v, o once over the HBM rate:
``families/glm5_next_text/flops.py``), over the ``pallas:flash_*`` ops' device
time."""

from benchmark.families.glm5_next_text import flops, readers


def read(run):
    ctx = run["ctx"]
    kernel_s = readers.flash_kernel_s(run)
    traced = run["counters"].get("traced_batches")
    if ctx["peaks"] is None or not kernel_s or not traced:
        return None
    need_s = traced * flops.flash_roofline_s(ctx["model"], ctx["traffic"], ctx["peaks"])
    return 100.0 * need_s / kernel_s
