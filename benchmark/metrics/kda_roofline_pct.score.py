"""The KDA kernel's share of its roofline in the traced batches: the least
time the chip could take for the calls the batches need, counted AS THE
RECURRENCE whatever chunking implements it (per call the larger of needed
FLOPs over the bf16 peak and needed bytes over the HBM rate:
``families/glm5_next_text/flops.py``), over the ``pallas:kda_chunk`` ops'
device time."""

from benchmark.families.glm5_next_text import flops, readers


def read(run):
    ctx = run["ctx"]
    kernel_s = readers.kda_kernel_s(run)
    traced = run["counters"].get("traced_batches")
    if ctx["peaks"] is None or not kernel_s or not traced:
        return None
    need_s = traced * flops.kda_roofline_s(ctx["model"], ctx["traffic"], ctx["peaks"])
    return 100.0 * need_s / kernel_s
