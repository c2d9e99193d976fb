"""The KDA kernel's share of the device's busy time in the traced batches
(``pallas:kda_chunk`` ops over the union of all op intervals): what the
delta-rule recurrence costs beside the projections around it."""

from benchmark.families.glm5_next_text import readers


def read(run):
    kernel_s = readers.kda_kernel_s(run)
    tr = run.get("trace")
    if not kernel_s or not tr or not tr.get("busy_s"):
        return None
    return 100.0 * kernel_s / tr["busy_s"]
