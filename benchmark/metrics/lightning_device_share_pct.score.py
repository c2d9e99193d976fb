"""The lightning-attention kernel's share of the device's busy time in the
traced batches (``pallas:lightning_attention`` ops over the union of all op
intervals): the recurrence is cheap by construction and should stay so."""

from benchmark.families.minicpm_sala import readers


def read(run):
    kernel_s = readers.lightning_kernel_s(run)
    tr = run.get("trace")
    if not kernel_s or not tr or not tr.get("busy_s"):
        return None
    return 100.0 * kernel_s / tr["busy_s"]
