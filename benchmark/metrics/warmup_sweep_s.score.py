"""``wall_s`` of the record just before the window's first: the warm-up
batch's sweep, the program's own share of set-up (a process's first sweep:
the crc pass, the residency tier's seating, compilation on a checkout's
first run). Nothing where the log no longer holds that record."""

from benchmark import sweep_account


def read(run):
    records = sweep_account.window(run)
    if records is None:
        return None
    from flexible_llm_sharding_tpu.runtime import executor

    log, n = executor.process_sweep_log(), len(records)
    if len(log) <= n or log[-n]["sweep_id"] != records[0]["sweep_id"]:
        return None
    return log[-n - 1]["wall_s"]
