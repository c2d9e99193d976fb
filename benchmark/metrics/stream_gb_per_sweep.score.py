"""GB of weights the executor built for upload per sweep (one batch is one
sweep): the program's own streamed-bytes counter over the window."""


def read(run):
    c = run["counters"]
    if not c.get("batches"):
        return None
    return c["streamed_bytes"] / c["batches"] / 1e9
