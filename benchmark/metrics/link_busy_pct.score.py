"""Time the window's streamed bytes need at the host->HBM rate measured in
set-up, as a share of the window. The rate is one large transfer's, so this
is the share of the window the link would be busy at its best."""


def read(run):
    c = run["counters"]
    if not c.get("streamed_bytes") or not c.get("link_gbps"):
        return None
    return 100.0 * (c["streamed_bytes"] / 1e9 / c["link_gbps"]) / c["window_s"]
