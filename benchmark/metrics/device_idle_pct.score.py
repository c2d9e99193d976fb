"""1 - busy/window of the traced window, busy being the union of the device
op intervals in the profiler's trace (trace_reduce.py)."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
