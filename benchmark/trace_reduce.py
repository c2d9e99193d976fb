"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read:
device busy seconds, op time by name, and the longest idle gaps labelled by
the benchmark's own host span that covers each.

Read with nothing but JAX (``jax.profiler.ProfileData``). A TPU's plane is
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed HLO
op, named by its whole HLO line, and its line ``XLA Modules`` one event per
executed program (``jit__decoder_block(<hash>)``). Busy is the union of the
op intervals (a ``while`` op spans its body's ops, so a sum would count
twice). The host's plane ``/host:CPU`` holds one line per
thread; the benchmark's spans are the ``TraceAnnotation`` events whose names
start with ``bench.``. Both planes are on one clock to about a millisecond
(the recorded trace shows device ops ~1 ms ahead of the host span that
launched them), which is enough to label gaps of tens of milliseconds.

Checked by ``tests/test_trace_reduce.py`` on the small recorded trace beside
it.
"""

from __future__ import annotations

import bisect
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MIN_GAP_S = 50e-6  # shorter gaps are launch spacing, not idleness worth a label


def start(trace_dir: str) -> None:
    """Start the profiler the way every traced window does: host spans
    (TraceAnnotation) on, the Python tracer off (it slows the host)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


CONTAINERS = ("while", "conditional", "call")  # their time is their bodies'
def op_label(name: str, module: str = "") -> str:
    """A short name for a device op. The trace names an op by its whole HLO
    line (``%fusion.12 = bf16[...] fusion(...)``): keep the instruction's
    name without its numeric suffix, mark a Pallas kernel (a custom call
    whose target is ``tpu_custom_call``) as such, and put the XLA module
    (the jitted step) it ran in before it."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    base = head.rsplit(".", 1)[0] if head.rsplit(".", 1)[-1].isdigit() else head
    if 'custom_call_target="tpu_custom_call"' in name:
        base = f"pallas:{base}"
    mod = module.split("(", 1)[0]
    return f"{mod}/{base}" if mod else base


def merge_intervals(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def reduce_planes(device_planes: list[list[tuple[str, float, float, str]]],
                  host_spans: list[tuple[str, float, float]],
                  window_s: float | None = None) -> dict | None:
    """``device_planes``: per chip, the op events (name, start_s, end_s,
    module). ``host_spans``: (name, start_s, end_s). Returns None when no op
    ran on any device."""
    device_planes = [sorted(p, key=lambda e: e[1]) for p in device_planes if p]
    if not device_planes:
        return None
    busy = []
    by_name: dict[str, float] = {}
    gaps: list[tuple[float, str]] = []
    lo = min(p[0][1] for p in device_planes)
    hi = max(e[2] for p in device_planes for e in p)
    spans = sorted(host_spans, key=lambda s: s[2] - s[1])  # innermost first

    def cover(t: float) -> str:
        for n, a, b in spans:
            if a <= t <= b:
                return n
        return "outside"

    for plane in device_planes:
        merged = merge_intervals([(a, b) for _, a, b, _ in plane])
        busy.append(sum(b - a for a, b in merged))
        for n, a, b, mod in plane:
            lab = op_label(n, mod)
            if lab.rsplit("/", 1)[-1] in CONTAINERS:
                continue
            by_name[lab] = by_name.get(lab, 0.0) + (b - a)
        ends = sorted((b, i) for i, (_, _, b, _) in enumerate(plane))
        starts = [e[1] for e in plane]
        k = 0
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            if s1 - e0 < MIN_GAP_S:
                continue
            while k + 1 < len(ends) and ends[k + 1][0] <= e0 + 1e-12:
                k += 1
            before = plane[ends[k][1]]
            nxt = plane[min(bisect.bisect_left(starts, s1 - 1e-12), len(plane) - 1)]
            gaps.append((
                s1 - e0,
                f"{cover((e0 + s1) / 2)}|after:{op_label(before[0], before[3])}"
                f"|before:{nxt[3].split('(', 1)[0] or op_label(nxt[0])}",
            ))
    n = len(device_planes)
    gaps.sort(reverse=True)
    return {
        "busy_s": sum(busy) / n,
        "window_s": float(window_s) if window_s else hi - lo,
        "trace_span_s": hi - lo,
        "chips": n,
        "device_ops": [[k, v / n] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[lab, g] for g, lab in gaps[:50]],
        "idle_gap_count": len(gaps),
    }


def read_xplane(path: str, host_span_prefix: str = "bench."):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_planes, host_spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        ops.append((ev.name, s, s + ev.duration_ns * 1e-9))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        mods.append((s, s + ev.duration_ns * 1e-9, ev.name))
            mods.sort()
            mstarts = [m[0] for m in mods]
            evs = []
            for name, a, b in ops:
                i = bisect.bisect_right(mstarts, a + 1e-9) - 1
                mod = mods[i][2] if i >= 0 and a <= mods[i][1] + 1e-6 else ""
                evs.append((name, a, b, mod))
            device_planes.append(evs)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_span_prefix):
                        s = ev.start_ns * 1e-9
                        host_spans.append(
                            (ev.name[len(host_span_prefix):], s, s + ev.duration_ns * 1e-9)
                        )
    return device_planes, host_spans


def reduce_dir(trace_dir: str, host_span_prefix: str = "bench.",
               window_s: float | None = None) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    device_planes, host_spans = read_xplane(path, host_span_prefix)
    return reduce_planes(device_planes, host_spans, window_s)
