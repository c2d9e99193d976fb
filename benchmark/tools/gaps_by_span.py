"""Idle seconds of a traced window by what the PROGRAM was doing: the proof
that the program's own spans (``fls.<name>``, ``obs/trace.py``) sit on the
device's clock. Run by hand through the chip tool:

    python3 benchmark/tools/gaps_by_span.py --workload <cell> --seed <n> [--seconds 12]
    python3 benchmark/tools/gaps_by_span.py --workload <cell> --toy --keep fls_probe
    python3 benchmark/tools/gaps_by_span.py --trace-dir <dir holding plugins/profile/...>

With ``--workload`` it runs the cell's own traced window (the harness's
context and driver, nothing of them edited), as ``tools/probe.py`` runs its
own; ``--toy`` cuts the cell to its rehearsal's widths on whatever device
there is, for a small trace to keep (``--keep <name>`` copies the
``.xplane.pb`` to ``chiprun_out/<name>.xplane.pb``). Either way it reads the
trace through the committed ``trace_reduce`` with ``host_span_prefix="fls."``
and prints, and writes to ``<--out-dir>/gaps_by_span.<cell>[.toy].json``
(``chiprun_out`` unless given):

- idle seconds (every gap between device ops, not only the longest) summed by
  the innermost span of the CONSUMER's thread that covers the gap's middle:
  what the thread that feeds the device was doing while the device stood (a
  ``device_wait`` is named with the span it lies in: ``device_wait/act_store``
  is the wait inside the activation store, ``device_wait/compute`` the one at
  a shard's end);
- the share of those seconds with a weight ``upload`` in flight, and with the
  producer building (``shard_load``), dispatching (``upload_dispatch``) or
  blocked on its queue (``producer_blocked``);
- with ``--workload``, the window's records of the program's sweep log beside
  the driver's batch walls (in the JSON only), for the account's identities;
- the Pallas ops by the kernel name in their ``frontend_attributes`` (the
  HLO instruction's own name is lost under vmap: PERF.md section 5).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

PREFIX = "fls."
# The spans of the thread that drives the device, outermost first; a gap is
# labelled by the innermost of them that covers it.
CONSUMER = ("sweep", "sweep_head", "executor_init", "tokenize", "source_wait", "compute",
            "dispatch", "device_wait", "act_fetch", "act_store", "sweep_tail")
# Other threads' spans, each reported as a share of the idle seconds.
BESIDE = ("upload", "shard_load", "upload_dispatch", "producer_blocked")
COARSE = ("sweep", "outside")  # not finer than the whole sweep
KERNEL = re.compile(r'kernel_metadata=\{\s*"kernel":"([A-Za-z0-9_]+)"')


def idle_gaps(plane) -> list[tuple[float, float]]:
    merged = trace_reduce.merge_intervals([(a, b) for _, a, b, _ in plane])
    return [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])
            if s1 - e0 >= trace_reduce.MIN_GAP_S]


def attribute(device_planes, host_spans) -> dict:
    consumer = sorted((s for s in host_spans if s[0] in CONSUMER), key=lambda s: s[2] - s[1])
    beside = {n: trace_reduce.merge_intervals([(a, b) for m, a, b in host_spans if m == n])
              for n in BESIDE}

    def cover(t: float) -> str:
        inner = [n for n, a, b in consumer if a <= t <= b]  # shortest first: the innermost
        if not inner:
            return "outside"
        if inner[0] == "device_wait" and len(inner) > 1:
            # where the consumer waits for the device: inside the activation
            # store's round trip, or at the shard's end (under compute)
            return f"device_wait/{inner[1]}"
        return inner[0]

    def overlap(a: float, b: float, merged) -> float:
        return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged)

    by_span: dict[str, float] = {}
    with_beside = dict.fromkeys(BESIDE, 0.0)
    total = 0.0
    for plane in device_planes:
        for a, b in idle_gaps(plane):
            total += b - a
            lab = cover((a + b) / 2)
            by_span[lab] = by_span.get(lab, 0.0) + (b - a)
            for n in BESIDE:
                with_beside[n] += overlap(a, b, beside[n])
    fine = sum(v for k, v in by_span.items() if k not in COARSE)
    return {
        "idle_s": total,
        "idle_by_consumer_span_s": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "idle_under_a_finer_span_pct": 100.0 * fine / total if total else None,
        "idle_with_pct": {n: 100.0 * v / total if total else None
                          for n, v in with_beside.items()},
        "spans_seen": sorted({n for n, _, _ in host_spans}),
    }


def pallas_ops(device_planes) -> dict[str, list]:
    """Device seconds of each Pallas op, by (the label the committed reduction
    gives it, the kernel name its HLO line carries)."""
    out: dict[str, float] = {}
    for plane in device_planes:
        for name, a, b, mod in plane:
            if 'custom_call_target="tpu_custom_call"' not in name:
                continue
            m = KERNEL.search(name)
            key = f"{trace_reduce.op_label(name, mod)} kernel={m.group(1) if m else '?'}"
            out[key] = out.get(key, 0.0) + (b - a)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def report(trace_dir: str, window_s: float | None = None) -> dict:
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    device_planes, host_spans = trace_reduce.read_xplane(path, PREFIX)
    reduced = trace_reduce.reduce_dir(trace_dir, host_span_prefix=PREFIX, window_s=window_s)
    out = attribute([p for p in device_planes if p], host_spans)
    if reduced is not None:
        out.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"],
                   longest_gaps=reduced["idle_gaps"][:10])
    out["pallas_ops"] = pallas_ops(device_planes)
    # the benchmark's own spans, to show the program's lie inside them
    _, bench_spans = trace_reduce.read_xplane(path, "bench.")
    runs = [(a, b) for n, a, b in bench_spans if n == "batch.run"]
    inside = sum(1 for _, a, b in host_spans if any(x - 1e-3 <= a and b <= y + 1e-3 for x, y in runs))
    out["program_spans"] = len(host_spans)
    out["program_spans_inside_bench_batch_run"] = inside
    out["xplane"] = path
    return out


def show(rep: dict) -> None:
    if not rep["idle_s"]:
        print("no device op in the trace: nothing to attribute (a CPU run has no device plane)")
        print("program spans seen:", ", ".join(rep["spans_seen"]))
        return
    print(f"idle {rep['idle_s']:.3f} s between device ops"
          + (f" (busy {rep['busy_s']:.3f} s of a {rep['window_s']:.3f} s window)"
             if "busy_s" in rep else ""))
    print(f"{'consumer span':<22} {'idle s':>9} {'share':>7}")
    for name, sec in rep["idle_by_consumer_span_s"].items():
        print(f"{name:<22} {sec:>9.3f} {100.0 * sec / rep['idle_s']:>6.1f}%")
    print(f"under a span finer than the sweep: {rep['idle_under_a_finer_span_pct']:.1f}%")
    for name, pct in rep["idle_with_pct"].items():
        print(f"idle with {name} under way: {pct:.1f}%")
    for key, sec in rep["pallas_ops"].items():
        print(f"pallas op {key}: {sec:.4f} s")
    print(f"{rep['program_spans_inside_bench_batch_run']} of {rep['program_spans']} "
          "program spans lie inside a bench.batch.run")


def run_cell(a) -> tuple[str, float | None, dict]:
    """One traced window of the cell through the harness's own context and
    driver; the trace directory stays until this process removes it."""
    from benchmark import run as bench_run

    argv = ["--workload", a.workload, "--seed", str(a.seed), "--seconds", str(a.seconds),
            "--trace", "1", "--benchmark-json", a.benchmark_json]
    if a.toy:  # the rehearsal's widths and lengths, on the device that is there
        with open(a.benchmark_json) as f:
            bench = json.load(f)
        cell, config = bench_run.find_cell(bench, a.workload)
        with open(os.path.join(ROOT, config["file"])) as f:
            model = json.load(f)
        with open(os.path.join(ROOT, bench["paths"][0], "traffic", f"{cell['traffic']}.json")) as f:
            traffic = json.load(f)
        for where, d in (("model", model), ("traffic", traffic)):
            for k, v in d.get("rehearsal", {}).items():
                argv += ["--set", f"{where}.{k}={json.dumps(v)}"]
    for kv in a.set:  # as run.py's own --set, applied after the toy cut
        argv += ["--set", kv]
    args = bench_run.parser().parse_args(argv)
    if a.toy and os.environ.get("JAX_PLATFORMS") == "cpu":
        args.cpu_rehearsal = True  # the harness refuses a CPU that is not asked for
    ctx = bench_run.build_ctx(args)
    if isinstance(ctx, int):
        raise SystemExit(ctx)
    driver = importlib.import_module(f"benchmark.drivers.{ctx['traffic']['driver']}")
    run = driver.run(ctx)
    tr = run.get("trace") or {}
    c = run["counters"]
    from flexible_llm_sharding_tpu.runtime import executor

    ctx["account"] = {  # the window's sweeps as the program accounted for them
        "batch_walls": c["batch_walls"],
        "streamed_bytes_per_batch": c["streamed_bytes"] / c["batches"],
        "sweep_log": executor.process_sweep_log()[-c["batches"]:],
    }
    return os.path.join(ctx["work"], "trace"), tr.get("window_s"), ctx


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=12.0)
    p.add_argument("--toy", action="store_true")
    p.add_argument("--keep", default="", help="copy the .xplane.pb to chiprun_out/<name>.xplane.pb")
    p.add_argument("--trace-dir")
    p.add_argument("--set", action="append", default=[],
                   help="model.<key>=<json> or traffic.<key>=<json>, as run.py's")
    p.add_argument("--out-dir", default=os.path.join(ROOT, "chiprun_out"))
    p.add_argument("--benchmark-json", default=os.path.join(ROOT, "BENCHMARK.json"))
    a = p.parse_args(argv)
    if not a.trace_dir and not a.workload:
        p.error("give --workload or --trace-dir")
    out_dir = a.out_dir
    os.makedirs(out_dir, exist_ok=True)
    work = None
    try:
        if a.trace_dir:
            trace_dir, window_s = a.trace_dir, None
        else:
            trace_dir, window_s, ctx = run_cell(a)
            work = ctx["work"]
        rep = report(trace_dir, window_s)
        show(rep)
        if work is not None:
            rep.update(ctx["account"])
        if a.keep:
            shutil.copy(rep["xplane"], os.path.join(out_dir, f"{a.keep}.xplane.pb"))
        name = a.workload or os.path.basename(os.path.normpath(a.trace_dir))
        if a.toy:
            name += ".toy"  # never over a real window's numbers
        with open(os.path.join(out_dir, f"gaps_by_span.{name}.json"), "w") as f:
            json.dump(rep, f, indent=1)
    finally:
        if work is not None:
            shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
