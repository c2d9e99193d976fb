"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is found by name in ``BENCHMARK.json``: its configuration's file, its
traffic file ``traffic/<traffic>.json`` (which names its driver), the limits
of its comparison ``limits/<workload>.json``, and, for a traced run, one
reader ``metrics/<name>.py`` per per-layer metric. Adding a
configuration, a mix or a metric is adding files and entries; nothing here
needs an edit.

The process makes its weights and inputs from ``--seed``, warms up the
cell's own shapes (set-up), measures for ``--seconds``, reads the device's
memory peak, frees the program's state, runs the plain reference over a
seeded sample of what the window produced, and prints one JSON line last.
Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result. ``--cpu-rehearsal`` is the explicit toy-size
CPU run: it reports ``platform: cpu`` and is never a measurement.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class Compiles:
    """Counts backend compilations from JAX's own monitoring events, so the
    window can show it compiled nothing."""

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += secs

    def _ev(self, name, **kw):
        if name.endswith("cache_hits"):
            self.cache_hits += 1


def span(name: str):
    """One of the benchmark's own host spans, written into the profiler's
    trace (``bench.<name>``) so idle gaps on the device can be labelled by
    what the host was doing."""
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return cell, configs[cell["config"]]


def reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_reader(bench_dir: str, name: str):
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + "".join(c if c.isalnum() else "_" for c in name), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def build_ctx(a) -> dict | int:
    """Everything a driver needs for one run of one cell (or an exit code
    when the platform is not the one the run is for)."""
    with open(a.benchmark_json) as f:
        bench = json.load(f)
    bench_dir = os.path.join(ROOT, bench["paths"][0])
    cell, config = find_cell(bench, a.workload)
    with open(os.path.join(ROOT, config["file"])) as f:
        model = json.load(f)
    with open(os.path.join(bench_dir, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    if a.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        model.update(model.pop("rehearsal"))
        traffic.update(traffic.pop("rehearsal", {}))
    model.pop("rehearsal", None)
    traffic.pop("rehearsal", None)
    for kv in a.set:  # calibration only: model.<key>=<json> or traffic.<key>=<json>
        where, _, rest = kv.partition(".")
        key, _, val = rest.partition("=")
        {"model": model, "traffic": traffic}[where][key] = json.loads(val)
    if "limits" not in traffic:  # the rehearsal carries its own, for toy widths
        with open(os.path.join(bench_dir, "limits", f"{a.workload}.json")) as f:
            traffic["limits"] = json.load(f)

    # The program's own rule for the persistent cache (JAX_COMPILATION_CACHE_DIR
    # if set, else <checkout>/.jax_cache: a fixed path), before first JAX use.
    from flexible_llm_sharding_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    # Every program goes to the cache, also the ones that compile in under a
    # second, so that only a checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if a.cpu_rehearsal:
        jax.config.update("jax_enable_compilation_cache", False)

    devs = jax.devices()
    want = "cpu" if a.cpu_rehearsal else "tpu"
    if devs[0].platform != want:
        log(f"platform is {devs[0].platform!r}; this run needs {want!r} "
            "(a CPU run is only ever the explicit --cpu-rehearsal)")
        return 3
    if len(devs) < int(cell["chips"]):
        log(f"{len(devs)} devices, the cell asks for {cell['chips']}")
        return 3
    from benchmark import peaks

    work = os.path.join(ROOT, ".bench_work")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    return {
        "workload": a.workload, "cell": cell, "model": model, "traffic": traffic,
        "seed": int(a.seed), "seconds": float(a.seconds), "trace": bool(a.trace),
        "rehearsal": bool(a.cpu_rehearsal), "work": work, "t_process_start": T_PROCESS_START,
        "device": devs[0], "compiles": Compiles(), "span": span,
        "peaks": None if a.cpu_rehearsal else peaks.peaks_for(devs[0].device_kind),
        "fault": a.fault, "log": log, "bench": bench, "bench_dir": bench_dir,
        "cache_dir": cache_dir,
        "device_info": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                        "count": int(cell["chips"])},
    }


def parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, default=0, choices=(0, 1))
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="toy widths on the CPU backend; reports platform cpu, "
                        "proves nothing about a chip")
    p.add_argument("--benchmark-json", default=os.path.join(ROOT, "BENCHMARK.json"),
                   help=argparse.SUPPRESS)
    p.add_argument("--fault", default="", help=argparse.SUPPRESS)
    p.add_argument("--set", action="append", default=[], help=argparse.SUPPRESS)
    return p


def main(argv=None) -> int:
    a = parser().parse_args(argv)
    ctx = build_ctx(a)
    if isinstance(ctx, int):
        return ctx
    bench, bench_dir, device, work = ctx["bench"], ctx["bench_dir"], ctx["device_info"], ctx["work"]
    driver = importlib.import_module(f"benchmark.drivers.{ctx['traffic']['driver']}")
    try:
        run = driver.run(ctx)
        mem = ctx["device"].memory_stats() or {}
        device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
        driver.release(ctx, run)
        t0 = time.monotonic()
        correct, compared = driver.check(ctx, run)
        log(f"reference check took {time.monotonic() - t0:.1f} s")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    log(f"compile cache: {ctx['cache_dir']}; compilations in set-up {run['compiles_setup']} "
        f"({run['compile_s_setup']:.1f} s, {ctx['compiles'].cache_hits} cache hits), "
        f"in the window {run['compiles_window']}")
    run.update(ctx=ctx, device_info=device, bench=bench)
    breakdown = None
    if a.trace:
        values = {}
        for m in bench["per_layer"]:
            if reports(m, a.workload):
                v = load_reader(bench_dir, m["name"])(run)
                if v is not None:  # a reader that finds nothing to read returns nothing
                    values[m["name"]] = float(v)
        tr = run.get("trace")
        if tr is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            breakdown = {"device_ops": tr["device_ops"][:10], "idle_gaps": tr["idle_gaps"][:10]}
    else:
        mine = {m["name"] for m in bench["end_to_end"] if reports(m, a.workload)}
        values = {k: v for k, v in run["end_to_end"].items() if k in mine}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    if run["compiles_window"]:
        log(f"WARNING: {run['compiles_window']} compilations inside the measured window")
    line = {"correct": bool(correct), "attempted": run["attempted"], "failed": run["failed"],
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["info"] = run.get("info", {})
    line["compared"] = compared
    for k, v in compared.items():
        log(f"compared {k}: value {v['value']} limit {v['limit']}")
    log(f"correct: {bool(correct)}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
