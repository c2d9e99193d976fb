"""The sweep record's account of the device's idle seconds, held against the
device's own trace; and a hunt for the batch that stalls. Run by hand through
the chip tool:

    python3 benchmark/tools/idle_account.py --workload <cell> --seed <n> [--seconds 12]
    python3 benchmark/tools/idle_account.py --workload <cell> --seed <n> --stall [--seconds 600]
    python3 benchmark/tools/idle_account.py --trace-dir <dir holding plugins/profile/...>

**Default**: one traced window of the cell through the harness's own context
and driver (as ``gaps_by_span.py`` runs it; ``--toy`` cuts the cell to its
rehearsal's widths), then two views of the traced sweeps side by side:

- the record's view: ``drained_s``, ``own_upload_wait_s``, ``behind_upload_s``
  of ``process_sweep_log()``, summed over the sweeps the trace holds whole: the
  host's own stamps, no profiler needed;
- the trace's view: every idle gap between device ops inside those sweeps,
  cut by what the program's ``fls.`` annotations say was going on: the
  consumer's innermost span over each piece of the gap (``device_wait`` at a
  shard's end or inside the store, ``source_wait``, ``dispatch``, anything
  else: a gap is cut where the consumer's spans start and end), and whether
  a weight ``upload`` was under way and WHOSE: the shard the consumer is on
  (``own``; between two shards: the one it is about to take), a later one
  (``later``: a launch queued behind a transfer it does not need) or an
  earlier one. Seconds under an own upload count as ``own`` even where a later
  shard's is queued behind it, as the record counts them.

It also prints, once, the stat names the device plane's op events carry (does
any hold ``op_name`` with the ``named_scope``s?).

**``--stall``**: one UNTRACED window (600 s unless ``--seconds`` says
otherwise), then the driver's batch walls beside ``process_slow_sweeps()``.
The log's ring holds 256 records and such a window ~700 sweeps, so a long batch
is matched to its slow record by ``sweep_id``: the window's i-th batch is sweep
``first + i``. A long batch with no slow record stalled outside the program's
sweep; one with a record names the phase, the shard and the collector's share.

Both write ``<--out-dir>/idle_account.<cell>[.stall][.toy].json`` (``chiprun_out``
unless given).
"""

from __future__ import annotations

import argparse
import bisect
import importlib
import json
import os
import shutil
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

PREFIX = "fls."
FINE = ("device_wait", "source_wait", "dispatch")  # the consumer's spans asked for
WHOSE = ("none", "own", "later", "earlier")
IDLE_KEYS = ("drained_s", "own_upload_wait_s", "behind_upload_s")


def read_trace(path: str):
    """-> (device op intervals per chip, the program's spans with their
    attributes per host thread, the op events' stat names)."""
    from jax.profiler import ProfileData

    planes, threads, stat_names = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    ops.append((a, a + ev.duration_ns * 1e-9))
                    for k, v in dict(ev.stats).items():
                        stat_names.setdefault(k, f"{ev.name[:60]} -> {str(v)[:120]}")
            if ops:
                planes.append(ops)
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        a = ev.start_ns * 1e-9
                        spans.append({"name": ev.name[len(PREFIX):], "a": a,
                                      "b": a + ev.duration_ns * 1e-9, **dict(ev.stats)})
                if spans:
                    threads.append(spans)
    return planes, threads, stat_names


def _int(span: dict, key: str) -> int | None:
    try:
        return int(span[key])
    except (KeyError, ValueError):
        return None


def _overlap(a: float, b: float, merged) -> list[tuple[float, float]]:
    return [(max(a, x), min(b, y)) for x, y in merged if min(b, y) > max(a, x)]


def _minus(pieces, merged):
    """``pieces`` (disjoint intervals) less the union ``merged``."""
    out = []
    for a, b in pieces:
        for x, y in merged:
            if y <= a or x >= b:
                continue
            if x > a:
                out.append((a, x))
            a = max(a, y)
        if b > a:
            out.append((a, b))
    return out


def _seconds(pieces) -> float:
    return sum(b - a for a, b in pieces)


def consumer_segments(consumer: list[dict]) -> list[tuple]:
    """The consumer's thread as disjoint stretches ``(start, end, label,
    sweep_id, shard_idx)``, cut wherever one of its spans starts or ends and
    labelled by the innermost span over the stretch (the spans of one thread
    nest). ``shard_idx``: of the ``compute`` span over the stretch, or between
    two of them the next one's: the shard the consumer is about to take."""
    spans = sorted(consumer, key=lambda s: (s["a"], -s["b"]))
    cuts = sorted({t for s in spans for t in (s["a"], s["b"])})
    computes = [s for s in spans if s["name"] == "compute"]
    starts = [c["a"] for c in computes]
    out, stack, k = [], [], 0
    for p, q in zip(cuts, cuts[1:]):
        while stack and stack[-1]["b"] <= p:
            stack.pop()
        while k < len(spans) and spans[k]["a"] <= p:
            while stack and stack[-1]["b"] <= p:
                stack.pop()
            stack.append(spans[k])
            k += 1
        sweep = next((s for s in stack if s["name"] == "sweep"), None)
        if sweep is None:
            continue
        top = stack[-1]
        label = top["name"] if top["name"] != "sweep" else "other"
        if label == "device_wait" and top.get("at") != "shard_end":
            label = "device_wait/store"
        on = next((s for s in stack if s["name"] == "compute"), None)
        if on is None:
            nxt = bisect.bisect_left(starts, q)
            on = computes[nxt] if nxt < len(computes) and computes[nxt]["a"] < sweep["b"] else None
        out.append((p, q, label, _int(sweep, "sweep_id"), _int(on, "shard_idx") if on else None))
    return out


def trace_view(planes, threads) -> dict:
    """The traced sweeps' idle seconds by (consumer span, whose upload): every
    gap between device ops, cut by the consumer's stretches it overlaps."""
    spans = [s for t in threads for s in t]
    sweeps = {_int(s, "sweep_id"): (s["a"], s["b"]) for s in spans if s["name"] == "sweep"}
    sweeps.pop(None, None)
    uploads = [s for s in spans if s["name"] == "upload"]
    # the consumer's thread is the one that holds the sweeps' spans
    consumer = [s for t in threads if any(x["name"] == "sweep" for x in t) for s in t]
    segments = consumer_segments(consumer)
    seg_ends = [q for _, q, *_ in segments]
    table = {f: dict.fromkeys(WHOSE, 0.0) for f in (*FINE, "device_wait/store", "other")}
    per_sweep = {sid: dict.fromkeys(WHOSE, 0.0) for sid in sweeps}
    by_shard: dict[tuple, dict] = {}

    def whose(sid, cur) -> dict:
        if (sid, cur) not in by_shard:
            by_shard[sid, cur] = {w: trace_reduce.merge_intervals(
                [(u["a"], u["b"]) for u in uploads
                 if _int(u, "sweep_id") == sid and cur is not None and (
                     (w == "own" and _int(u, "shard_idx") == cur)
                     or (w == "later" and _int(u, "shard_idx") > cur)
                     or (w == "earlier" and _int(u, "shard_idx") < cur))])
                for w in WHOSE[1:]}
        return by_shard[sid, cur]

    idle_all = 0.0
    for ops in planes:
        merged = trace_reduce.merge_intervals(ops)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            if s1 - e0 < trace_reduce.MIN_GAP_S:
                continue
            idle_all += s1 - e0
            k = bisect.bisect_right(seg_ends, e0)
            while k < len(segments) and segments[k][0] < s1:
                p, q, label, sid, cur = segments[k]
                k += 1
                if label not in table:
                    label = "other"
                rest = [(max(p, e0), min(q, s1))]
                for w in WHOSE[1:]:
                    by = whose(sid, cur)[w]
                    got = [x for a, b in rest for x in _overlap(a, b, by)]
                    rest = _minus(rest, by)
                    table[label][w] += _seconds(got)
                    per_sweep[sid][w] += _seconds(got)
                table[label]["none"] += _seconds(rest)
                per_sweep[sid]["none"] += _seconds(rest)
    inside = sum(sum(v.values()) for v in table.values())
    return {
        "sweeps": sweeps,
        "idle_by_span_and_upload_s": table,
        "idle_by_sweep_s": per_sweep,
        "idle_s": inside,
        "idle_outside_any_sweep_s": idle_all - inside,
        "idle_under_s": {w: sum(v[w] for v in table.values()) for w in WHOSE},
    }


def record_view(records: list[dict], sweeps: dict) -> dict:
    """The record's three numbers over the sweeps the trace holds."""
    mine = [r for r in records if r.get("sweep_id") in sweeps]
    out = {"sweeps": [r["sweep_id"] for r in mine], "wall_s": sum(r["wall_s"] for r in mine)}
    for k in (*IDLE_KEYS, "dispatch_s", "device_wait_s", "source_wait_s"):
        out[k] = sum(r[k] for r in mine) if all(k in r for r in mine) and mine else None
    return out


def show(view: dict, rec: dict | None, stat_names: dict) -> None:
    if not view["idle_s"] and not view["idle_outside_any_sweep_s"]:
        print("no device op in the trace: nothing to split (a CPU run has no device plane)")
    print(f"traced sweeps {sorted(view['sweeps'])}: idle {view['idle_s']:.4f} s inside them, "
          f"{view['idle_outside_any_sweep_s']:.4f} s outside any sweep")
    print(f"{'consumer span':<20}" + "".join(f"{'upload: ' + w:>18}" for w in WHOSE))
    for label, row in view["idle_by_span_and_upload_s"].items():
        print(f"{label:<20}" + "".join(f"{row[w]:>18.4f}" for w in WHOSE))
    under = view["idle_under_s"]
    print(f"{'all':<20}" + "".join(f"{under[w]:>18.4f}" for w in WHOSE))
    if rec is not None and rec["sweeps"]:
        print(f"the records of sweeps {rec['sweeps']} (wall {rec['wall_s']:.4f} s):")
        # drained: the consumer is NOT in a wait for the device and no upload is under way
        not_waiting = sum(row["none"] for label, row in view["idle_by_span_and_upload_s"].items()
                          if not label.startswith("device_wait"))
        for key, t, what in (
                ("behind_upload_s", under["later"], "idle under a later shard's upload"),
                ("own_upload_wait_s", under["own"], "idle under the own shard's upload"),
                ("drained_s", not_waiting, "idle, no upload, consumer not in device_wait")):
            r = rec[key]
            if r is None:
                print(f"  {key}: the program keeps no such field")
                continue
            ratio = f"{r / t:.3f}" if t else "n/a"
            print(f"  {key:<20} {r:>9.4f} s   trace, {what}: {t:>9.4f} s   record/trace {ratio}")
        print(f"  record's three together {sum(rec[k] or 0.0 for k in IDLE_KEYS):.4f} s "
              f"against {view['idle_s']:.4f} s idle in the trace")
    print("stat names of the device plane's op events: "
          + (", ".join(sorted(stat_names)) or "none")
          + ("" if any("op_name" in k for k in stat_names) else "  (none is op_name)"))


def run_cell(a, trace: bool) -> tuple[dict, dict]:
    """One window of the cell through the harness's own context and driver.
    -> (ctx, run); ``ctx['work']`` stays until the caller removes it."""
    from benchmark import run as bench_run

    argv = ["--workload", a.workload, "--seed", str(a.seed), "--seconds", str(a.seconds),
            "--trace", "1" if trace else "0", "--benchmark-json", a.benchmark_json]
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
    args = bench_run.parser().parse_args(argv)
    if a.toy and os.environ.get("JAX_PLATFORMS") == "cpu":
        args.cpu_rehearsal = True  # the harness refuses a CPU that is not asked for
    ctx = bench_run.build_ctx(args)
    if isinstance(ctx, int):
        raise SystemExit(ctx)
    driver = importlib.import_module(f"benchmark.drivers.{ctx['traffic']['driver']}")
    return ctx, driver.run(ctx)


def stall_report(run: dict) -> dict:
    """The window's batch walls against the program's slow sweeps."""
    from flexible_llm_sharding_tpu.runtime import executor

    walls = run["counters"]["batch_walls"]
    log = executor.process_sweep_log()
    slow = getattr(executor, "process_slow_sweeps", lambda: [])()
    first = log[-1]["sweep_id"] - (len(walls) - 1)  # one sweep a batch, in order
    med = statistics.median(walls)
    by_id = {r["sweep_id"]: r for r in slow}
    in_ring = {r["sweep_id"]: r for r in log}
    long_batches = []
    for i, wall in enumerate(walls):
        if wall <= max(1.5 * med, med + 0.5):
            continue
        sid = first + i
        kept = by_id.get(sid)
        row = {"batch": i, "sweep_id": sid, "batch_wall_s": wall,
               "record_wall_s": (kept or in_ring.get(sid) or {}).get("wall_s")}
        if kept is not None:
            worst = sorted(kept["shards"], key=lambda r: -(
                r["source_wait_s"] + r["dispatch_s"] + r["device_wait_s"]))[:3]
            row.update(verdict="inside the sweep", worst_phase=kept["worst_phase"],
                       worst_phase_excess_s=kept["worst_phase_excess_s"],
                       worst_shard=kept["worst_shard"], worst_shard_s=kept["worst_shard_s"],
                       gc_s=kept["gc_s"], gc_collections=kept["gc_collections"],
                       phases={k: kept[k] for k in ("head_s", "source_wait_s", "dispatch_s",
                                                    "device_wait_s", "tail_s")},
                       median=kept["median"], slowest_shards=worst)
        elif sid in in_ring and in_ring[sid].get("slow") == 0:
            row["verdict"] = "outside the program: the sweep's own record is not slow"
        else:
            row["verdict"] = ("no slow record kept (over 8 slow sweeps, or a program "
                              "without them): cannot say")
        long_batches.append(row)
    return {
        "batches": len(walls), "first_sweep_id": first, "median_batch_wall_s": med,
        "max_batch_wall_s": max(walls), "slowest_sweep_x": max(walls) / med,
        "slow_sweeps_seen": executor.stream_stats().get("slow_sweeps"),
        "slow_sweeps_kept": [r["sweep_id"] for r in slow],
        "gc_s_in_ring": sum(r.get("gc_s", 0.0) for r in log),
        "gc_collections_in_ring": sum(r.get("gc_collections", 0) for r in log),
        "long_batches": long_batches,
        "batch_walls": walls,
    }


def show_stall(rep: dict) -> None:
    print(f"{rep['batches']} batches (sweeps {rep['first_sweep_id']}..), median wall "
          f"{rep['median_batch_wall_s']:.4f} s, longest {rep['max_batch_wall_s']:.4f} s "
          f"({rep['slowest_sweep_x']:.2f} x); slow sweeps seen {rep['slow_sweeps_seen']}, "
          f"kept {rep['slow_sweeps_kept']}; generation-2 collections in the ring's records "
          f"{rep['gc_collections_in_ring']} ({rep['gc_s_in_ring']:.4f} s)")
    if not rep["long_batches"]:
        print("no batch over 1.5 x the median and 0.5 s over it: no stall in this window")
    for row in rep["long_batches"]:
        print(json.dumps(row))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--toy", action="store_true")
    p.add_argument("--stall", action="store_true")
    p.add_argument("--trace-dir")
    p.add_argument("--out-dir", default=os.path.join(ROOT, "chiprun_out"))
    p.add_argument("--benchmark-json", default=os.path.join(ROOT, "BENCHMARK.json"))
    a = p.parse_args(argv)
    if not a.trace_dir and not a.workload:
        p.error("give --workload or --trace-dir")
    if a.seconds is None:
        a.seconds = 600.0 if a.stall else 12.0
    os.makedirs(a.out_dir, exist_ok=True)
    name = a.workload or os.path.basename(os.path.normpath(a.trace_dir))
    name += (".stall" if a.stall else "") + (".toy" if a.toy else "")
    work = None
    try:
        records = None
        if a.trace_dir:
            trace_dir = a.trace_dir
        else:
            ctx, run = run_cell(a, trace=not a.stall)
            work, trace_dir = ctx["work"], os.path.join(ctx["work"], "trace")
            from flexible_llm_sharding_tpu.runtime import executor

            records = executor.process_sweep_log()
        if a.stall:
            rep = stall_report(run)
            show_stall(rep)
        else:
            path = trace_reduce.find_xplane(trace_dir)
            if path is None:
                raise SystemExit(f"no .xplane.pb under {trace_dir}")
            planes, threads, stat_names = read_trace(path)
            view = trace_view(planes, threads)
            rec = record_view(records, view["sweeps"]) if records is not None else None
            show(view, rec, stat_names)
            rep = {"trace": view, "record": rec, "op_event_stats": stat_names,
                   "records": [r for r in records or [] if r["sweep_id"] in view["sweeps"]]}
        with open(os.path.join(a.out_dir, f"idle_account.{name}.json"), "w") as f:
            json.dump(rep, f, indent=1)
    finally:
        if work is not None:
            shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
