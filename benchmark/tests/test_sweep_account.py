"""The five metrics that read the program's per-sweep account (PR 25):
their window rule on made-up logs, and on the CPU rehearsal of each cell the
account's identities, sweep by sweep, beside the driver's own batch walls."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark import sweep_account

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NEW = ("upload_gbps.score", "link_idle_pct.score", "sweep_ends_pct.score",
       "source_wait_pct.score", "producer_blocked_pct.score")
PHASES = ("head_s", "source_wait_s", "dispatch_s", "device_wait_s", "tail_s")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
SCORE_CELLS = [w["name"] for w in BENCHMARK["workloads"] if w["traffic"].startswith("score")]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def record(wall=2.0, **kw):
    rec = {"wall_s": wall, "head_s": 0.1, "tail_s": 0.1, "source_wait_s": 0.4,
           "dispatch_s": 0.9, "device_wait_s": 0.5, "producer_blocked_s": 1.0,
           "upload_busy_s": 1.6, "upload_bytes": 16e9}
    rec.update(kw)
    return rec


@pytest.fixture
def log(monkeypatch):
    """Stand a made-up sweep log in the program's place."""
    from flexible_llm_sharding_tpu.runtime import executor

    held = []
    monkeypatch.setattr(executor, "process_sweep_log", lambda: list(held), raising=False)
    return held


def run_of(walls):
    return {"counters": {"batches": len(walls), "batch_walls": list(walls)}}


def test_the_five_are_declared_for_both_score_cells_and_move_the_rate():
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        assert m["workloads"] == SCORE_CELLS and m["moves"] == "score_tokens_per_s"
        assert m["source"] == "program_counter" and "roofline" not in name and "mfu" not in name
    assert [m["name"] for m in BENCHMARK["per_layer"]][-5:] == list(NEW)  # appended, in order


def test_readers_report_the_median_over_the_windows_sweeps(log):
    log += [record(wall=9.9)]  # an older sweep, outside the window
    log += [record(), record(upload_busy_s=1.0, head_s=0.3), record(upload_busy_s=1.8)]
    run = run_of([2.01, 2.02, 2.0])
    assert reader("upload_gbps.score")(run) == pytest.approx(10.0)  # 16 GB / 1.6 s
    assert reader("link_idle_pct.score")(run) == pytest.approx(20.0)
    assert reader("sweep_ends_pct.score")(run) == pytest.approx(10.0)
    assert reader("source_wait_pct.score")(run) == pytest.approx(20.0)
    assert reader("producer_blocked_pct.score")(run) == pytest.approx(50.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_returns_nothing_without_a_matching_window(log, name):
    read = reader(name)
    log += [record(), record()]
    assert read(run_of([2.01, 2.01, 2.01])) is None  # log shorter than the window
    assert read(run_of([2.01, 2.2])) is None  # a wall_s more than 3% under its batch wall
    assert read(run_of([2.01, 1.9])) is None  # a wall_s OVER its batch wall: another call's
    assert read({"counters": {"batches": 3, "batch_walls": [2.01, 2.01]}}) is None
    assert read(run_of([2.01, 2.01])) is not None
    log[-1]["upload_busy_s"] = 0.0  # nothing to divide by: left out, not raised
    assert reader("upload_gbps.score")(run_of([2.01, 2.01])) is None


def test_a_program_without_the_log_reads_as_nothing(monkeypatch):
    """The parent commit's executor has no ``process_sweep_log``: the
    readers leave their metrics out of the line and do not raise."""
    from flexible_llm_sharding_tpu.runtime import executor

    monkeypatch.delattr(executor, "process_sweep_log")
    for name in NEW:
        assert reader(name)(run_of([2.0])) is None


@pytest.mark.parametrize("cell", SCORE_CELLS)
def test_traced_rehearsal_prints_the_five_and_the_accounts_identities_hold(cell, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed",
         str(2**31 + 25), "--seconds", "1", "--trace", "1", "--cpu-rehearsal"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads([l for l in p.stdout.splitlines() if l.strip()][-1])
    got = line["metrics"]
    assert set(NEW) <= set(got) and line["correct"] is True
    assert got["upload_gbps.score"]["value"] > 0
    for name in NEW[1:]:
        assert 0.0 <= got[name]["value"] <= 100.0, name
    assert {"stream_gb_per_sweep.score", "sweep_s.score", "link_busy_pct.score"} <= set(got)

    # The same window through the tool that dumps the records themselves.
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "gaps_by_span.py"), "--workload", cell,
         "--toy", "--seed", "7", "--seconds", "1", "--out-dir", str(tmp_path)],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    with open(tmp_path / f"gaps_by_span.{cell}.toy.json") as f:
        rep = json.load(f)
    records, walls = rep["sweep_log"], rep["batch_walls"]
    assert len(records) == len(walls) >= 3
    assert sweep_account.match(records, walls) is not None
    for rec, wall in zip(records, walls):
        assert sum(rec[k] for k in PHASES) == pytest.approx(rec["wall_s"], rel=0.02)
        assert 0.97 * wall <= rec["wall_s"] <= wall + 1e-4
        assert rec["upload_bytes"] == pytest.approx(rep["streamed_bytes_per_batch"], rel=1e-3)
        assert 0.0 < rec["upload_busy_s"] <= rec["wall_s"] and rec["upload_misses"] == 0
    for name in ("sweep", "sweep_head", "source_wait", "compute", "upload", "act_fetch",
                 "act_store", "sweep_tail"):
        assert name in rep["spans_seen"], name
    assert rep["program_spans_inside_bench_batch_run"] >= 0.9 * rep["program_spans"]
