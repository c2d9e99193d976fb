"""The harness end to end in its CPU rehearsal (toy widths of the same
``deepseek_v3`` shape, kernels in interpret mode, ``platform: cpu`` said
truthfully): the contract's last line, the data-driven lookup, the refusal
without a chip, and ``correct`` coming out false when the timed path is
broken underneath or the control stands in the program's place."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
RUN = os.path.join(BENCH, "run.py")


def run_cell(*args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    p = subprocess.run([sys.executable, RUN, *args], cwd=ROOT, env=e, text=True,
                       capture_output=True, timeout=900)
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    return p, (json.loads(lines[-1]) if lines else None)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
SCORE_CELLS = [w["name"] for w in BENCHMARK["workloads"] if w["traffic"].startswith("score")]


def end_to_end_of(cell):
    return {m["name"] for m in BENCHMARK["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", SCORE_CELLS)
def test_rehearsal_prints_the_contracts_last_line(cell):
    p, line = run_cell("--workload", cell, "--seed", str(2**31 + 7), "--seconds", "1",
                       "--trace", "0", "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == end_to_end_of(cell)
    assert all(v["value"] > 0 for v in line["metrics"].values())
    tail = p.stderr.strip().splitlines()[-8:]
    assert tail[-1] == "correct: True" and any(l.startswith("compared row_rms_median") for l in tail)
    assert "in the window 0" in p.stderr
    assert not os.path.exists(os.path.join(ROOT, ".bench_work"))


@pytest.mark.parametrize("cell", SCORE_CELLS[:1])
def test_traced_rehearsal_reports_per_layer_metrics_and_no_device_number(cell):
    p, line = run_cell("--workload", cell, "--seed", "3", "--seconds", "1", "--trace", "1",
                       "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    assert {"stream_gb_per_sweep.score", "sweep_s.score", "link_busy_pct.score"} <= set(
        line["metrics"])
    assert line["metrics"] and set(line["metrics"]) <= set(per_layer)
    # a CPU run has no device trace and no peaks: those readers return nothing
    assert not any(per_layer[k]["source"] == "device_trace" or "mfu" in k
                   for k in line["metrics"])
    assert "busy_s" not in line["device"]


def test_without_a_chip_it_exits_nonzero_and_prints_no_result():
    p, line = run_cell("--workload", SCORE_CELLS[0], "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and line is None and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", SCORE_CELLS)
def test_broken_timed_path_comes_out_not_correct(cell):
    """The one fault these cells can have: an answer (every prompt's first
    row) altered where it is produced, underneath the driver. The rest of a
    run is the real one."""
    p, line = run_cell("--workload", cell, "--seed", "5", "--seconds", "1", "--cpu-rehearsal",
                       "--fault", "alter_answer")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is False
    c = line["compared"]
    assert c["row_rms_max"]["value"] > c["row_rms_max"]["limit"]


@pytest.fixture
def fourth_cell(tmp_path):
    """A cell made only of NEW files: a configuration, a traffic mix and a
    per-layer metric reader, plus entries in a copy of BENCHMARK.json."""
    made = []

    def put(rel, text):
        path = os.path.join(BENCH, rel)
        with open(path, "w") as f:
            f.write(text)
        made.append(path)

    with open(os.path.join(BENCH, "configs", "moonlight-16b-a3b.json")) as f:
        model = json.load(f)
    model["rehearsal"]["n_routed_experts"] = 4
    put("configs/tmp-fourth.json", json.dumps(model))
    with open(os.path.join(BENCH, "traffic", "score-b8.json")) as f:
        t = json.load(f)
    t["prompts"], t["suffixes"] = 3, 2
    put("traffic/tmp-mix.json", json.dumps(t))
    put("metrics/tmp_batches.py",
        "def read(run):\n    return float(run['counters']['batches'])\n")
    put("metrics/tmp_nothing.py", "def read(run):\n    return None\n")
    put("limits/tmp.cell.json", json.dumps({"row_rms_q10": 0.017, "row_rms_median": 0.03, "row_rms_max": 1.2}))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "tmp-fourth", "source": "test", "reduced": [],
                             "file": "benchmark/configs/tmp-fourth.json", "why": "test"})
    bench["workloads"].append({"name": "tmp.cell", "config": "tmp-fourth", "traffic": "tmp-mix",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "score_tokens_per_s":
            m["workloads"].append("tmp.cell")
    for name in ("tmp_batches", "tmp_nothing"):
        bench["per_layer"].append({"name": name, "unit": "batches", "better": "higher",
                                   "source": "program_counter", "layer": "entry points",
                                   "moves": "score_tokens_per_s", "workloads": ["tmp.cell"]})
    path = str(tmp_path / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    yield path
    for p in made:
        os.remove(p)
    shutil.rmtree(os.path.join(BENCH, "metrics", "__pycache__"), ignore_errors=True)


def test_a_cell_made_only_of_new_files_runs(fourth_cell):
    p, line = run_cell("--benchmark-json", fourth_cell, "--workload", "tmp.cell", "--seed", "4",
                       "--seconds", "0.5", "--trace", "1", "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is True and line["attempted"] % 3 == 0
    # the new reader is found by its name; one that finds nothing is left out
    assert line["metrics"]["tmp_batches"]["value"] == line["attempted"] / 3
    assert "tmp_nothing" not in line["metrics"]
    # metrics of other cells are not reported here
    assert set(line["metrics"]) == {"tmp_batches"}


def test_benchmark_json_names_files_that_exist():
    for c in BENCHMARK["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in BENCHMARK["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
        assert os.path.exists(os.path.join(BENCH, "limits", f"{w['name']}.json"))
    for m in BENCHMARK["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{m['name']}.py")), m["name"]
        assert m["moves"] in {e["name"] for e in BENCHMARK["end_to_end"]}
