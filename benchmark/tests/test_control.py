"""The control at a size a test run can hold. The configuration states
bfloat16; the precision below it that the program has a path of its own for is
int8 (``requantize_native``). With that path switched on, the program has to
come out NOT correct by the rehearsal's limits, while the program as the
configuration states it comes out correct. On the chip, at the cells' own
sizes, ``tools/calibrate.py`` reads the same numbers (PERF.md, Findings)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import check as chk, reference, traffic as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def toy(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        m = json.load(f)
    m.update(m.pop("rehearsal"))
    return m


def test_the_programs_own_int8_path_comes_out_not_correct(tmp_path):
    """Three seeds through the timed path in the rehearsal, each read twice:
    as the configuration states it, and with the program's int8 path on."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "calibrate.py"), "--workload",
         "moonlight-16b.score-b8", "--seeds", "1,2147483650,3", "--seconds", "0.3",
         "--cpu-rehearsal", "--program-int8", "3", "--out-dir", str(tmp_path)],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True, capture_output=True,
        timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    recs = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    assert len(recs) == 3 and not any("program_int8_error" in r for r in recs)
    for r in recs:
        assert r["program"]["correct"] is True and r["program_int8"]["correct"] is False, r
    # it is the floor that the control lifts (three times and more at the cells' own
    # widths on the chip; the toy's channels are 32 times shorter and its int8 finer)
    low = max(r["program"]["row_rms_q10"] for r in recs)
    assert min(r["program_int8"]["row_rms_q10"] for r in recs) > 2 * low


@pytest.mark.parametrize("config", ["moonlight-16b-a3b", "kanana-2-30b-a3b"])
def test_reference_at_int8_fails_and_at_the_programs_precision_passes(config):
    """Both configurations' shapes, the reference in the program's place:
    with bfloat16 activations (the stated precision) it passes, with int8
    weights it fails."""
    model = toy(config)
    t = tr.load_traffic("score-b8")
    t.update(t.pop("rehearsal"))
    tok = tr.WordIdTokenizer(model["vocab_size"])
    for seed in (1, 2):
        seqs = []
        for prefix, suffixes in tr.make_batch(t, model["vocab_size"], seed, 0):
            pids = tok(prefix)["input_ids"]
            sids = [x[1:] for x in tok(list(suffixes))["input_ids"]]
            seqs.append(reference.scoring_sequence(pids, sids, pad_to=128))
        full = reference.forward_rows(model, seed, seqs)
        for q, want in (("bf16_act", True), ("int8", False)):
            low = reference.forward_rows(model, seed, seqs, quant=q)
            numbers = chk.compare([chk.softmax(x) for x in low], full)
            assert chk.verdict(numbers, t["limits"], rows_min=numbers["rows"])[0] is want, (
                q, seed, numbers)


def test_verdict_holds_every_number_the_limits_name_and_the_row_count():
    good = {"row_rms_q10": 0.02, "row_rms_median": 0.1, "row_rms_max": 0.2, "pick_gap": 9.0,
            "rows": 8}
    limits = {"row_rms_q10": 0.045, "row_rms_median": 0.3, "row_rms_max": 0.5}
    ok, out = chk.verdict(good, limits, rows_min=8)
    assert ok and out["pick_gap"]["limit"] is None and out["row_rms_q10"]["limit"] == 0.045
    for name, value in (("row_rms_q10", 0.05), ("row_rms_median", 0.31), ("row_rms_max", 0.6),
                        ("rows", 7), ("row_rms_median", float("nan"))):
        assert not chk.verdict({**good, name: value}, limits, 8)[0], name
    bad = chk.compare([np.full((2, 5), np.nan)], [np.zeros((2, 5))])
    assert not chk.verdict(bad, limits, 2)[0]
