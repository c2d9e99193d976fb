"""The ``ouro`` family's benchmark modules: the seeded weights and the bytes
the files hold, the published sizes against the catalog's numbers, the needed
operations and bytes against a hand count, the four readers on made-up
accounts and traces, and the toy cell: a sound program inside the rehearsal's
limits, the planted fault and every control outside them."""

import importlib.util
import json
import os

import numpy as np
import pytest
from safetensors.numpy import load_file

from benchmark import check as chk, peaks, traffic as tr
from benchmark.families.ouro import flops, readers, reference, weights

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "ouro-2.6b.score-long-b4"


def published():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


def toy(**over):
    m = published()
    m.update(m.pop("rehearsal"))
    m.update(over)
    return m


def traffic():
    with open(os.path.join(BENCH, "traffic", "score-long-b4.json")) as f:
        return json.load(f)


# --- weights -----------------------------------------------------------------

def test_weights_are_the_seed_s_and_every_layer_is_its_own(tmp_path):
    model = toy()
    out = weights.write_model(model, 3, str(tmp_path / "a"))
    again = weights.write_model(model, 3, str(tmp_path / "b"))
    other = weights.write_model(model, 4, str(tmp_path / "c"))
    names = weights.layer_names(model)
    assert out == again == other and out["files"] == len(names) == 6
    assert out["bytes_written"] == out["bytes_model"] == weights.model_bytes(model) == 1_101_314
    for n in names:
        a, b, c = (load_file(str(tmp_path / d / f"{n}.safetensors")) for d in "abc")
        assert sorted(a) == sorted(k for k, _, _ in weights.tensor_specs(model, n))
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])  # the same seed, the same bytes
        random = [k for k, _, how in weights.tensor_specs(model, n) if how == "normal"]
        assert all(not np.array_equal(a[k], c[k]) for k in random)  # another seed, others
    # no two layers share a file or a tensor: nothing is linked, nothing repeats
    inodes = {os.stat(tmp_path / "a" / f"{n}.safetensors").st_ino for n in names}
    assert len(inodes) == len(names)
    l0, l1 = (load_file(str(tmp_path / "a" / f"model.layers.{i}.safetensors")) for i in (0, 1))
    assert not np.array_equal(l0["attn.wq"], l1["attn.wq"])
    assert all((l0[f"{n}.scale"] == 1).all() for n in weights.NORMS)
    norm = load_file(str(tmp_path / "a" / "model.norm.safetensors"))
    assert norm["gate.kernel"].shape == (128, 1) and (norm["gate.bias"] == 0).all()
    assert (norm["scale"] == 1).all() and norm["gate.kernel"].std() > 0.04  # N(0, 0.08)
    with open(tmp_path / "a" / "config.json") as f:
        cfg = json.load(f)
    assert cfg["model_type"] == "ouro" and cfg["total_ut_steps"] == 4
    assert "assumed" not in cfg and "init_std" not in cfg and len(cfg["layer_types"]) == 3


def test_published_sizes_and_the_catalogs_numbers():
    """ISSUE 33's arithmetic: 5,335,949,314 B, the gate's kernel and bias
    stored in bfloat16 beside the final norm's scale (8,194 B). Every number
    of the catalog's entry is in the file under its key; ``reduced`` is
    empty."""
    model = published()
    size = lambda n: sum(2 * int(np.prod(s)) for _, s, _ in weights.tensor_specs(model, n))  # noqa: E731
    assert size("model.layers.0") == 2 * 51_388_416 == 102_776_832
    assert size("lm_head") == size("model.embed_tokens") == 201_326_592
    assert size("model.norm") == 4_096 + 4_096 + 2
    assert weights.model_bytes(model) == 5_335_949_314
    assert weights.model_bytes(model) / 16_909_336_064 > 0.31  # pins alone, over the 25% floor
    assert flops.layer_visits(model) == 192
    assert model["total_ut_steps"] == 4 and model["early_exit_threshold"] == 1
    for key in ("four_norms", "final_norm_in_loop", "exit_gate", "exit_rule", "no_biases",
                "no_qk_norm", "rotary", "tensor_names", "weights", "origin"):
        assert key in model["assumed"], key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = next(c for c in json.load(f)["configs"] if c["name"] == "ouro-2.6b")
    assert declared["source"] == entry["source_url"] == model["source"]
    assert declared["reduced"] == []
    assert {k for k, v in entry["config"].items() if model.get(k, "absent") != v} == set()


# --- needed operations and bytes ---------------------------------------------

def test_needed_flops_against_a_hand_count():
    model, t = published(), traffic()
    pre, suf = flops.batch_lengths(t)
    assert pre == [1214, 1703, 2389, 3351] and len(suf) == 16 and sum(suf) == 640
    tokens = sum(pre) + 640
    assert tokens == 9297 and max(pre) + 64 <= 4096
    d, f, v = 2048, 5632, 49152
    per_token_layer = 2 * (4 * d * d + 3 * d * f)  # q, k, v, o and the SwiGLU, 2 FLOPs a MAC
    assert per_token_layer == 2 * (51_388_416 - 4 * 2048)
    keys = sum(p * (p + 1) / 2 for p in pre) + sum(np.mean(pre) * x + x * (x + 1) / 2 for x in suf)
    attention = 2 * keys * 16 * (128 + 128)  # QK^T and PV over the keys each query sees
    by_hand = 192 * (tokens * per_token_layer + attention) + 16 * 2 * d * v
    assert flops.needed_flops(model, t) == pytest.approx(by_hand, rel=1e-12)
    assert 2.0e14 < by_hand < 2.05e14  # 21.8 GFLOP a token: 1.03 s at the peak
    assert 192 * attention == pytest.approx(1.87e13, rel=0.02)
    pk = peaks.peaks_for("TPU v5 lite")
    calls = flops.flash_need(model, t)
    assert len(calls) == 8  # a causal call and a prefix-shared call a prompt
    assert sum(fl for fl, _ in calls) == pytest.approx(attention)
    row = 4 * 16 * 128 * 2  # q, k, v, o of one token, bfloat16
    assert sum(b for _, b in calls) == pytest.approx(tokens * row + sum(pre) * 2 * 16 * 128 * 2)
    # the prefix calls are bound by the FLOPs (p / 2 keys a row against 16 KB)
    assert all(fl / pk["bf16_flops"] > b / pk["hbm_bytes_per_s"] for fl, b in calls[::2])
    least = flops.flash_roofline_s(model, t, pk)
    assert least >= 192 * attention / 197e12 and 0.09 < least < 0.12


# --- readers -----------------------------------------------------------------

def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + "".join(c if c.isalnum() else "_" for c in name),
        os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


NEW = ("score_mfu_pct.ouro-2.6b", "loop_pin_hit_pct.score", "flash_device_share_pct.ouro-2.6b",
       "flash_roofline_pct.ouro-2.6b")
JOINED = ("stream_gb_per_sweep.score", "sweep_s.score", "device_idle_pct.score",
          "link_idle_pct.score", "sweep_ends_pct.score", "source_wait_pct.score",
          "producer_blocked_pct.score", "act_wait_pct.score", "act_link_gb_per_sweep.score")
# nothing to divide by with the link silent: they return nothing by their own code
LEFT_OUT = ("host_cache_hit_pct.score", "link_busy_pct.score", "upload_gbps.score")


@pytest.fixture
def log(monkeypatch):
    from flexible_llm_sharding_tpu.runtime import executor

    held = []
    monkeypatch.setattr(executor, "process_sweep_log", lambda: list(held), raising=False)
    return held


def run_of(walls, trace=None, pk="TPU v5 lite", **counters):
    return {"counters": {"batches": len(walls), "batch_walls": list(walls), "window_s": sum(walls),
                         "traced_batches": 2, **counters},
            "ctx": {"model": published(), "traffic": traffic(),
                    "peaks": pk and peaks.peaks_for(pk)},
            "trace": trace}


def test_the_cell_and_its_metrics_are_declared_by_appending():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)  # in order, behind what was there
    assert names.index(NEW[0]) > names.index("act_link_gb_per_sweep.score")
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "score_tokens_per_s"
        assert m["unit"] == "%" and m["layer"] in layers and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "ouro-2.6b", "traffic": "score-long-b4", "chips": 1,
                    "why": cell["why"]} and len(cell["why"]) <= 200
    config = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert len(config["why"]) <= 200 and config["file"] == "benchmark/configs/ouro-2.6b.json"
    rate = next(m for m in bench["end_to_end"] if m["name"] == "score_tokens_per_s")
    assert rate["workloads"][-1] == CELL
    for name in JOINED:
        assert per_layer[name]["workloads"][-1] == CELL, name
    for name in per_layer.keys() - set(JOINED) - set(NEW):
        assert CELL not in per_layer[name]["workloads"], name
    assert set(LEFT_OUT) <= per_layer.keys() - set(JOINED)
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert len(json.dumps(bench)) < 64 * 1024


def test_accepted_readers_with_the_link_silent(log):
    """What the new cell's window looks like to the accepted readers: nothing
    streamed, no upload, the cache never asked. Nine return numbers, three
    nothing (so the cell is not on their lists)."""
    rec = {"wall_s": 3.99, "head_s": 0.02, "tail_s": 0.01, "source_wait_s": 0.001,
           "producer_blocked_s": 0.0, "upload_bytes": 0, "upload_busy_s": 0.0,
           "act_wait_s": 0.0, "act_bytes": 0}
    log.extend([rec, rec])
    run = run_of([4.0, 4.0], streamed_bytes=0, link_gbps=9.0)
    run["trace"] = {"busy_s": 7.2, "window_s": 8.0, "device_ops": [], "idle_gaps": []}
    got = {name: reader(name)(run) for name in JOINED + LEFT_OUT}
    assert all(got[name] is not None for name in JOINED), got
    assert all(got[name] is None for name in LEFT_OUT), got
    assert got["stream_gb_per_sweep.score"] == 0.0 and got["link_idle_pct.score"] == 100.0


def test_pin_hits_reader_reads_the_windows_sweeps(log):
    rec = lambda p, s: {"wall_s": 3.99, "visits_pinned": p, "visits_streamed": s,  # noqa: E731
                        "layer_visits": p + s}
    log.extend([rec(144, 48), rec(192, 0), rec(192, 0)])  # the first is the seating sweep
    assert reader("loop_pin_hit_pct.score")(run_of([4.0, 4.0])) == 100.0
    log[-1] = rec(96, 96)  # half the stack re-streamed every step
    assert reader("loop_pin_hit_pct.score")(run_of([4.0, 4.0])) == pytest.approx(75.0)
    log[-2:] = [{"wall_s": 3.99}, {"wall_s": 3.99}]  # the parent's account: no such counters
    assert reader("loop_pin_hit_pct.score")(run_of([4.0, 4.0])) is None
    assert readers.visits(run_of([4.0, 4.0, 4.0])) is None  # a window the account does not cover


def test_mfu_reads_the_window_and_the_families_need():
    mfu = reader("score_mfu_pct.ouro-2.6b")(run_of([4.1, 4.1]))
    need = flops.needed_flops(published(), traffic())
    assert mfu == pytest.approx(100 * need / 4.1 / 197e12) and 24 < mfu < 26
    assert reader("score_mfu_pct.ouro-2.6b")(run_of([4.1], pk=None)) is None


def test_kernel_readers_read_the_flash_ops_alone():
    trace = {"busy_s": 8.0, "window_s": 9.0, "device_ops": [
        ["jit__decoder_block/fusion", 2.0],
        ["jit__decoder_block/pallas:flash_causal_attention", 4.5],
        ["jit__decoder_block/pallas:flash_prefix_shared_attention", 0.5],
        ["jit__decoder_block/pallas:grouped_matmul", 0.7],
        ["jit__decoder_block/pallas:lightning_attention", 0.3]]}
    run = run_of([4.5, 4.5], trace)
    assert readers.flash_kernel_s(run) == 5.0  # no other Pallas kernel is counted
    assert reader("flash_device_share_pct.ouro-2.6b")(run) == pytest.approx(62.5)
    roof = reader("flash_roofline_pct.ouro-2.6b")(run)
    least = flops.flash_roofline_s(published(), traffic(), peaks.peaks_for("TPU v5 lite"))
    assert roof == pytest.approx(100 * 2 * least / 5.0) and 3 < roof < 6


@pytest.mark.parametrize("name", NEW[1:])
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(log, monkeypatch, name):
    """No trace, a trace without the flash kernels (``use_pallas`` off), an
    account without the counters (the parent's), no account at all: nothing,
    and no exception."""
    no_kernel = {"busy_s": 1.0, "window_s": 2.0, "device_ops": [
        ["jit__x/fusion", 1.0], ["jit__x/pallas:grouped_matmul", 0.1]]}
    log.append({"wall_s": 3.99})
    assert reader(name)(run_of([4.0])) is None
    assert reader(name)(run_of([4.0], no_kernel)) is None
    from flexible_llm_sharding_tpu.runtime import executor

    monkeypatch.delattr(executor, "process_sweep_log")
    assert reader(name)(run_of([4.0], no_kernel)) is None


# --- the toy cell: sound, planted fault, controls -----------------------------

@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    """The rehearsal's own path in-process: the toy model's files, the CLI
    parser's defaults with the kernels on, eight prompts of the rehearsal's
    traffic in two batches through ``run_prompts``; the reference sequences
    beside the program's probability rows."""
    import jax

    from benchmark.drivers import score_closed
    from flexible_llm_sharding_tpu.runtime import hostcache, orchestration, residency

    model, t = toy(), traffic()
    t.update(t.pop("rehearsal"))
    d = str(tmp_path_factory.mktemp("ouro_cell") / "model")
    weights.write_model(model, 21, d)
    cfg = score_closed.program_config(d, rehearsal=True)
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    residency.reset_process_tier()
    hostcache.reset_process_cache()
    seqs, probs = [], []
    for b in range(2):
        prompts = tr.make_batch(t, int(model["vocab_size"]), 21, b)
        scores = orchestration.run_prompts(cfg, prompts, tokenizer=tok, devices=jax.devices()[:1])
        for (prefix, suffixes), s in zip(prompts, scores):
            pids = tok(prefix)["input_ids"]
            sids = [x[1:] for x in tok(list(suffixes))["input_ids"]]
            seqs.append(reference.scoring_sequence(pids, sids, 256))
            probs.append(np.asarray(s)[:, 0, :])
    return model, t["limits"], seqs, probs


def verdict(limits, probs, logits):
    return chk.verdict(chk.compare(probs, logits), limits)


def test_sound_program_is_inside_the_rehearsal_limits_and_the_planted_fault_outside(toy_run):
    """``score-long-b4``'s accepted rehearsal limits (0.011 / 0.016 / 1.2)
    serve this family's toy: a sound bfloat16 run reads 0.008-0.010 / 0.010-
    0.013 / 0.014-0.024 and ``test_harness``'s planted fault (a prompt's first
    answer rolled by one token id) 1.7 on the worst row."""
    model, limits, seqs, probs = toy_run
    ref = reference.forward_rows(model, 21, seqs)
    ok, numbers = verdict(limits, probs, ref)
    assert ok, numbers
    broken = [p.copy() for p in probs]
    for p in broken:
        p[0] = np.roll(p[0], 1, axis=-1)
    ok, numbers = verdict(limits, broken, ref)
    assert not ok and numbers["row_rms_max"]["value"] > numbers["row_rms_max"]["limit"]


@pytest.mark.parametrize("part", reference.PARTS)
def test_each_control_is_outside_the_rehearsal_limits(toy_run, part):
    """The reference with T - 1 steps, without the final norm between steps,
    without the two output-side norms, with the layers reversed inside a
    step: the sound program against each reads not correct, its best rows and
    its median row 20 times over their limits (the toy's worst-row limit, 1.2,
    is the planted fault's and wide: a control lifts every row alike)."""
    model, limits, seqs, probs = toy_run
    ok, numbers = verdict(limits, probs, reference.forward_rows(model, 21, seqs, leave_out=(part,)))
    assert not ok
    for k in ("row_rms_q10", "row_rms_median"):
        assert numbers[k]["value"] > 20 * numbers[k]["limit"], numbers


def test_one_precision_step_down_is_outside_the_rehearsal_limits(toy_run):
    """The reference with its weights through int8 in the program's place
    (the step-down control of the calibration) reads not correct; through the
    program's own precision (activations rounded to bfloat16) it reads what
    the program reads."""
    model, limits, seqs, probs = toy_run
    ref = reference.forward_rows(model, 21, seqs)
    low = [chk.softmax(x) for x in reference.forward_rows(model, 21, seqs, quant="int8")]
    assert not verdict(limits, low, ref)[0]
    same = [chk.softmax(x) for x in reference.forward_rows(model, 21, seqs, quant="bf16_act")]
    assert verdict(limits, same, ref)[0]


def test_unknown_part_is_an_error_and_the_exit_rule_by_hand():
    with pytest.raises(ValueError, match="unknown parts"):
        reference.forward_rows(toy(), 1, [], leave_out=("nothing",))
    lam = np.array([[0.3, 0.6, 0.05], [0.5, 0.9, 0.05], [0.9, 0.1, 0.05], [0.2, 0.2, 0.2]])
    # row 0: p = .3, .35, .315, .035 -> cumulative .3, .65, ...; row 1: .6 at once; row 2:
    # .05, .0475, .045, .857: only the last step reaches a half
    assert reference.exit_steps(lam, 0.5).tolist() == [2, 1, 4]
    assert reference.exit_steps(lam, 1.0).tolist() == [4, 4, 4]
    assert reference.exit_steps(lam, 0.01).tolist() == [1, 1, 1]
