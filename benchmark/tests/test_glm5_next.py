"""The ``glm5_next_text`` family's benchmark modules: the seeded weights and
the direct writer's layout, the published sizes against the catalog's numbers,
the needed operations and bytes against hand counts (at the toy size and at
the published one), the four readers on made-up accounts and traces, and the
toy cell: a sound program inside the rehearsal's limits, the planted fault,
one precision step down and every control outside them."""

import importlib.util
import json
import os

import numpy as np
import pytest
from safetensors.numpy import load_file

from benchmark import check as chk, peaks, traffic as tr
from benchmark.families.glm5_next_text import flops, readers, reference, weights

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "glm-5.3-flash.score-mid-b16"


def published():
    with open(os.path.join(BENCH, "configs", "glm-5.3-flash.json")) as f:
        return json.load(f)


def toy(**over):
    m = published()
    m.update(m.pop("rehearsal"))
    m.update(over)
    return m


def traffic():
    with open(os.path.join(BENCH, "traffic", "score-mid-b16.json")) as f:
        return json.load(f)


# --- weights -----------------------------------------------------------------

def test_layers_repeat_within_their_kind_and_the_spreads_are_the_files(tmp_path):
    model = toy()
    out = weights.write_model(model, 3, str(tmp_path / "m"))
    names = weights.layer_names(model)
    assert out["files"] == len(names) == 11
    slots = [weights.slot_of(model, n) for n in names[1:-2]]
    assert slots == ["kda_dense.0", "kda_moe.0", "kda_moe.1", "latent_moe.0", "kda_moe.0",
                     "kda_moe.1", "kda_moe.0", "latent_moe.1"]
    inode = lambda n: os.stat(tmp_path / "m" / f"{n}.safetensors").st_ino  # noqa: E731
    assert inode("model.layers.4") == inode("model.layers.1") == inode("model.layers.6")
    assert inode("model.layers.2") != inode("model.layers.1")
    assert inode("model.layers.7") != inode("model.layers.3")
    assert out["bytes_written"] < out["bytes_model"]
    kda = load_file(str(tmp_path / "m" / "model.layers.1.safetensors"))
    assert sorted(kda) == sorted(k for k, _, _ in weights.tensor_specs(model, "model.layers.1"))
    assert kda["attn.conv_q"].shape == (4, 256) and kda["attn.A_log"].shape == (2,)
    assert kda["hc_attn.phi"].shape == (256, 24) and kda["mlp.gate"].shape == (4, 64, 32)
    assert kda["mlp.router"].shape == (64, 16)  # the router's whole width, 4 of 16 held
    assert float(kda["post_attention_layernorm.scale"][0]) == model["mlp_norm_scale"]
    assert np.all(kda["hc_mlp.a"].astype(np.float32) == model["hc_scale"])
    assert 0.5 < float(np.std(kda["hc_attn.b"].astype(np.float32))) < 1.6  # N(0, 1) over 24
    assert 1.2 < float(np.std(kda["attn.dt_bias"].astype(np.float32))) < 2.8
    latent = load_file(str(tmp_path / "m" / "model.layers.3.safetensors"))
    assert "attn.A_log" not in latent and latent["attn.kv_b"].shape == (32, 2 * 256)
    assert float(latent["attn.q_a_norm"][0]) == model["latent_norm_scale"]
    assert not any("indexer" in k for k in latent)
    with open(tmp_path / "m" / "config.json") as f:
        cfg = json.load(f)
    assert cfg["model_type"] == "glm5_next_text" and cfg["n_routed_experts"] == 16
    assert (cfg["ep_size"], cfg["ep_rank"]) == (4, 0) and len(cfg["layer_types"]) == 8
    assert not {"assumed", "init_std", "hc_scale", "deployment", "published"} & set(cfg)


def test_published_sizes_and_the_catalogs_numbers():
    """ISSUE 37's arithmetic by the files' own shapes: 21.2 GB a sweep in
    layer files of three sizes, over the chip's 16.909 GB. Every number of the
    catalog's entry is in the file under its key but the three in ``reduced``."""
    model = published()
    size = lambda names: sum(2 * int(np.prod(s)) for n in names  # noqa: E731
                             for _, s, _ in weights.tensor_specs(model, n))
    assert size(["model.layers.0"]) == pytest.approx(0.579e9, rel=2e-3)  # KDA, dense
    assert size(["model.layers.3"]) == pytest.approx(2.101e9, rel=1e-3)  # latent, experts
    assert size(["model.layers.4"]) == pytest.approx(2.142e9, rel=1e-3)  # KDA, experts
    assert size(["lm_head"]) == size(["model.embed_tokens"]) == 19360 * 4096 * 2
    total = size(weights.layer_names(model))
    assert 21.1e9 < total < 21.3e9 and total > 16_909_336_064
    # what a run writes: three distinct files a kind
    distinct = {weights.slot_of(model, n): n for n in reversed(weights.layer_names(model))}
    assert 14.7e9 < size(distinct.values()) < 14.9e9
    assert flops.n_layers(model, weights.is_linear_layer) == 9
    assert flops.n_layers(model, weights.is_moe_layer) == 9
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.3-Flash")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = next(c for c in json.load(f)["configs"] if c["name"] == "glm-5.3-flash")
    assert declared["source"] == entry["source_url"] == model["source"]
    differs = {k for k, v in entry["config"].items() if model.get(k, "absent") != v}
    assert differs == set(declared["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                                   "vocab_size"}
    assert model["published"] == {k: entry["config"][k] for k in declared["reduced"]}


# --- needed operations and bytes ---------------------------------------------

def test_needed_flops_by_hand_at_the_toy_size():
    """Hidden 64, 2 KDA heads of 128, 2 latent heads of 128 / 128 behind
    ranks 48 / 32, dense 96, experts 32 wide (4 of 16 held, top-2, one
    shared), 4 streams, vocabulary 512, 8 layers [3 KDA, latent] x 2."""
    model, t = toy(), traffic()
    t.update(t.pop("rehearsal"))
    pre, suf = flops.batch_lengths(t)
    assert len(pre) == 4 and len(suf) == 16
    tokens = sum(pre) + sum(suf)
    d, hd = 64, 128
    hc = 2 * (256 * 24 + 256 + 20 * 64)  # both sublayers: the mixes, the read, the write
    kda = 4 * d * 2 * hd + 2 * (d * hd + hd * 2 * hd) + d * 2 + 3 * 4 * 2 * hd
    latent = d * 48 + 48 * 2 * hd + d * 32 + 32 * 2 * 2 * hd + 2 * hd * d
    assert flops.kda_projection_macs(model) == kda and flops.hc_macs(model) * 2 == hc
    assert flops.latent_projection_macs(model) == latent
    assert flops.recurrence_flops_per_token(model) == 2 * 7 * hd * hd
    dense, sparse = 3 * d * 96, d * 16 + 3 * d * 32
    keys = sum(p * (p + 1) / 2 for p in pre) + sum(np.mean(pre) * x + x * (x + 1) / 2 for x in suf)
    held = 1234.0
    by_hand = (
        2 * tokens * (8 * hc + 6 * kda + 2 * latent + dense + 7 * sparse)
        + 6 * tokens * 2 * 7 * hd * hd
        + 2 * 2 * keys * 2 * (hd + hd)
        + 2 * held * 3 * d * 32
        + 16 * 2 * d * 512
    )
    assert flops.needed_flops(model, t, held) == pytest.approx(by_hand, rel=1e-12)


def test_needed_flops_and_the_kernels_need_at_the_published_size():
    model, t = published(), traffic()
    model.pop("rehearsal")
    pre, suf = flops.batch_lengths(t)
    assert pre[0] == 535 and pre[-1] == 1843 and len(suf) == 64 and sum(suf) == 2560
    tokens = sum(pre) + sum(suf)
    assert tokens == 19615 and max(pre) + max(suf) <= 2048
    assert len({tr.bucket(p) for p in pre}) == 15
    # uniform routing: an eighth of the real tokens' 8 x 9 assignments
    need = flops.needed_flops(model, t, tokens * 8 * 9 / 8)
    assert 100e12 < need < 106e12  # ~5.2 GFLOP a token
    assert flops.kda_projection_macs(model) == pytest.approx(137.7e6, rel=1e-3)
    assert flops.latent_projection_macs(model) == pytest.approx(117.4e6, rel=1e-3)
    pk = peaks.peaks_for("TPU v5 lite")
    calls = flops.kda_need(model, t)
    assert len(calls) == 32  # a prefix call and a suffix call a prompt
    assert sum(fl for fl, _ in calls) == pytest.approx(tokens * 64 * 7 * 128 * 128)
    state = 64 * 128 * 128 * 4
    per_token = (5 * 64 * 128 + 64) * 2  # q, k, v, g, o at 2 bytes, beta a head: 82 KB
    assert sum(b for _, b in calls) == pytest.approx(tokens * per_token + 32 * state)
    # bound by HBM: 82 KB a token against 7.3 MFLOP
    assert all(b / pk["hbm_bytes_per_s"] > fl / pk["bf16_flops"] for fl, b in calls)
    least = flops.kda_roofline_s(model, t, pk)
    assert least == pytest.approx(9 * sum(b for _, b in calls) / 819e9) and 0.015 < least < 0.025
    # the 3 latent layers' flash calls: 64 heads of 256 / 256, keys and values expanded
    flash = flops.flash_need(model, t)
    assert len(flash) == 32  # a causal call and a prefix-shared call a prompt
    keys = flops.attended_keys(pre, suf)
    assert sum(fl for fl, _ in flash) == pytest.approx(2.0 * keys * 64 * 512)
    row = 64 * 512 * 2  # q and o, or k and v, of one token: 64 KB
    assert sum(b for _, b in flash) == pytest.approx(
        2 * row * sum(pre) + 2 * row * sum(suf) + row * sum(pre))
    # the longest prefix's causal call is bound by the MXU, the shortest's by
    # HBM; every suffix call by HBM (it reads the whole prefix's keys and
    # values for ~160 query rows)
    t_mxu = [fl / pk["bf16_flops"] for fl, _ in flash]
    t_hbm = [b / pk["hbm_bytes_per_s"] for _, b in flash]
    assert t_mxu[-2] > t_hbm[-2] and t_mxu[0] < t_hbm[0]
    assert all(a < b for a, b in zip(t_mxu[1::2], t_hbm[1::2]))
    least = flops.flash_roofline_s(model, t, pk)
    assert least == pytest.approx(3 * sum(map(max, t_mxu, t_hbm))) and 0.01 < least < 0.03


# --- readers -----------------------------------------------------------------

def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + "".join(c if c.isalnum() else "_" for c in name),
        os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


NEW = ("score_mfu_pct.glm-5.3-flash", "kda_roofline_pct.score", "kda_device_share_pct.score",
       "kda_kernel_rows_pct.score", "flash_roofline_pct.glm-5.3-flash",
       "flash_device_share_pct.glm-5.3-flash")
JOINED = ("stream_gb_per_sweep.score", "sweep_s.score", "host_cache_hit_pct.score",
          "link_busy_pct.score", "device_idle_pct.score", "upload_gbps.score",
          "link_idle_pct.score", "sweep_ends_pct.score", "source_wait_pct.score",
          "producer_blocked_pct.score", "held_expert_hit_pct.score", "act_wait_pct.score",
          "act_link_gb_per_sweep.score", "drained_pct.score", "own_upload_wait_pct.score",
          "behind_upload_pct.score", "dispatch_pct.score", "slowest_sweep_x.score",
          "warmup_sweep_s.score")


@pytest.fixture
def log(monkeypatch):
    from flexible_llm_sharding_tpu.runtime import executor

    held = []
    monkeypatch.setattr(executor, "process_sweep_log", lambda: list(held), raising=False)
    return held


def run_of(walls, trace=None, pk="TPU v5 lite"):
    model = published()
    model.pop("rehearsal")
    return {"counters": {"batches": len(walls), "batch_walls": list(walls), "window_s": sum(walls),
                         "traced_batches": 2},
            "ctx": {"model": model, "traffic": traffic(), "peaks": pk and peaks.peaks_for(pk)},
            "trace": trace}


def test_the_cell_and_its_metrics_are_declared_by_appending():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(NEW)  # appended, in order
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "score_tokens_per_s"
        assert m["unit"] == "%" and m["layer"] in layers and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "glm-5.3-flash", "traffic": "score-mid-b16", "chips": 1,
        "why": bench["workloads"][-1]["why"]} and len(bench["workloads"][-1]["why"]) <= 200
    config = bench["configs"][-1]
    assert config["name"] == "glm-5.3-flash" and len(config["why"]) <= 200
    assert config["file"] == "benchmark/configs/glm-5.3-flash.json"
    rate = next(m for m in bench["end_to_end"] if m["name"] == "score_tokens_per_s")
    assert rate["workloads"][-1] == CELL
    for name in JOINED:
        assert per_layer[name]["workloads"][-1] == CELL, name
    for name in per_layer.keys() - set(JOINED) - set(NEW):
        assert CELL not in per_layer[name]["workloads"], name
    assert len(bench["workloads"]) == 7 and all(w["chips"] == 1 for w in bench["workloads"])


def test_rows_reader_reads_the_windows_sweeps(log):
    rec = lambda k, x: {"wall_s": 1.29, "kda_rows_kernel": k, "kda_rows_xla": x}  # noqa: E731
    log.extend([rec(0, 5000), rec(221184, 0), rec(221184, 0)])  # the first is the warm-up's
    assert reader("kda_kernel_rows_pct.score")(run_of([1.3, 1.3])) == 100.0
    log[-1] = rec(165888, 55296)
    assert reader("kda_kernel_rows_pct.score")(run_of([1.3, 1.3])) == pytest.approx(87.5)
    log[-2:] = [rec(0, 0), rec(0, 0)]  # a model without KDA layers: nothing to read
    assert reader("kda_kernel_rows_pct.score")(run_of([1.3, 1.3])) is None


def test_mfu_reads_the_window_and_the_held_experts_share(log):
    rec = {"wall_s": 1.29, "held_expert_hits": 125, "routed_assignments": 1000}
    log.extend([rec, rec])
    run = run_of([1.3, 1.3])
    held = readers.held_assignments_per_batch(run)
    assert held == pytest.approx(19615 * 8 * 9 * 0.125)
    mfu = reader("score_mfu_pct.glm-5.3-flash")(run)
    need = flops.needed_flops(run["ctx"]["model"], traffic(), held)
    assert mfu == pytest.approx(100 * need / 1.3 / 197e12) and 35 < mfu < 45
    assert reader("score_mfu_pct.glm-5.3-flash")(run_of([1.3, 1.3], pk=None)) is None
    log[:] = [{"wall_s": 1.29}, {"wall_s": 1.29}]  # the parent's account: no expert counts
    assert reader("score_mfu_pct.glm-5.3-flash")(run_of([1.3, 1.3])) is None


def test_kernel_readers_read_the_kda_ops_of_the_trace():
    trace = {"busy_s": 2.0, "window_s": 4.0, "device_ops": [
        ["jit__decoder_block/fusion", 1.5],
        ["jit__decoder_block/pallas:kda_chunk", 0.2],
        ["jit__decoder_block/pallas:flash_causal_attention", 0.15],
        ["jit__decoder_block/pallas:grouped_matmul", 0.05]]}
    run = run_of([1.3, 1.3], trace)
    assert readers.kda_kernel_s(run) == 0.2  # no other kernel is counted
    assert reader("kda_device_share_pct.score")(run) == pytest.approx(10.0)
    roof = reader("kda_roofline_pct.score")(run)
    least = flops.kda_roofline_s(run["ctx"]["model"], traffic(), peaks.peaks_for("TPU v5 lite"))
    assert roof == pytest.approx(100 * 2 * least / 0.2) and 0 < roof < 100
    assert readers.flash_kernel_s(run) == 0.15  # the flash_* labels alone
    assert reader("flash_device_share_pct.glm-5.3-flash")(run) == pytest.approx(7.5)
    roof = reader("flash_roofline_pct.glm-5.3-flash")(run)
    least = flops.flash_roofline_s(run["ctx"]["model"], traffic(), peaks.peaks_for("TPU v5 lite"))
    assert roof == pytest.approx(100 * 2 * least / 0.15) and 0 < roof < 100


@pytest.mark.parametrize("name", NEW[1:])
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(log, monkeypatch, name):
    """No trace, a trace without the kernel (the XLA op ran, or the parent's
    program), an account without the counters (the parent's), a window the
    account does not cover, no account at all."""
    no_kernel = {"busy_s": 1.0, "window_s": 2.0, "device_ops": [
        ["jit__x/fusion", 1.0], ["jit__x/pallas:grouped_matmul", 0.1]]}
    log.append({"wall_s": 1.29})
    assert reader(name)(run_of([1.3])) is None
    assert reader(name)(run_of([1.3], no_kernel)) is None
    assert reader(name)(run_of([1.3, 1.3], no_kernel)) is None
    from flexible_llm_sharding_tpu.runtime import executor

    monkeypatch.delattr(executor, "process_sweep_log")
    assert reader(name)(run_of([1.3], no_kernel)) is None


# --- the toy cell -------------------------------------------------------------

@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    """The rehearsal's own path in-process: the toy model's files, the CLI
    parser's defaults with the kernels on (interpret mode), eight prompts of
    the rehearsal's traffic in two batches through ``run_prompts``; the
    reference sequences beside the program's probability rows."""
    import jax

    from benchmark.drivers import score_closed
    from flexible_llm_sharding_tpu.runtime import hostcache, orchestration, residency

    model, t = toy(), traffic()
    t.update(t.pop("rehearsal"))
    d = str(tmp_path_factory.mktemp("glm_cell") / "model")
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
    """The toy's own limits (0.022 / 0.045 / 1.5; PERF.md has the readings
    they lie between): a sound bfloat16 run reads 0.013-0.017 / 0.023-0.037 /
    0.28-0.95 (the worst rows are expert choices parted at a near-tie) and
    ``test_harness``'s planted fault (a prompt's first answer rolled by one
    token id) past the worst-row limit."""
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
    """No mHC, one Sinkhorn round, no decay gate, no beta, no convolution, no
    clamp, rotary added to the latent layers: the sound program against each
    reads not correct, by its best rows (every control lifts every row)."""
    model, limits, seqs, probs = toy_run
    ok, numbers = verdict(limits, probs, reference.forward_rows(model, 21, seqs, leave_out=(part,)))
    assert not ok
    assert numbers["row_rms_q10"]["value"] > numbers["row_rms_q10"]["limit"], numbers


def test_one_precision_step_down_is_outside_the_rehearsal_limits(toy_run):
    """The reference with its weights through int8 in the program's place
    (the step-down control of the calibration) reads not correct; through the
    program's own precision (activations rounded to bfloat16) it reads
    correct."""
    model, limits, seqs, probs = toy_run
    ref = reference.forward_rows(model, 21, seqs)
    low = [chk.softmax(x) for x in reference.forward_rows(model, 21, seqs, quant="int8")]
    assert not verdict(limits, low, ref)[0]
    same = [chk.softmax(x) for x in reference.forward_rows(model, 21, seqs, quant="bf16_act")]
    assert verdict(limits, same, ref)[0]


def test_unknown_part_is_an_error():
    with pytest.raises(ValueError, match="unknown parts"):
        reference.forward_rows(toy(), 1, [], leave_out=("nothing",))
