"""The ``minicpm_sala`` family's benchmark modules: the plain reference
against the program's own monolithic float32 forward at toy size, the direct
writer's layout, the published sizes against the catalog's numbers, the needed
operations and bytes, and the four readers on made-up accounts and traces."""

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from safetensors.numpy import load_file

from benchmark import peaks
from benchmark.families.minicpm_sala import flops, readers, reference, weights

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "minicpm-sala.score-long-b4"


def published():
    with open(os.path.join(BENCH, "configs", "minicpm-sala.json")) as f:
        return json.load(f)


def toy(**over):
    m = published()
    m.update(m.pop("rehearsal"))
    m.update(over)
    return m


def traffic():
    with open(os.path.join(BENCH, "traffic", "score-long-b4.minicpm-sala.json")) as f:
        return json.load(f)


def test_the_traffic_is_score_long_b4_s_with_a_rehearsal_of_its_own():
    """The same numbers as the accepted file; only the toy's limits differ
    (with the head's input scaled down, the other families' rehearsal limits
    would pass a planted fault: ``test_harness`` plants one in every cell)."""
    with open(os.path.join(BENCH, "traffic", "score-long-b4.json")) as f:
        accepted = json.load(f)
    mine = traffic()
    assert mine["rehearsal"].pop("limits") != accepted["rehearsal"].pop("limits")
    assert mine.pop("what") != accepted.pop("what")
    assert mine == accepted


# --- reference ---------------------------------------------------------------

def program_logits(model, seed, ids):
    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.models import llama

    cfg = LlamaConfig.from_hf_config(weights.hf_config(model))
    names = weights.layer_names(model)
    trees = [weights.unflatten(weights.layer_tensors(model, seed, n)) for n in names]
    params = {"embed": trees[0], "layers": trees[1:-2], "norm": trees[-2], "lm_head": trees[-1]}
    return np.asarray(llama.forward_full(params, cfg, jnp.asarray(ids)[None], dtype=jnp.float32)[0])


CASES = {
    "the-rehearsal": {},
    "linear-first-and-last": {"num_hidden_layers": 6, "mixer_types": [
        "lightning-attn", "minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
        "lightning-attn"]},
    "rotary-in-both-kinds": {"attn_use_rope": True},
    "no-gates-no-norms": {"use_output_gate": False, "attn_use_output_gate": False,
                          "qk_norm": False},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_forward_full(case):
    """The quadratic form against the program's chunked scan, a plain causal
    sequence: float32 both, 2e-5 is the order of the sums."""
    model = toy(**CASES[case])
    ids = np.random.default_rng(3).integers(3, int(model["vocab_size"]), 150)
    seq = reference.causal_sequence(ids, rows=list(range(150)), pad_to=160)
    want = reference.forward_rows(model, 7, [seq])[0]
    np.testing.assert_allclose(program_logits(model, 7, ids), want, atol=2e-5)


@pytest.mark.parametrize("part", ["decay", "gate", "output_norm", "qk_norm", "mup", "nope"])
def test_each_part_left_out_moves_the_logits(part):
    model = toy()
    ids = np.random.default_rng(4).integers(3, int(model["vocab_size"]), 150)
    seq = reference.causal_sequence(ids, rows=[100, 149], pad_to=160)
    whole = reference.forward_rows(model, 7, [seq])[0]
    cut = reference.forward_rows(model, 7, [seq], leave_out=(part,))[0]
    assert np.abs(whole - cut).max() > 0.02


def test_lower_precision_weights_move_the_logits_and_a_long_prompt_is_refused():
    model = toy()
    ids = np.random.default_rng(5).integers(3, int(model["vocab_size"]), 100)
    seq = reference.causal_sequence(ids, rows=[99], pad_to=128)
    whole = reference.forward_rows(model, 7, [seq])[0]
    for quant in ("int8", "fp8"):
        low = reference.forward_rows(model, 7, [seq], quant=quant)[0]
        assert 1e-3 < np.abs(whole - low).max() < 0.3  # fp8 reads 0.12 at these widths
    long = dict(seq, positions=seq["positions"] + reference.DENSE_LEN)
    with pytest.raises(AssertionError, match="sparse branch"):
        reference.forward_rows(model, 7, [long])


# --- weights -----------------------------------------------------------------

def test_layers_repeat_within_their_kind_and_the_decay_follows_the_index(tmp_path):
    model = toy()
    out = weights.write_model(model, 3, str(tmp_path / "m"))
    names = weights.layer_names(model)
    assert out["files"] == len(names) == 11
    # linear layers 1, 2, 3, 6 and softmax layers 0, 4, 5, 7 cycle with period 3
    slots = [weights.slot_of(model, n) for n in names[1:-2]]
    assert slots == ["softmax.0", "linear.0", "linear.1", "linear.2", "softmax.1",
                     "softmax.2", "linear.0", "softmax.0"]
    inode = lambda n: os.stat(tmp_path / "m" / f"{n}.safetensors").st_ino  # noqa: E731
    assert inode("model.layers.6") == inode("model.layers.1")
    assert inode("model.layers.7") == inode("model.layers.0")
    assert inode("model.layers.2") != inode("model.layers.1")
    assert out["bytes_written"] < out["bytes_model"]
    flat = load_file(str(tmp_path / "m" / "model.layers.1.safetensors"))
    assert sorted(flat) == sorted(k for k, _, _ in weights.tensor_specs(model, "model.layers.1"))
    assert "attn.o_norm" in flat and "attn.wg" in flat and flat["attn.wk"].shape == (128, 256)
    # two layers of one file still differ: the decay is the index's, not the file's
    assert not np.array_equal(weights.log_decay(model, 1), weights.log_decay(model, 6))
    with open(tmp_path / "m" / "config.json") as f:
        cfg = json.load(f)
    assert cfg["model_type"] == "minicpm_sala" and "assumed" not in cfg and "init_std" not in cfg


def test_published_sizes_and_the_catalogs_numbers():
    """ISSUE 31's arithmetic: 18.95 GB a sweep in layer files of two sizes,
    over the chip's 16.909 GB with nothing cut. Every number of the catalog's
    entry is in the file under its key; ``reduced`` is empty."""
    model = published()
    size = lambda names: sum(2 * int(np.prod(s)) for n in names  # noqa: E731
                             for _, s, _ in weights.tensor_specs(model, n))
    assert size(["model.layers.0"]) == pytest.approx(0.5075e9, rel=1e-3)  # minicpm4
    assert size(["model.layers.1"]) == pytest.approx(0.5704e9, rel=1e-3)  # lightning-attn
    assert size(["lm_head"]) == size(["model.embed_tokens"]) == 73448 * 4096 * 2
    total = size(weights.layer_names(model))
    assert 18.9e9 < total < 19.0e9 and total > 16_909_336_064
    assert flops.n_layers(model, True) == 24 and flops.n_layers(model, False) == 8
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = next(c for c in json.load(f)["configs"] if c["name"] == "minicpm-sala")
    assert declared["source"] == entry["source_url"] == model["source"]
    assert declared["reduced"] == []
    assert {k for k, v in entry["config"].items() if model.get(k, "absent") != v} == set()


# --- needed operations and bytes ---------------------------------------------

def test_needed_flops_and_the_recurrence_count():
    model, t = published(), traffic()
    pre, suf = flops.batch_lengths(t)
    assert pre == [1214, 1703, 2389, 3351] and len(suf) == 16 and sum(suf) == 640
    tokens = sum(pre) + 640
    assert tokens == 9297 and max(pre) + 64 <= 4096
    # the recurrence by hand: 32 heads, a [128, 128] state updated (k^T v) and
    # read (q S) once a token, 2 FLOPs a MAC
    assert flops.recurrence_flops_per_token(model) == 32 * (2 * 128 * 128 + 2 * 128 * 128)
    d, f, v = 4096, 16384, 73448
    mlp = 2 * 3 * d * f
    linear = 2 * 5 * d * d + mlp + 32 * 4 * 128 * 128
    softmax = 2 * (3 * d * d + 2 * d * 256) + mlp
    keys = sum(p * (p + 1) / 2 for p in pre) + sum(np.mean(pre) * x + x * (x + 1) / 2 for x in suf)
    by_hand = tokens * (24 * linear + 8 * softmax) + 8 * 2 * keys * 32 * 256 + 16 * 2 * d * v
    assert flops.needed_flops(model, t) == pytest.approx(by_hand, rel=1e-12)
    assert 160e12 < by_hand < 175e12  # ~18 GFLOP a token
    pk = peaks.peaks_for("TPU v5 lite")
    calls = flops.lightning_need(model, t)
    assert len(calls) == 8  # a prefix call and a suffix call a prompt
    assert sum(fl for fl, _ in calls) == pytest.approx(tokens * 32 * 4 * 128 * 128)
    state = 32 * 128 * 128 * 4
    assert sum(b for _, b in calls) == pytest.approx(tokens * 4 * 4096 * 2 + 8 * state)
    # bound by HBM: 32 KB of q, k, v, o a token against 2.1 MFLOP
    assert all(b / pk["hbm_bytes_per_s"] > fl / pk["bf16_flops"] for fl, b in calls)
    least = flops.lightning_roofline_s(model, t, pk)
    assert least == pytest.approx(24 * sum(b for _, b in calls) / 819e9) and 0.008 < least < 0.011


# --- readers -----------------------------------------------------------------

def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + "".join(c if c.isalnum() else "_" for c in name),
        os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


NEW = ("score_mfu_pct.minicpm-sala", "lightning_roofline_pct.score",
       "lightning_device_share_pct.score", "lightning_kernel_rows_pct.score")


@pytest.fixture
def log(monkeypatch):
    from flexible_llm_sharding_tpu.runtime import executor

    held = []
    monkeypatch.setattr(executor, "process_sweep_log", lambda: list(held), raising=False)
    return held


def run_of(walls, trace=None, pk="TPU v5 lite"):
    return {"counters": {"batches": len(walls), "batch_walls": list(walls), "window_s": sum(walls),
                         "traced_batches": 2},
            "ctx": {"model": published(), "traffic": traffic(),
                    "peaks": pk and peaks.peaks_for(pk)},
            "trace": trace}


def test_the_four_are_declared_for_the_cell_and_the_cell_joins_the_shared_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "score_tokens_per_s"
        assert m["unit"] == "%" and m["layer"] in layers
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW)  # appended, in order
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == bench["workloads"][-1] and cell["chips"] == 1
    assert cell["traffic"] == "score-long-b4.minicpm-sala" and cell["config"] == "minicpm-sala"
    rate = next(m for m in bench["end_to_end"] if m["name"] == "score_tokens_per_s")
    assert rate["workloads"][-1] == CELL
    shared = [m for m in bench["per_layer"] if CELL in m["workloads"] and m["name"] not in NEW]
    assert len(shared) == 10 and all(m["workloads"][-1] == CELL for m in shared)
    for name in ("score_mfu_pct", "score_mfu_pct.mimo-v2-flash", "attn_roofline_pct.score",
                 "attn_device_share_pct.score", "held_expert_hit_pct.score"):
        assert CELL not in per_layer[name]["workloads"]
    assert len(bench["workloads"]) == 5 and all(w["chips"] == 1 for w in bench["workloads"])


def test_rows_reader_reads_the_windows_sweeps(log):
    rec = lambda k, x: {"wall_s": 1.99, "linear_rows_kernel": k, "linear_rows_xla": x}  # noqa: E731
    log.extend([rec(0, 5000), rec(235008, 0), rec(235008, 0)])  # the first is the warm-up's
    assert reader("lightning_kernel_rows_pct.score")(run_of([2.0, 2.0])) == 100.0
    log[-1] = rec(176256, 58752)
    assert reader("lightning_kernel_rows_pct.score")(run_of([2.0, 2.0])) == pytest.approx(87.5)
    log[-2:] = [rec(0, 0), rec(0, 0)]  # a model without linear layers: nothing to read
    assert reader("lightning_kernel_rows_pct.score")(run_of([2.0, 2.0])) is None


def test_mfu_reads_the_window_and_the_families_need():
    mfu = reader("score_mfu_pct.minicpm-sala")(run_of([1.7, 1.7]))
    need = flops.needed_flops(published(), traffic())
    assert mfu == pytest.approx(100 * need / 1.7 / 197e12) and 45 < mfu < 55
    assert reader("score_mfu_pct.minicpm-sala")(run_of([1.7], pk=None)) is None


def test_kernel_readers_read_the_lightning_ops_of_the_trace():
    trace = {"busy_s": 2.0, "window_s": 4.0, "device_ops": [
        ["jit__decoder_block/fusion", 1.5],
        ["jit__decoder_block/pallas:lightning_attention", 0.08],
        ["jit__decoder_block/pallas:flash_causal_attention", 0.15],
        ["jit__decoder_block/pallas:grouped_matmul", 0.05]]}
    run = run_of([2.0, 2.0], trace)
    assert readers.lightning_kernel_s(run) == 0.08  # no other kernel is counted
    assert reader("lightning_device_share_pct.score")(run) == pytest.approx(4.0)
    roof = reader("lightning_roofline_pct.score")(run)
    least = flops.lightning_roofline_s(published(), traffic(), peaks.peaks_for("TPU v5 lite"))
    assert roof == pytest.approx(100 * 2 * least / 0.08) and 0 < roof < 100


@pytest.mark.parametrize("name", NEW[1:])
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(log, monkeypatch, name):
    """No trace, a trace without the kernel (the XLA op ran, or the parent's
    program), an account without the counters (the parent's), a window the
    account does not cover, no account at all."""
    no_kernel = {"busy_s": 1.0, "window_s": 2.0, "device_ops": [
        ["jit__x/fusion", 1.0], ["jit__x/pallas:flash_causal_attention", 0.1]]}
    log.append({"wall_s": 1.99})
    assert reader(name)(run_of([2.0])) is None
    assert reader(name)(run_of([2.0], no_kernel)) is None
    assert reader(name)(run_of([2.0, 2.0], no_kernel)) is None
    from flexible_llm_sharding_tpu.runtime import executor

    monkeypatch.delattr(executor, "process_sweep_log")
    assert reader(name)(run_of([2.0], no_kernel)) is None
