"""The ``mimo_v2_flash`` family's benchmark modules: the plain reference
against the program's own monolithic float32 forward at toy size, the direct
writer against ``prepare_weights.py`` (which stacks the held experts of a
checkpoint that has them all), the published sizes, the needed operations and
bytes, and the four readers on made-up accounts and traces."""

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from safetensors.numpy import load_file, save_file

from benchmark import peaks
from benchmark.families.mimo_v2_flash import flops, reference, weights

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "mimo-v2-flash.score-long-b4"


def published():
    with open(os.path.join(BENCH, "configs", "mimo-v2-flash.json")) as f:
        return json.load(f)


def toy(**over):
    m = published()
    m.update(m.pop("rehearsal"))
    m.update(over)
    return m


def traffic():
    with open(os.path.join(BENCH, "traffic", "score-long-b4.json")) as f:
        return json.load(f)


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
    "rank-0-of-4": {},
    "rank-3-of-4": {"ep_rank": 3},
    "all-experts-held": {"n_routed_experts": 16, "ep_size": 1},
    "sink-in-both-kinds": {"add_full_attention_sink_bias": True},
    "six-layers": {"num_hidden_layers": 6, "hybrid_layer_pattern": [0, 1, 1, 0, 1, 0],
                   "moe_layer_freq": [0, 1, 1, 1, 1, 1]},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_forward_full(case):
    model = toy(**CASES[case])
    ids = np.random.default_rng(3).integers(3, model["vocab_size"], size=40)
    want = program_logits(model, 11, ids)
    seq = reference.causal_sequence(ids, rows=range(40), pad_to=64)
    got = reference.forward_rows(model, 11, [seq])[0]
    assert got.shape == want.shape == (40, model["vocab_size"])
    assert np.abs(got - want).max() < 2e-4 * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("part", ["sink", "window", "value_scale"])
def test_each_part_left_out_moves_the_logits(part):
    model = toy()
    seq = reference.causal_sequence(np.arange(3, 43), rows=[39], pad_to=64)
    full = reference.forward_rows(model, 4, [seq])[0]
    cut = reference.forward_rows(model, 4, [seq], leave_out=(part,))[0]
    assert np.abs(cut - full).max() > 0.02


def test_lower_precision_weights_move_the_logits_and_taps_see_the_whole_router():
    model = toy()
    seq = reference.causal_sequence(np.arange(3, 35), rows=[31], pad_to=64)
    taps = []
    full = reference.forward_rows(model, 4, [seq], taps=taps)[0]
    assert len(taps) == 3 and taps[0][0].shape == (1, 2)  # 3 expert layers, top-2
    assert max(int(t[0].max()) for t in taps) >= 4  # ids of the router's 16, not of the 4 held
    for q in ("fp8", "int8"):
        low = reference.forward_rows(model, 4, [seq], quant=q)[0]
        assert 1e-3 < np.abs(low - full).max() < 2.0


# --- weights -----------------------------------------------------------------

HF_NAMES = {
    "input_layernorm.scale": ("input_layernorm.weight", False),
    "post_attention_layernorm.scale": ("post_attention_layernorm.weight", False),
    "attn.wq": ("self_attn.q_proj.weight", True),
    "attn.wk": ("self_attn.k_proj.weight", True),
    "attn.wv": ("self_attn.v_proj.weight", True),
    "attn.wo": ("self_attn.o_proj.weight", True),
    "attn.sink": ("self_attn.attention_sink_bias", False),
    "mlp.router": ("mlp.gate.weight", True),
    "mlp.correction_bias": ("mlp.gate.e_score_correction_bias", False),
}


def hf_state(model, seed):
    """The seeded tensors as an HF checkpoint that has EVERY routed expert:
    the held ones are the configuration's, the others random."""
    rng = np.random.default_rng(seed)
    held = weights.held_experts(model)
    sd = {}
    for name in weights.layer_names(model):
        flat = {k: np.asarray(v) for k, v in weights.layer_tensors(model, seed, name).items()}
        if name == "model.embed_tokens":
            sd["model.embed_tokens.weight"] = flat["embedding"]
        elif name == "model.norm":
            sd["model.norm.weight"] = flat["scale"]
        elif name == "lm_head":
            sd["lm_head.weight"] = flat["kernel"].T
        else:
            moe = "mlp.router" in flat
            for k, v in flat.items():
                if k in HF_NAMES:
                    hf, t = HF_NAMES[k]
                    sd[f"{name}.{hf}"] = v.T if t else v
                    continue
                proj = {"mlp.gate": "gate_proj", "mlp.up": "up_proj", "mlp.down": "down_proj"}[k]
                if not moe:
                    sd[f"{name}.mlp.{proj}.weight"] = v.T
                    continue
                for e in range(weights.router_width(model)):
                    w = v[e - held.start] if e in held else rng.standard_normal(
                        v.shape[1:]).astype(v.dtype)
                    sd[f"{name}.mlp.experts.{e}.{proj}.weight"] = w.T
    return {k: np.ascontiguousarray(v) for k, v in sd.items()}


@pytest.mark.parametrize("rank", [0, 2])
def test_direct_writer_equals_prepare_weights_over_the_held_share(tmp_path, rank):
    import prepare_weights
    from flexible_llm_sharding_tpu.config import LlamaConfig

    model = toy(ep_rank=rank)
    direct, hf, converted = (str(tmp_path / d) for d in ("direct", "hf", "converted"))
    info = weights.write_model(model, 9, direct)
    os.makedirs(hf)
    save_file(hf_state(model, 9), os.path.join(hf, "model.safetensors"))
    with open(os.path.join(hf, "config.json"), "w") as f:
        json.dump(weights.hf_config(model), f)
    prepare_weights.main([hf, converted, "--dtype", "bfloat16"])
    names = weights.layer_names(model)
    assert info["files"] == len(names)
    for n in names:
        a = load_file(os.path.join(direct, f"{n}.safetensors"))
        b = load_file(os.path.join(converted, f"{n}.safetensors"))
        assert sorted(a) == sorted(b), n
        for k in a:
            assert a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]), (n, k)
    with open(os.path.join(direct, "integrity.json")) as f:
        mine = json.load(f)["layers"]
    with open(os.path.join(converted, "integrity.json")) as f:
        theirs = json.load(f)["layers"]
    assert mine == theirs
    cfg = LlamaConfig.from_pretrained(direct)
    assert cfg == LlamaConfig.from_pretrained(converted)
    assert cfg.held_experts == range(4 * rank, 4 * rank + 4) and cfg.num_local_experts == 16


def test_expert_layers_repeat_within_their_attention_kind(tmp_path):
    model = published()
    model.update(model.pop("rehearsal"))
    model.update(num_hidden_layers=18, hybrid_layer_pattern=published()["hybrid_layer_pattern"],
                 moe_layer_freq=published()["moe_layer_freq"])
    out = str(tmp_path / "m")
    info = weights.write_model(model, 1, out)
    ino = lambda i: os.stat(os.path.join(out, f"model.layers.{i}.safetensors")).st_ino  # noqa: E731
    window = [i for i in range(1, 18) if weights.is_window_layer(model, i)]
    assert [i for i in range(18) if i not in window] == [0, 5, 11, 17]
    assert ino(window[0]) == ino(window[3]) == ino(window[6]) and ino(window[1]) == ino(window[4])
    assert len({ino(i) for i in window[:3]}) == 3
    assert len({ino(i) for i in (0, 5, 11, 17)}) == 4  # three full layers: all distinct
    assert not {ino(i) for i in window} & {ino(i) for i in (0, 5, 11, 17)}
    assert info["bytes_written"] < info["bytes_model"]
    with open(os.path.join(out, "config.json")) as f:
        cfg = json.load(f)
    assert len(cfg["hybrid_layer_pattern"]) == len(cfg["moe_layer_freq"]) == 18
    assert (cfg["n_routed_experts"], cfg["ep_size"], cfg["ep_rank"]) == (16, 4, 0)
    assert not {"published", "deployment", "assumed", "sink_mean"} & set(cfg)


def test_published_sizes_and_the_catalogs_numbers():
    """ISSUE 27's arithmetic: 17.80 GB a sweep in layer files of three sizes,
    over the chip's 16.909 GB; depth 17 would be under it. Every number of the
    catalog's entry is in the file, but for the three keys of ``reduced``."""
    model = published()
    size = lambda m, names: sum(2 * int(np.prod(s)) for n in names  # noqa: E731
                                for _, s, _ in weights.tensor_specs(m, n))
    one = lambda i: size(model, [f"model.layers.{i}"])  # noqa: E731
    assert one(0) == pytest.approx(0.581e9, rel=2e-3)
    assert one(1) == pytest.approx(0.996e9, rel=2e-3) and one(5) == pytest.approx(0.986e9, rel=2e-3)
    assert 17.7e9 < size(model, weights.layer_names(model)) < 17.9e9
    less = {**model, "num_hidden_layers": 17}
    assert size(less, weights.layer_names(less)) < 16.909e9
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2-Flash")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = next(c for c in json.load(f)["configs"] if c["name"] == "mimo-v2-flash")
    assert declared["source"] == entry["source_url"] == model["source"]
    differs = {k for k, v in entry["config"].items() if model.get(k, "absent") != v}
    assert differs == set(declared["reduced"]) == set(model["published"])
    assert all(model["published"][k] == entry["config"][k] for k in differs)


# --- needed operations and bytes ---------------------------------------------

def test_needed_flops_and_the_attention_roofline():
    model, t = published(), traffic()
    pre, suf = flops.batch_lengths(t)
    assert pre == [1214, 1703, 2389, 3351] and len(suf) == 16 and sum(suf) == 640
    assert max(pre) + 64 <= 4096  # under the program's default max_token_len
    assert flops._tri(5, None) == 15 and flops._tri(5, 2) == 1 + 2 + 2 + 2 + 2
    # a full layer attends to ~half the square of each prefix, a window layer to 128 keys a token
    full, window = (sum(flops.attended_keys(pre, suf, w)) for w in (None, 128))
    assert full == pytest.approx(sum(p * p / 2 for p in pre) + 640 * np.mean(pre), rel=0.01)
    assert window == (sum(pre) + 640) * 128 - 4 * (128 * 127 // 2)  # less each prefix's first 127
    tokens = sum(pre) + 640
    uniform = tokens * 8 * 17 * 16 / 256  # held assignments on uniform routing
    need = flops.needed_flops(model, t, uniform)
    assert 38e12 < need < 46e12  # PERF.md's reckoning: ~42 TFLOP a batch
    assert flops.needed_flops(model, t, 2 * uniform) - need == pytest.approx(
        uniform * 2 * 3 * 4096 * 2048)
    pk = peaks.peaks_for("TPU v5 lite")
    calls = {w: flops.attention_need(model, t, w) for w in (False, True)}
    assert len(calls[True]) == len(calls[False]) == 8  # a causal and a prefix-shared call a prompt
    assert all(f > 0 and b > 0 for c in calls.values() for f, b in c)
    assert sum(f for f, _ in calls[False]) > 10 * sum(f for f, _ in calls[True])
    least = flops.attention_roofline_s(model, t, pk)
    assert 0.005 < least < 0.05  # a few per cent of a ~1.3 s device sweep at most


# --- readers -----------------------------------------------------------------

def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + "".join(c if c.isalnum() else "_" for c in name),
        os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


NEW = ("score_mfu_pct.mimo-v2-flash", "attn_roofline_pct.score", "attn_device_share_pct.score",
       "held_expert_hit_pct.score")


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


def test_the_four_are_declared_for_the_cell_and_move_the_rate():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    # PERF.md's layers: the accepted metrics', and "kernels", which had none
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW} | {"kernels"}
    for name in NEW:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "score_tokens_per_s"
        assert m["unit"] == "%" and m["layer"] in layers
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == cells["moonlight-16b.score-b32"]["chips"] == 1
    assert CELL not in per_layer["score_mfu_pct"]["workloads"]
    assert "moonlight-16b.score-b32" in per_layer["score_mfu_pct"]["workloads"]


def test_expert_counts_read_the_windows_sweeps(log):
    rec = lambda hits: {"wall_s": 1.99, "held_expert_hits": hits, "routed_assignments": 1000}  # noqa: E731
    log.extend([rec(500), rec(60), rec(70)])  # the first is the warm-up's
    run = run_of([2.0, 2.0])
    assert reader("held_expert_hit_pct.score")(run) == pytest.approx(6.5)
    mfu = reader("score_mfu_pct.mimo-v2-flash")(run)
    tokens = 9297
    need = flops.needed_flops(published(), traffic(), tokens * 8 * 17 * 0.065)
    assert mfu == pytest.approx(100 * need / 2.0 / 197e12) and 9 < mfu < 13
    assert reader("score_mfu_pct.mimo-v2-flash")(run_of([2.0, 2.0], pk=None)) is None


def test_kernel_readers_read_the_pallas_ops_of_the_trace(log):
    trace = {"busy_s": 2.0, "window_s": 4.0, "device_ops": [
        ["jit__decoder_block/fusion", 1.5],
        ["jit__decoder_block/pallas:flash_causal_attention", 0.15],
        ["jit__decoder_block/pallas:flash_prefix_shared_attention", 0.05]]}
    run = run_of([2.0, 2.0], trace)
    assert reader("attn_device_share_pct.score")(run) == pytest.approx(10.0)
    roof = reader("attn_roofline_pct.score")(run)
    least = flops.attention_roofline_s(published(), traffic(), peaks.peaks_for("TPU v5 lite"))
    assert roof == pytest.approx(100 * 2 * least / 0.2) and 0 < roof < 100


@pytest.mark.parametrize("name", NEW)
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(log, monkeypatch, name):
    """No trace, a trace without Pallas ops, an account without the counters
    (the parent's), a window the account does not cover, no account at all."""
    no_kernels = {"busy_s": 1.0, "window_s": 2.0, "device_ops": [["jit__x/fusion", 1.0]]}
    log.append({"wall_s": 1.99})
    assert reader(name)(run_of([2.0])) is None
    assert reader(name)(run_of([2.0], no_kernels)) is None
    assert reader(name)(run_of([2.0, 2.0], no_kernels)) is None
    from flexible_llm_sharding_tpu.runtime import executor

    monkeypatch.delattr(executor, "process_sweep_log")
    assert reader(name)(run_of([2.0])) is None
