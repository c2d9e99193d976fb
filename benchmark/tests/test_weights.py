"""The direct writer against ``prepare_weights.py``: the same tensors laid
out as an HF checkpoint and converted give the same per-layer files."""

import json
import os

import numpy as np
import pytest
from safetensors.numpy import load_file, save_file

from benchmark import weights

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")

HF_NAMES = {
    "input_layernorm.scale": ("input_layernorm.weight", False),
    "post_attention_layernorm.scale": ("post_attention_layernorm.weight", False),
    "attn.wq": ("self_attn.q_proj.weight", True),
    "attn.kv_a": ("self_attn.kv_a_proj_with_mqa.weight", True),
    "attn.kv_a_norm": ("self_attn.kv_a_layernorm.weight", False),
    "attn.kv_b": ("self_attn.kv_b_proj.weight", True),
    "attn.wo": ("self_attn.o_proj.weight", True),
    "mlp.router": ("mlp.gate.weight", True),
    "mlp.correction_bias": ("mlp.gate.e_score_correction_bias", False),
    "mlp.shared_gate": ("mlp.shared_experts.gate_proj.weight", True),
    "mlp.shared_up": ("mlp.shared_experts.up_proj.weight", True),
    "mlp.shared_down": ("mlp.shared_experts.down_proj.weight", True),
}


def hf_state(model, seed):
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
                elif moe:  # stacked experts [E, in, out] -> one Linear each
                    proj = {"mlp.gate": "gate_proj", "mlp.up": "up_proj", "mlp.down": "down_proj"}[k]
                    for e in range(v.shape[0]):
                        sd[f"{name}.mlp.experts.{e}.{proj}.weight"] = v[e].T
                else:
                    proj = {"mlp.gate": "gate_proj", "mlp.up": "up_proj", "mlp.down": "down_proj"}[k]
                    sd[f"{name}.mlp.{proj}.weight"] = v.T
    return {k: np.ascontiguousarray(v) for k, v in sd.items()}


@pytest.mark.parametrize("name", ["moonlight-16b-a3b", "kanana-2-30b-a3b"])
def test_direct_writer_equals_prepare_weights(tmp_path, name):
    import prepare_weights
    from flexible_llm_sharding_tpu.config import LlamaConfig

    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        model = json.load(f)
    model.update(model.pop("rehearsal"))
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
    assert LlamaConfig.from_pretrained(direct) == LlamaConfig.from_pretrained(converted)


def test_expert_layers_repeat_as_hard_links_and_bytes_written_shrink(tmp_path):
    with open(os.path.join(CONFIGS, "moonlight-16b-a3b.json")) as f:
        model = json.load(f)
    model.update(model.pop("rehearsal"))
    model["num_hidden_layers"] = 8
    assert model["distinct_expert_layers"] == 3
    out = str(tmp_path / "m")
    info = weights.write_model(model, 1, out)
    ino = lambda i: os.stat(os.path.join(out, f"model.layers.{i}.safetensors")).st_ino  # noqa: E731
    assert ino(1) == ino(4) == ino(7) and ino(2) == ino(5) and ino(3) == ino(6)
    assert len({ino(i) for i in range(4)}) == 4
    assert info["bytes_written"] < info["bytes_model"]
    a = weights.layer_tensors(model, 1, "model.layers.1")["attn.wq"]
    b = weights.layer_tensors(model, 1, "model.layers.2")["attn.wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(
        np.asarray(a), np.asarray(weights.layer_tensors(model, 2, "model.layers.1")["attn.wq"]))


def test_published_sizes_by_shape():
    """ISSUE.md's reckoning: 17.9 GB and 17.8 GB of per-layer files, over the
    chip's 16.9 GB; one layer less would be under it."""
    for name, lo, hi in (("moonlight-16b-a3b", 17.8e9, 18.0e9), ("kanana-2-30b-a3b", 17.7e9, 17.9e9)):
        with open(os.path.join(CONFIGS, f"{name}.json")) as f:
            model = json.load(f)
        size = lambda m: sum(2 * int(np.prod(s)) for n in weights.layer_names(m)  # noqa: E731
                             for _, s, _ in weights.tensor_specs(m, n))
        assert lo < size(model) < hi
        assert size({**model, "num_hidden_layers": model["num_hidden_layers"] - 1}) < 16.9e9
