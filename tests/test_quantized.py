"""int8 weight-streaming: the opt-in transfer-compression mode.

The streaming executor is transfer-bound by design (weights cross the
host->HBM link once per shard per batch); ``split_into_layers(dtype='int8')``
halves the bytes on that link and the executor dequantizes on device after
the transfer. These tests pin the machinery exactly (int8-streamed scores ==
monolithic forward of the host-dequantized network) and the quantization
quality loosely (close to fp32 on a tiny model). No reference equivalent —
the reference streams fp16 only."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.runtime.executor import StreamingExecutor
from flexible_llm_sharding_tpu.runtime.tokenization import PromptTokenizer
from flexible_llm_sharding_tpu.utils import checkpoint as ckpt
from flexible_llm_sharding_tpu.utils.checkpoint import save_params

from tests.fake_tokenizer import FakeTokenizer

PROMPTS = [
    ("The capital of France", (" is Paris", " is Rome")),
    ("Two plus two equals", (" four", " five", " fish")),
]


def _write_hf_checkpoint(params, cfg: LlamaConfig, path: str) -> None:
    """Flat HF-keyed single-file checkpoint from a native params pytree
    (kernels transposed back to HF's [out, in])."""
    import json

    from safetensors.numpy import save_file

    sd = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["embedding"]),
        "model.norm.weight": np.asarray(params["norm"]["scale"]),
    }
    if not cfg.tie_word_embeddings:
        sd["lm_head.weight"] = np.ascontiguousarray(
            np.asarray(params["lm_head"]["kernel"]).T
        )
    hf_sub = {
        "attn.wq": "self_attn.q_proj.weight",
        "attn.wk": "self_attn.k_proj.weight",
        "attn.wv": "self_attn.v_proj.weight",
        "attn.wo": "self_attn.o_proj.weight",
        "mlp.gate": "mlp.gate_proj.weight",
        "mlp.up": "mlp.up_proj.weight",
        "mlp.down": "mlp.down_proj.weight",
    }
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}"
        sd[f"{p}.input_layernorm.weight"] = np.asarray(layer["input_layernorm"]["scale"])
        sd[f"{p}.post_attention_layernorm.weight"] = np.asarray(
            layer["post_attention_layernorm"]["scale"]
        )
        for nk, hk in hf_sub.items():
            a, b = nk.split(".")
            sd[f"{p}.{hk}"] = np.ascontiguousarray(np.asarray(layer[a][b]).T)
    os.makedirs(path, exist_ok=True)
    save_file(sd, os.path.join(path, "model.safetensors"))
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(
            {
                "model_type": "llama",
                "vocab_size": cfg.vocab_size,
                "hidden_size": cfg.hidden_size,
                "intermediate_size": cfg.intermediate_size,
                "num_hidden_layers": cfg.num_hidden_layers,
                "num_attention_heads": cfg.num_attention_heads,
                "num_key_value_heads": cfg.num_key_value_heads,
                "rms_norm_eps": cfg.rms_norm_eps,
                "tie_word_embeddings": cfg.tie_word_embeddings,
            },
            f,
        )


@pytest.fixture(scope="module")
def dirs(tiny_cfg, tmp_path_factory):
    """(fp32_native_dir, int8_dir, params)."""
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    base = tmp_path_factory.mktemp("q8")
    f32 = base / "f32"
    save_params(jax.tree.map(np.asarray, params), str(f32), tiny_cfg)
    hf = base / "hf"
    _write_hf_checkpoint(params, tiny_cfg, str(hf))
    q8 = base / "q8"
    ckpt.split_into_layers(str(hf), str(q8), dtype="int8")
    return str(f32), str(q8), params


def _dequantized_params(q8_dir: str, cfg: LlamaConfig):
    names = ckpt.layer_names_for(cfg.num_hidden_layers, cfg.tie_word_embeddings)
    deq = lambda t: jax.tree.map(  # noqa: E731
        lambda n: ckpt.dequantize_np(n) if ckpt.is_quantized_leaf(n) else n,
        t,
        is_leaf=ckpt.is_quantized_leaf,
    )
    out = {
        "embed": deq(ckpt.load_layer(q8_dir, "model.embed_tokens")),
        "layers": [
            deq(ckpt.load_layer(q8_dir, f"model.layers.{i}"))
            for i in range(cfg.num_hidden_layers)
        ],
        "norm": deq(ckpt.load_layer(q8_dir, "model.norm")),
    }
    if "lm_head" in names:
        out["lm_head"] = deq(ckpt.load_layer(q8_dir, "lm_head"))
    return jax.tree.map(jnp.asarray, out)


def test_int8_files_half_the_bytes(dirs, tiny_cfg):
    f32, q8, _ = dirs
    name = "model.layers.0.safetensors"
    a, b = os.path.getsize(os.path.join(f32, name)), os.path.getsize(
        os.path.join(q8, name)
    )
    assert b < 0.30 * a  # int8 payload + fp32 scales vs fp32 payload
    layer = ckpt.load_layer(q8, "model.layers.0")
    assert ckpt.is_quantized_leaf(layer["attn"]["wq"])
    assert layer["attn"]["wq"]["q8"].dtype == np.int8
    # 1-D tensors stay exact.
    assert not ckpt.is_quantized_leaf(layer["input_layernorm"]["scale"])


def test_int8_streaming_matches_dequantized_oracle(dirs, tiny_cfg, tmp_path):
    """The machinery invariant, EXACT: streaming the int8 checkpoint (int8
    over the link, on-device dequant) must equal the monolithic forward of
    the same network dequantized on host."""
    _, q8, _ = dirs
    fw = FrameworkConfig(
        model_path=q8,
        dtype="float32",
        bucket_multiple=8,
        layer_num_per_shard=1,
        prefetch_depth=1,
    )
    got = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS)

    params_deq = _dequantized_params(q8, tiny_cfg)
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    for (prefix, suffixes), sc in zip(PROMPTS, got):
        t = tok(prefix, suffixes)
        for s in range(t.num_suffixes):
            n_real = int(t.suffix_eos[s]) + 1
            full = np.concatenate(
                [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
            )[None, :]
            logits = llama.forward_full(params_deq, tiny_cfg, jnp.asarray(full))
            want = np.asarray(jax.nn.softmax(logits[0, -1]))
            np.testing.assert_allclose(sc[s, 0], want, rtol=2e-4, atol=2e-5)


def test_int8_close_to_fp32(dirs, tiny_cfg):
    """Quality smoke: per-channel int8 stays close to the fp32 scores."""
    f32, q8, _ = dirs
    def run(path):
        fw = FrameworkConfig(
            model_path=path, dtype="float32", bucket_multiple=8, prefetch_depth=0
        )
        return StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS)

    a, b = run(f32), run(q8)
    for x, y in zip(a, b):
        assert float(np.abs(x - y).max()) < 0.05


def test_int8_tied_embeddings(tiny_cfg, tmp_path):
    """Tied models requantize the transposed embedding for the head (per-V
    channels) — streamed scores still match the host-dequantized oracle."""
    import dataclasses

    cfg = dataclasses.replace(tiny_cfg, tie_word_embeddings=True)
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    hf = tmp_path / "hf"
    _write_hf_checkpoint(params, cfg, str(hf))
    q8 = tmp_path / "q8"
    ckpt.split_into_layers(str(hf), str(q8), dtype="int8")

    fw = FrameworkConfig(
        model_path=str(q8), dtype="float32", bucket_multiple=8, prefetch_depth=0
    )
    got = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS[:1])

    # Oracle: dequantized embed/layers/norm, head = requantized transpose
    # (exactly what the tied loader streams).
    params_deq = _dequantized_params(str(q8), cfg)
    emb_q = ckpt.load_layer(str(q8), "model.embed_tokens")["embedding"]
    kq, ks = ckpt._quantize_int8(
        np.ascontiguousarray(ckpt.dequantize_np(emb_q).T)
    )
    params_deq = dict(params_deq)
    params_deq["lm_head"] = {"kernel": jnp.asarray(kq.astype(np.float32) * ks)}

    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    prefix, suffixes = PROMPTS[0]
    t = tok(prefix, suffixes)
    for s in range(t.num_suffixes):
        n_real = int(t.suffix_eos[s]) + 1
        full = np.concatenate(
            [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
        )[None, :]
        logits = llama.forward_full(params_deq, cfg, jnp.asarray(full))
        want = np.asarray(jax.nn.softmax(logits[0, -1]))
        np.testing.assert_allclose(got[0][s, 0], want, rtol=2e-4, atol=2e-5)


def test_requantize_native_dir(dirs, tiny_cfg, tmp_path):
    """requantize_native (native dir -> int8, no HF source needed) produces a checkpoint the executor streams correctly."""
    f32, _, _ = dirs
    q8 = tmp_path / "q8b"
    names = ckpt.requantize_native(f32, str(q8))
    assert "model.layers.0" in names and os.path.exists(q8 / "config.json")

    fw = FrameworkConfig(
        model_path=str(q8), dtype="float32", bucket_multiple=8, prefetch_depth=0
    )
    got = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS[:1])
    params_deq = _dequantized_params(str(q8), tiny_cfg)
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    t = tok(*PROMPTS[0])
    for s in range(t.num_suffixes):
        n_real = int(t.suffix_eos[s]) + 1
        full = np.concatenate(
            [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
        )[None, :]
        logits = llama.forward_full(params_deq, tiny_cfg, jnp.asarray(full))
        want = np.asarray(jax.nn.softmax(logits[0, -1]))
        np.testing.assert_allclose(got[0][s, 0], want, rtol=2e-4, atol=2e-5)


def test_int8_stacked_shards_and_moe(tiny_cfg, tmp_path):
    """layer_num_per_shard >= 2 stacks quantized layers to q8 [k, ...] with
    scales [k, out] — the dequant must broadcast the scale on its own axis
    (a plain q*s crashes or silently mis-scales). MoE experts add a 4-D
    stacked case ([k, E, D, F] with scales [k, F])."""
    import dataclasses

    from tests.test_model_families import MIXTRAL_CFG

    for cfg, seed in ((tiny_cfg, 2), (MIXTRAL_CFG, 3)):
        params = llama.init_params(jax.random.PRNGKey(seed), cfg)
        f32 = tmp_path / f"f32-{cfg.model_type}-{seed}"
        save_params(jax.tree.map(np.asarray, params), str(f32), cfg)
        q8 = tmp_path / f"q8-{cfg.model_type}-{seed}"
        ckpt.requantize_native(str(f32), str(q8))

        fw = FrameworkConfig(
            model_path=str(q8),
            dtype="float32",
            bucket_multiple=8,
            layer_num_per_shard=2,
            prefetch_depth=0,
        )
        got = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS[:1])
        params_deq = _dequantized_params(str(q8), cfg)
        tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
        t = tok(*PROMPTS[0])
        for s in range(t.num_suffixes):
            n_real = int(t.suffix_eos[s]) + 1
            full = np.concatenate(
                [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
            )[None, :]
            logits = llama.forward_full(params_deq, cfg, jnp.asarray(full))
            want = np.asarray(jax.nn.softmax(logits[0, -1]))
            np.testing.assert_allclose(got[0][s, 0], want, rtol=2e-4, atol=2e-5)


def test_int8_kv_cache_decode(dirs, tiny_cfg):
    """DecodeGenerator over an int8 checkpoint: the dequant in _place feeds
    the prefill and per-token scans; greedy tokens must match the
    host-dequantized oracle."""
    from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator

    _, q8, _ = dirs
    n_gen = 2
    fw = FrameworkConfig(
        model_path=q8,
        dtype="float32",
        bucket_multiple=8,
        prefetch_depth=0,
        num_gen_token=n_gen,
    )
    scores, _ = DecodeGenerator(fw, tokenizer=FakeTokenizer())(PROMPTS[:1])

    params_deq = _dequantized_params(q8, tiny_cfg)
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    t = tok(*PROMPTS[0])
    for s in range(t.num_suffixes):
        ids = np.concatenate(
            [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, : int(t.suffix_eos[s]) + 1]]
        )
        for g in range(n_gen):
            logits = llama.forward_full(params_deq, tiny_cfg, jnp.asarray(ids[None]))
            want = np.asarray(jax.nn.softmax(logits[0, -1]))
            np.testing.assert_allclose(scores[0][s, g], want, rtol=2e-4, atol=1e-5)
            ids = np.concatenate([ids, [int(want.argmax())]])


def test_int8_tied_head_kv_decode(tiny_cfg, tmp_path):
    """The tied-embeddings + int8 + KV-decode crossing (VERDICT r2 weak 8):
    the loader's cached requantized-transpose head is streamed once per
    decode step — per-token scores must match the oracle built from the SAME
    double-quantized head (dequant -> transpose -> requant), pinning that the
    error stays at the int8 level end-to-end rather than compounding."""
    import dataclasses

    from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator

    cfg = dataclasses.replace(tiny_cfg, tie_word_embeddings=True)
    params = llama.init_params(jax.random.PRNGKey(4), cfg)
    hf = tmp_path / "hf"
    _write_hf_checkpoint(params, cfg, str(hf))
    q8 = tmp_path / "q8"
    ckpt.split_into_layers(str(hf), str(q8), dtype="int8")

    n_gen = 2
    fw = FrameworkConfig(
        model_path=str(q8),
        dtype="float32",
        bucket_multiple=8,
        prefetch_depth=0,
        num_gen_token=n_gen,
    )
    scores, _ = DecodeGenerator(fw, tokenizer=FakeTokenizer())(PROMPTS[:1])

    params_deq = _dequantized_params(str(q8), cfg)
    emb_q = ckpt.load_layer(str(q8), "model.embed_tokens")["embedding"]
    kq, ks = ckpt._quantize_int8(np.ascontiguousarray(ckpt.dequantize_np(emb_q).T))
    params_deq = dict(params_deq)
    params_deq["lm_head"] = {"kernel": jnp.asarray(kq.astype(np.float32) * ks)}

    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    t = tok(*PROMPTS[0])
    for s in range(t.num_suffixes):
        ids = np.concatenate(
            [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, : int(t.suffix_eos[s]) + 1]]
        )
        for g in range(n_gen):
            logits = llama.forward_full(params_deq, cfg, jnp.asarray(ids[None]))
            want = np.asarray(jax.nn.softmax(logits[0, -1]))
            np.testing.assert_allclose(scores[0][s, g], want, rtol=2e-4, atol=1e-5)
            ids = np.concatenate([ids, [int(want.argmax())]])


def test_int8_composes_with_tensor_parallel(dirs, tiny_cfg):
    """int8 + TP: the int8 payload takes the Megatron weight sharding and
    its scale the matching channel-axis sharding, so the on-device dequant
    runs sharded. Scores must equal the single-device int8 run exactly."""
    from flexible_llm_sharding_tpu.parallel.sharding import TpPlacement

    _, q8, _ = dirs
    fw = FrameworkConfig(
        model_path=q8, dtype="float32", bucket_multiple=8, prefetch_depth=0
    )
    single = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS)
    pl = TpPlacement(jax.devices()[:2], tiny_cfg)
    sharded = StreamingExecutor(fw, device=pl, tokenizer=FakeTokenizer())(PROMPTS)
    for a, b in zip(single, sharded):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_int8_dp_tp_composition(dirs):
    """int8 x (dp x tp): the broadcast producer device_puts the SAME int8
    host shard to each group's Megatron placement (payload takes the weight
    sharding, scale the channel axis) and each group dequantizes on its own
    sub-mesh. Must equal the single-device int8 run exactly."""
    from flexible_llm_sharding_tpu.runtime.orchestration import run_prompts

    _, q8, _ = dirs
    fw = FrameworkConfig(
        model_path=q8, dtype="float32", bucket_multiple=8, prefetch_depth=1
    )
    single = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS)
    import dataclasses

    both = run_prompts(
        dataclasses.replace(fw, tensor_parallel=2, data_parallel=True),
        PROMPTS,
        tokenizer=FakeTokenizer(),
        devices=jax.devices()[:4],
    )
    for a, b in zip(single, both):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", ["dp", "mp"])
def test_int8_multichip(dirs, tiny_cfg, mode, tmp_path):
    """int8 checkpoints through the multi-chip orchestration: DP prompt
    split (broadcast weight stream) and the interleaved MP pipeline both
    dequantize per chip/stage and must match the single-device int8 run."""
    from flexible_llm_sharding_tpu.runtime.orchestration import run_prompts

    _, q8, _ = dirs
    fw = FrameworkConfig(
        model_path=q8,
        dtype="float32",
        bucket_multiple=8,
        layer_num_per_shard=2,
        prefetch_depth=1,
        data_parallel=(mode == "dp"),
        disk_folder=str(tmp_path / "acts"),
    )
    single = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS)
    multi = run_prompts(fw, PROMPTS, tokenizer=FakeTokenizer(), devices=jax.devices()[:3])
    for a, b in zip(single, multi):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-5)


def test_int8_llama4_moe(tmp_path):
    """int8 over llama4's fused-expert tensors: [E, D, F] kernels quantize
    per (expert, output channel) — scale [E, F], amax over the input axis
    only — so an expert with small weights does not inherit the largest
    expert's scale; scores must match the host-dequantized oracle."""
    from tests.test_model_families import LLAMA4_CFG, _hf_llama4

    model = _hf_llama4(LLAMA4_CFG)
    src = tmp_path / "hf"
    model.save_pretrained(str(src))
    q8 = tmp_path / "q8"
    ckpt.split_into_layers(str(src), str(q8), dtype="int8")
    layer = ckpt.load_layer(str(q8), "model.layers.1")
    assert ckpt.is_quantized_leaf(layer["mlp"]["gate"])
    assert layer["mlp"]["gate"]["s"].shape == (4, 48)  # per (expert, F)

    fw = FrameworkConfig(
        model_path=str(q8),
        dtype="float32",
        bucket_multiple=8,
        layer_num_per_shard=3,
        prefetch_depth=0,
    )
    prompts = [("The capital of France", (" is Paris", " is Rome"))]
    got = StreamingExecutor(fw, tokenizer=FakeTokenizer())(prompts)

    params_deq = _dequantized_params(str(q8), LLAMA4_CFG)
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    t = tok(*prompts[0])
    for s in range(t.num_suffixes):
        n_real = int(t.suffix_eos[s]) + 1
        full = np.concatenate(
            [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
        )[None, :]
        logits = llama.forward_full(params_deq, LLAMA4_CFG, jnp.asarray(full))
        want = np.asarray(jax.nn.softmax(logits[0, -1]))
        np.testing.assert_allclose(got[0][s, 0], want, rtol=3e-4, atol=3e-5)


def test_int8_deepseek_mla(tmp_path):
    """int8 weight streaming composes with MLA + DeepSeek MoE: every
    2-D/3-D kernel (LoRA'd q, compressed kv_a/kv_b, stacked experts,
    shared expert, fp32 router) quantizes and the streamed scores match
    the host-dequant oracle. The router and correction bias must survive
    in a form the fp32 routing path still accepts."""
    cfg = LlamaConfig(
        model_type="deepseek_v3",
        vocab_size=256,
        hidden_size=64,
        intermediate_size=32,  # expert width (llama4 convention)
        intermediate_size_mlp=48,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=4,
        kv_lora_rank=32,
        q_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        num_local_experts=4,
        num_experts_per_tok=2,
        moe_n_group=2,
        moe_topk_group=1,
        moe_routed_scaling_factor=1.5,
        moe_layer_pattern=(False, True, True),
        rope_interleaved=True,
        query_pre_attn_scalar=24.0,
    )
    params = llama.init_mixed_params(jax.random.PRNGKey(9), cfg)
    # Rebuild the MoE MLPs with CONTROLLED weight scales (0.05-0.1 sigma):
    # init_mixed_params' defaults are fine structurally, but int8 error on
    # large-sigma random routers can flip expert selections, which would
    # turn a tolerance test into a flaky argmax comparison.
    rng = np.random.default_rng(9)
    for i, is_moe in enumerate(cfg.moe_layer_pattern):
        if not is_moe:
            continue
        e, f, d = cfg.num_local_experts, cfg.intermediate_size, cfg.hidden_size
        params["layers"][i]["mlp"] = {
            "router": jnp.asarray(rng.standard_normal((d, e)), jnp.float32) * 0.1,
            "correction_bias": jnp.asarray(rng.standard_normal((e,)), jnp.float32) * 0.1,
            "gate": jnp.asarray(rng.standard_normal((e, d, f)), jnp.float32) * 0.05,
            "up": jnp.asarray(rng.standard_normal((e, d, f)), jnp.float32) * 0.05,
            "down": jnp.asarray(rng.standard_normal((e, f, d)), jnp.float32) * 0.05,
            "shared_gate": jnp.asarray(rng.standard_normal((d, f)), jnp.float32) * 0.05,
            "shared_up": jnp.asarray(rng.standard_normal((d, f)), jnp.float32) * 0.05,
            "shared_down": jnp.asarray(rng.standard_normal((f, d)), jnp.float32) * 0.05,
        }
    f32 = tmp_path / "f32"
    save_params(jax.tree.map(np.asarray, params), str(f32), cfg)
    q8 = tmp_path / "q8"
    ckpt.requantize_native(str(f32), str(q8))

    fw = FrameworkConfig(
        model_path=str(q8), dtype="float32", bucket_multiple=8, prefetch_depth=0
    )
    got = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS[:1])
    params_deq = _dequantized_params(str(q8), cfg)
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    t = tok(*PROMPTS[0])
    for s in range(t.num_suffixes):
        n_real = int(t.suffix_eos[s]) + 1
        full = np.concatenate(
            [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
        )[None, :]
        logits = llama.forward_full(params_deq, cfg, jnp.asarray(full))
        want = np.asarray(jax.nn.softmax(logits[0, -1]))
        np.testing.assert_allclose(got[0][s, 0], want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# int4 (group-wise packed nibbles — a QUARTER of the bf16 link bytes)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dirs4(tiny_cfg, tmp_path_factory):
    """(fp32_native_dir, int4_dir)."""
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    base = tmp_path_factory.mktemp("q4")
    f32 = base / "f32"
    save_params(jax.tree.map(np.asarray, params), str(f32), tiny_cfg)
    hf = base / "hf"
    _write_hf_checkpoint(params, tiny_cfg, str(hf))
    q4 = base / "q4"
    ckpt.split_into_layers(str(hf), str(q4), dtype="int4")
    return str(f32), str(q4)


def test_int4_quantize_roundtrip_bound():
    """Per-weight error is bounded by half the GROUP's scale (symmetric
    round-to-nearest over [-7, 7]); packing/unpacking is lossless on the
    quantized integers."""
    rng = np.random.default_rng(0)
    w = rng.standard_normal((128, 96)).astype(np.float32)
    q, s = ckpt._quantize_int4(w)
    assert q.dtype == np.uint8 and q.shape == (64, 96)
    assert s.shape == (128 // ckpt.INT4_GROUP, 96)
    deq = ckpt.dequantize_np({"q4": q, "s": s})
    err = np.abs(deq - w).reshape(s.shape[0], ckpt.INT4_GROUP, 96)
    # Rounding: <= scale/2 everywhere (the group amax maps to exactly 7).
    assert np.all(err <= s[:, None, :] / 2 + 1e-6)
    # The group's own amax element is exactly representable.
    assert np.all(np.abs(deq).reshape(err.shape).max(axis=1) <= s * 7 + 1e-6)


def test_int4_files_quarter_the_bytes(dirs4, tiny_cfg):
    f32, q4 = dirs4
    name = "model.layers.0.safetensors"
    a = os.path.getsize(os.path.join(f32, name))
    b = os.path.getsize(os.path.join(q4, name))
    assert b < 0.20 * a  # packed nibbles + fp32 group scales vs fp32
    layer = ckpt.load_layer(q4, "model.layers.0")
    leaf = layer["attn"]["wq"]
    assert ckpt.is_quantized_leaf(leaf) and ckpt.quant_kind(leaf) == "q4"
    assert leaf["q4"].dtype == np.uint8
    d = tiny_cfg.hidden_size
    assert leaf["q4"].shape == (d // 2, d)
    assert leaf["s"].shape == (d // ckpt.INT4_GROUP, d)
    # 1-D tensors stay exact.
    assert not ckpt.is_quantized_leaf(layer["input_layernorm"]["scale"])


def _oracle_check(q_dir, cfg, got, prompts):
    """Shared exact-machinery assertion: streamed scores == monolithic
    forward of the host-dequantized network."""
    params_deq = _dequantized_params(q_dir, cfg)
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    for (prefix, suffixes), sc in zip(prompts, got):
        t = tok(prefix, suffixes)
        for s in range(t.num_suffixes):
            n_real = int(t.suffix_eos[s]) + 1
            full = np.concatenate(
                [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
            )[None, :]
            logits = llama.forward_full(params_deq, cfg, jnp.asarray(full))
            want = np.asarray(jax.nn.softmax(logits[0, -1]))
            np.testing.assert_allclose(sc[s, 0], want, rtol=2e-4, atol=2e-5)


def test_int4_streaming_matches_dequantized_oracle(dirs4, tiny_cfg):
    """The machinery invariant, EXACT: streaming the int4 checkpoint
    (packed nibbles over the link, on-device unpack + group dequant) must
    equal the monolithic forward of the same network dequantized on host."""
    _, q4 = dirs4
    fw = FrameworkConfig(
        model_path=q4,
        dtype="float32",
        bucket_multiple=8,
        layer_num_per_shard=1,
        prefetch_depth=1,
    )
    got = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS)
    _oracle_check(q4, tiny_cfg, got, PROMPTS)


def test_int4_close_to_fp32(dirs4):
    """Quality smoke: group-wise int4 stays in the fp32 scores'
    neighbourhood on the tiny model (looser than int8's 0.05 — 4 bits)."""
    f32, q4 = dirs4

    def run(path):
        fw = FrameworkConfig(
            model_path=path, dtype="float32", bucket_multiple=8, prefetch_depth=0
        )
        return StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS)

    a, b = run(f32), run(q4)
    for x, y in zip(a, b):
        assert float(np.abs(x - y).max()) < 0.15


def test_int4_stacked_shards_and_moe(tiny_cfg, tmp_path):
    """Stacked q4 leaves ([k, in/2, out] with scales [k, in/g, out]) under
    layer_num_per_shard=2, plus Mixtral's 3-D expert kernels, plus a MIXED
    checkpoint: intermediate 96 gives mlp.down an in-dim off the group, so
    that tensor falls back to per-output-channel int8 INSIDE the int4
    checkpoint (leaves self-describe) — asserted, not assumed."""
    import dataclasses

    from tests.test_model_families import MIXTRAL_CFG

    mixed_cfg = dataclasses.replace(tiny_cfg, intermediate_size=96)
    for cfg, seed in ((tiny_cfg, 2), (MIXTRAL_CFG, 3), (mixed_cfg, 5)):
        params = llama.init_params(jax.random.PRNGKey(seed), cfg)
        f32 = tmp_path / f"f32-{cfg.model_type}-{seed}"
        save_params(jax.tree.map(np.asarray, params), str(f32), cfg)
        q4 = tmp_path / f"q4-{cfg.model_type}-{seed}"
        ckpt.requantize_native(str(f32), str(q4), dtype="int4")

        fw = FrameworkConfig(
            model_path=str(q4),
            dtype="float32",
            bucket_multiple=8,
            layer_num_per_shard=2,
            prefetch_depth=0,
        )
        got = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS[:1])
        _oracle_check(str(q4), cfg, got, PROMPTS[:1])
        if cfg is mixed_cfg:
            layer = ckpt.load_layer(str(q4), "model.layers.0")
            assert ckpt.quant_kind(layer["mlp"]["down"]) == "q8"  # fallback
            assert ckpt.quant_kind(layer["mlp"]["gate"]) == "q4"


def test_int4_kv_cache_decode(dirs4, tiny_cfg):
    """DecodeGenerator over an int4 checkpoint: greedy tokens match the
    host-dequantized oracle across decode steps."""
    from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator

    _, q4 = dirs4
    n_gen = 2
    fw = FrameworkConfig(
        model_path=q4,
        dtype="float32",
        bucket_multiple=8,
        prefetch_depth=0,
        num_gen_token=n_gen,
    )
    scores, _ = DecodeGenerator(fw, tokenizer=FakeTokenizer())(PROMPTS[:1])

    params_deq = _dequantized_params(q4, tiny_cfg)
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    t = tok(*PROMPTS[0])
    for s in range(t.num_suffixes):
        ids = np.concatenate(
            [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, : int(t.suffix_eos[s]) + 1]]
        )
        for g in range(n_gen):
            logits = llama.forward_full(params_deq, tiny_cfg, jnp.asarray(ids[None]))
            want = np.asarray(jax.nn.softmax(logits[0, -1]))
            np.testing.assert_allclose(scores[0][s, g], want, rtol=2e-4, atol=1e-5)
            ids = np.concatenate([ids, [int(want.argmax())]])


def test_int4_tied_embeddings(tiny_cfg, tmp_path):
    """Tied models requantize the transposed embedding for the head at INT8
    even from an int4 source (ADVICE r4: a second int4 rounding can double
    the error on the quality-critical lm_head; int8's second rounding is
    negligible) — streamed scores match the oracle built from the SAME
    int4->int8 double-quantized head."""
    import dataclasses

    cfg = dataclasses.replace(tiny_cfg, tie_word_embeddings=True)
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    hf = tmp_path / "hf"
    _write_hf_checkpoint(params, cfg, str(hf))
    q4 = tmp_path / "q4"
    ckpt.split_into_layers(str(hf), str(q4), dtype="int4")

    fw = FrameworkConfig(
        model_path=str(q4), dtype="float32", bucket_multiple=8, prefetch_depth=0
    )
    got = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS[:1])

    params_deq = _dequantized_params(str(q4), cfg)
    emb_q = ckpt.load_layer(str(q4), "model.embed_tokens")["embedding"]
    assert ckpt.quant_kind(emb_q) == "q4"
    kq, ks = ckpt._quantize_int8(
        np.ascontiguousarray(ckpt.dequantize_np(emb_q).T)
    )
    params_deq = dict(params_deq)
    params_deq["lm_head"] = {
        "kernel": jnp.asarray(ckpt.dequantize_np({"q8": kq, "s": ks}))
    }

    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    prefix, suffixes = PROMPTS[0]
    t = tok(prefix, suffixes)
    for s in range(t.num_suffixes):
        n_real = int(t.suffix_eos[s]) + 1
        full = np.concatenate(
            [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
        )[None, :]
        logits = llama.forward_full(params_deq, cfg, jnp.asarray(full))
        want = np.asarray(jax.nn.softmax(logits[0, -1]))
        np.testing.assert_allclose(got[0][s, 0], want, rtol=2e-4, atol=2e-5)


def test_int4_tensor_parallel_rejects_group_split(dirs4, tiny_cfg):
    """int4 + TP when a Megatron row shard would SPLIT a quantization group
    across chips (here hidden=64 = exactly one group, tp=2) is a LOUD
    NotImplementedError, never a silent mis-shard. Group-aligned models
    compose — test_int4_composes_with_tensor_parallel."""
    from flexible_llm_sharding_tpu.parallel.sharding import TpPlacement

    _, q4 = dirs4
    fw = FrameworkConfig(
        model_path=q4, dtype="float32", bucket_multiple=8, prefetch_depth=0
    )
    pl = TpPlacement(jax.devices()[:2], tiny_cfg)
    with pytest.raises(NotImplementedError, match="quantization group"):
        StreamingExecutor(fw, device=pl, tokenizer=FakeTokenizer())(PROMPTS[:1])


def test_int4_composes_with_tensor_parallel(tmp_path):
    """int4 + TP (VERDICT r4 item 5): payload and group scale mirror the
    unquantized kernel axis-for-axis, so Megatron col shards apply verbatim
    and row shards slice whole groups when in/tp is a multiple of
    INT4_GROUP (hidden=128, tp=2 -> 64 = one group per chip). Scores must
    equal the single-device int4 run exactly (same double-quantized
    weights, same dequant math, just sharded)."""
    from flexible_llm_sharding_tpu.parallel.sharding import TpPlacement

    cfg = LlamaConfig(
        vocab_size=256,
        hidden_size=128,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        max_position_embeddings=512,
        tie_word_embeddings=False,
    )
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    hf = tmp_path / "hf"
    _write_hf_checkpoint(params, cfg, str(hf))
    q4 = tmp_path / "q4"
    ckpt.split_into_layers(str(hf), str(q4), dtype="int4")
    # The build must actually be int4 (in-dims all fit the group) — a
    # silent int8 fallback would make this test vacuous.
    leaf = ckpt.load_layer(str(q4), "model.layers.0")["attn"]["wo"]
    assert ckpt.quant_kind(leaf) == "q4"

    fw = FrameworkConfig(
        model_path=str(q4), dtype="float32", bucket_multiple=8,
        prefetch_depth=0,
    )
    single = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS)
    pl = TpPlacement(jax.devices()[:2], cfg)
    sharded = StreamingExecutor(fw, device=pl, tokenizer=FakeTokenizer())(
        PROMPTS
    )
    for a, b in zip(single, sharded):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_requantize_rejects_quantized_source(dirs4, tmp_path):
    """Re-quantizing an already-quantized dir would treat the 2-D fp32
    scale tensors as kernels (silent corruption) — it must raise instead."""
    _, q4 = dirs4
    with pytest.raises(ValueError, match="already quantized"):
        ckpt.requantize_native(q4, str(tmp_path / "bad"), dtype="int8")


# ---------------------------------------------------------------------------
# Per-layer mixed precision (ISSUE 14): sensitivity-planned int4/int8/bf16
# ---------------------------------------------------------------------------

from flexible_llm_sharding_tpu.integrity.manifest import (  # noqa: E402
    PrecisionMismatch,
    load_manifest,
)
from flexible_llm_sharding_tpu.runtime import precisionplan as pp  # noqa: E402


def _mixed_plan() -> pp.PrecisionPlan:
    """The suite's hand-built plan: bf16 layer 0 + int8 middle + int4
    elsewhere (the ISSUE's canonical shape)."""
    return pp.PrecisionPlan(
        layers=(
            ("model.embed_tokens", "int4"),
            ("model.layers.0", "bf16"),
            ("model.layers.1", "int8"),
            ("model.layers.2", "int4"),
            ("model.layers.3", "int4"),
            ("model.norm", "bf16"),
            ("lm_head", "int4"),
        ),
        divergence_cap=1.0,
    )


@pytest.fixture(scope="module")
def dirs_mixed(tiny_cfg, tmp_path_factory):
    """(f32_dir, uniform_bf16_dir, mixed_dir, plan)."""
    params = llama.init_params(jax.random.PRNGKey(7), tiny_cfg)
    base = tmp_path_factory.mktemp("mixed")
    f32 = base / "f32"
    save_params(jax.tree.map(np.asarray, params), str(f32), tiny_cfg)
    bf16 = base / "bf16"
    ckpt.requantize_native(str(f32), str(bf16), dtype="bfloat16")
    plan = _mixed_plan()
    mixed = base / "mixed"
    ckpt.requantize_native(str(f32), str(mixed), plan=plan)
    return str(f32), str(bf16), str(mixed), plan


def _mixed_oracle_params(mixed_dir: str, cfg: LlamaConfig):
    """Host oracle from the ACTUAL mixed files: quantized leaf-groups
    dequantized per layer, bf16 tensors cast to f32 (exactly what the
    on-device dequant + cast land in HBM)."""
    def fix(tree):
        return jax.tree.map(
            lambda n: (
                ckpt.dequantize_np(n)
                if ckpt.is_quantized_leaf(n)
                else np.asarray(n, np.float32)
            ),
            tree,
            is_leaf=ckpt.is_quantized_leaf,
        )

    out = {
        "embed": fix(ckpt.load_layer(mixed_dir, "model.embed_tokens")),
        "layers": [
            fix(ckpt.load_layer(mixed_dir, f"model.layers.{i}"))
            for i in range(cfg.num_hidden_layers)
        ],
        "norm": fix(ckpt.load_layer(mixed_dir, "model.norm")),
        "lm_head": fix(ckpt.load_layer(mixed_dir, "lm_head")),
    }
    return jax.tree.map(jnp.asarray, out)


def test_mixed_precision_streaming_matches_oracle(dirs_mixed, tiny_cfg):
    """The machinery invariant for a HETEROGENEOUS checkpoint: streaming
    the mixed dir (per-layer int4/int8/bf16 over the link, per-leaf
    on-device dequant/cast) equals the monolithic forward of the same
    network dequantized per layer on host. layer_num_per_shard=2 makes
    adjacent layers with DIFFERENT precisions land in one shard — the
    loader must split the scan runs at every structure change."""
    _, _, mixed, _ = dirs_mixed
    fw = FrameworkConfig(
        model_path=mixed,
        dtype="float32",
        bucket_multiple=8,
        layer_num_per_shard=2,
        prefetch_depth=1,
    )
    got = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS)
    params = _mixed_oracle_params(mixed, tiny_cfg)
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    for (prefix, suffixes), sc in zip(PROMPTS, got):
        t = tok(prefix, suffixes)
        for s in range(t.num_suffixes):
            n_real = int(t.suffix_eos[s]) + 1
            full = np.concatenate(
                [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
            )[None, :]
            logits = llama.forward_full(params, tiny_cfg, jnp.asarray(full))
            want = np.asarray(jax.nn.softmax(logits[0, -1]))
            np.testing.assert_allclose(sc[s, 0], want, rtol=2e-4, atol=2e-5)


def test_mixed_bf16_layers_bit_identical_to_uniform(dirs_mixed):
    """The plan's bf16 layers must be BIT-identical to the uniform-bf16
    baseline's files, tensor for tensor — same cast rule, zero extra
    rounding (the acceptance criterion's quality half)."""
    _, bf16, mixed, plan = dirs_mixed
    bf16_layers = [n for n, d in plan.layers if d == "bf16"]
    assert bf16_layers  # the plan must actually exercise the claim
    for name in bf16_layers:
        a = ckpt._mmap_safetensors(
            os.path.join(bf16, f"{name}{ckpt.LAYER_FILE_SUFFIX}")
        )
        b = ckpt._mmap_safetensors(
            os.path.join(mixed, f"{name}{ckpt.LAYER_FILE_SUFFIX}")
        )
        assert set(a) == set(b)
        for k in a:
            assert a[k].dtype == b[k].dtype
            assert np.array_equal(
                np.asarray(a[k]).view(np.uint8),
                np.asarray(b[k]).view(np.uint8),
            ), f"{name}/{k} drifted from the uniform bf16 encoding"


def test_mixed_manifest_dtypes_and_verify_audit(dirs_mixed):
    """The fresh integrity manifest records each layer's dtype kind, the
    plan is embedded, and the strict `verify` audit passes the dir —
    then catches a plan edit that no longer matches the files."""
    import json as _json

    from flexible_llm_sharding_tpu.integrity.verify import verify_model_dir

    _, _, mixed, plan = dirs_mixed
    man = load_manifest(mixed)
    kinds = {k: v["dtype"] for k, v in man["layers"].items()}
    assert kinds["model.layers.0"] == "bfloat16"
    assert kinds["model.layers.1"] == "int8"
    assert kinds["model.layers.2"] == "int4"
    assert kinds["model.embed_tokens"] == "int4"
    report = verify_model_dir(mixed)
    assert report["ok"], report["problems"]
    assert report["plan_layers_checked"] == len(plan.layers)

    # Flip one plan entry on disk: the audit must flag the layer whose
    # file/manifest no longer match the declared precision.
    path = os.path.join(mixed, pp.PLAN_NAME)
    with open(path) as f:
        data = _json.load(f)
    data["layers"]["model.layers.1"] = "bf16"
    with open(path, "w") as f:
        _json.dump(data, f)
    try:
        report = verify_model_dir(mixed)
        assert not report["ok"]
        assert any(
            p["status"] == "precision_mismatch" for p in report["problems"]
        )
    finally:
        plan.save(mixed)  # restore for the other module tests


def test_precision_mismatch_is_typed_at_load(dirs, tmp_path):
    """Manifest-vs-file precision drift is the typed PrecisionMismatch,
    not a crc error and not a retry storm: a manifest whose dtype entry
    disagrees with the (checksum-clean) file fails the load with the
    ShardLoadError-family error the serving degrade path understands."""
    _, q8, _ = dirs
    man = load_manifest(q8)
    bad = {
        "layers": {
            **man["layers"],
            "model.layers.1": {
                **man["layers"]["model.layers.1"],
                "dtype": "int4",
            },
        }
    }
    with pytest.raises(PrecisionMismatch, match="dtype kind 'int8'"):
        ckpt.load_layer(q8, "model.layers.1", manifest=bad)
    # Untouched entries still load clean.
    ckpt.load_layer(q8, "model.layers.0", manifest=man)


def test_plan_manifest_mismatch_typed_at_source_construction(
    dirs_mixed, tiny_cfg, tmp_path
):
    """An embedded plan that disagrees with the manifest fails at LOADER
    construction (two JSON files, no tensor reads) — before any wrong-
    precision byte crosses the link."""
    import json as _json
    import shutil

    from flexible_llm_sharding_tpu.runtime.executor import _HostShardLoader

    _, _, mixed, plan = dirs_mixed
    broken = tmp_path / "broken"
    shutil.copytree(mixed, broken)
    path = os.path.join(broken, pp.PLAN_NAME)
    with open(path) as f:
        data = _json.load(f)
    data["layers"]["model.layers.1"] = "bf16"  # manifest says int8
    with open(path, "w") as f:
        _json.dump(data, f)
    names = ckpt.layer_names_for(tiny_cfg.num_hidden_layers, False)
    with pytest.raises(PrecisionMismatch, match="planned 'bf16'"):
        _HostShardLoader(str(broken), names, np.float32)


def test_planner_determinism(dirs_mixed):
    """Same calibration batch + same budget -> bit-identical plan (the
    probe is RNG- and clock-free; greedy ties break by layer index)."""
    f32, _, _, _ = dirs_mixed
    budget = int(
        sum(
            pp.layer_dtype_bytes(ckpt.load_layer(f32, n))["bf16"]
            for n in ckpt.layer_names_for(4, False)
        )
        * 0.6
    )
    a = pp.build_plan(f32, PROMPTS[:1], FakeTokenizer(), bytes_budget=budget)
    b = pp.build_plan(f32, PROMPTS[:1], FakeTokenizer(), bytes_budget=budget)
    assert a.layers == b.layers
    assert a.est_bytes == b.est_bytes
    assert a.measured_divergence == b.measured_divergence
    assert a.est_bytes <= budget
    sens_a = pp.probe_sensitivity(f32, PROMPTS[:1], FakeTokenizer())
    sens_b = pp.probe_sensitivity(f32, PROMPTS[:1], FakeTokenizer())
    assert sens_a == sens_b


def test_plan_from_sensitivity_modes():
    """Greedy semantics, both constraint modes, on a synthetic table:
    budget mode downgrades the least-sensitive layer first; cap mode
    upgrades the most-relief-per-byte layer first."""
    names = ["a", "b"]
    sizes = {
        n: {"bf16": 100, "int8": 55, "int4": 30} for n in names
    }
    sens = {
        "a": {"int8": 0.001, "int4": 0.01},
        "b": {"int8": 0.1, "int4": 0.5},
    }
    plan = pp.plan_from_sensitivity(
        names, sizes, sens, bytes_budget=155
    )
    assert plan.dtypes == {"a": "int8", "b": "bf16"}
    assert plan.est_bytes == 155
    plan = pp.plan_from_sensitivity(
        names, sizes, sens, divergence_cap=0.011
    )
    assert plan.dtypes == {"a": "int4", "b": "bf16"}
    assert plan.divergence_cap == 0.011
    # A layer where quantization saves nothing lands at bf16 (dominance:
    # lossless AND no more bytes).
    sizes["c"] = {"bf16": 10, "int8": 20, "int4": 20}
    sens["c"] = {"int8": 0.0, "int4": 0.0}
    plan = pp.plan_from_sensitivity(
        names + ["c"], sizes, sens, divergence_cap=1.0
    )
    assert plan.dtypes["c"] == "bf16"
    # Stuck-rung regression: a layer whose int4 encoding falls back to
    # int8 entirely (same bytes, same divergence) has a zero-relief
    # int4->int8 step — cap mode must still reach bf16 through the
    # multi-rung move, or the plan would violate its own declared cap.
    plan = pp.plan_from_sensitivity(
        ["d"],
        {"d": {"bf16": 100, "int8": 55, "int4": 55}},
        {"d": {"int8": 0.5, "int4": 0.5}},
        divergence_cap=0.01,
    )
    assert plan.dtypes == {"d": "bf16"}
    assert plan.est_divergence <= 0.01


def test_layer_dtype_bytes_matches_materialized(dirs_mixed, tiny_cfg):
    """The planner's shapes-only byte estimates equal the converter's
    actual packed output, layer for layer and dtype for dtype — the
    estimate can never drift to the dequantized logical size."""
    f32, bf16, mixed, plan = dirs_mixed
    for name, dt in plan.layers:
        est = pp.layer_dtype_bytes(ckpt.load_layer(f32, name))[dt]
        src = mixed if dt != "bf16" else bf16
        flat = ckpt._mmap_safetensors(
            os.path.join(src, f"{name}{ckpt.LAYER_FILE_SUFFIX}")
        )
        actual = sum(np.asarray(v).nbytes for v in flat.values())
        assert est == actual, (name, dt, est, actual)


@pytest.mark.parametrize("plan_kind", ["hand_built", "budget_0.6"])
def test_mixed_stream_moves_the_plans_bytes(dirs_mixed, tmp_path, plan_kind):
    """What the link carries, by the executors' own ``streamed_bytes`` over
    identical sweeps: a uniform-bf16 directory streams the planner's bf16
    estimate and a mixed one the plan's, byte for byte. A plan built for
    60% of the bf16 bytes therefore takes at least 35% of them off the link
    (the mixed-precision acceptance line) and stays under the divergence cap
    it declares; a converter, loader or counter that fell back to bf16 would
    read 0."""
    f32, bf16, mixed, plan = dirs_mixed
    names = ckpt.layer_names_for(4, False)
    est = {n: pp.layer_dtype_bytes(ckpt.load_layer(f32, n)) for n in names}
    bf16_bytes = sum(e["bf16"] for e in est.values())
    if plan_kind == "budget_0.6":
        budget = int(bf16_bytes * 0.6)
        plan = pp.build_plan(f32, PROMPTS[:1], FakeTokenizer(), bytes_budget=budget)
        assert plan.est_bytes <= budget
        mixed = str(tmp_path / "mixed06")
        ckpt.requantize_native(f32, mixed, plan=plan)

    def streamed(path):
        fw = FrameworkConfig(
            model_path=path, dtype="float32", bucket_multiple=8,
            prefetch_depth=0, host_cache_gb=0.0, hbm_pin_gb=0.0,
        )
        ex = StreamingExecutor(fw, tokenizer=FakeTokenizer())
        return ex(PROMPTS), ex.stats["streamed_bytes"]

    (scores_b, b), (scores_m, m) = streamed(bf16), streamed(mixed)
    assert b == bf16_bytes
    assert m == sum(est[n][dt] for n, dt in plan.layers)
    if plan_kind == "budget_0.6":
        assert m == plan.est_bytes
        assert 1.0 - m / b >= 0.35
        # And the quality side of the same plan: the mixed stream's
        # next-token distributions against the bf16 stream's, under the
        # cap the plan itself declares.
        divs = [
            pp.kl_divergence(sb[i, 0][None], sm[i, 0][None])
            for sb, sm in zip(scores_b, scores_m)
            for i in range(sb.shape[0])
        ]
        assert float(np.mean(divs)) <= plan.divergence_cap


def test_mixed_composes_with_tensor_parallel(tmp_path):
    """Mixed precision + TP: per-leaf sharding adaptation (q4 group
    scales, q8 channel scales, raw bf16) must reproduce the single-
    device mixed run exactly. hidden=128 keeps every row shard on whole
    int4 groups."""
    from flexible_llm_sharding_tpu.parallel.sharding import TpPlacement

    cfg = LlamaConfig(
        vocab_size=256,
        hidden_size=128,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        max_position_embeddings=512,
        tie_word_embeddings=False,
    )
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    f32 = tmp_path / "f32"
    save_params(jax.tree.map(np.asarray, params), str(f32), cfg)
    plan = pp.PrecisionPlan(
        layers=(
            ("model.embed_tokens", "int8"),
            ("model.layers.0", "bf16"),
            ("model.layers.1", "int4"),
            ("model.norm", "bf16"),
            ("lm_head", "int8"),
        ),
        divergence_cap=1.0,
    )
    mixed = tmp_path / "mixed"
    ckpt.requantize_native(str(f32), str(mixed), plan=plan)
    fw = FrameworkConfig(
        model_path=str(mixed), dtype="float32", bucket_multiple=8,
        prefetch_depth=0,
    )
    single = StreamingExecutor(fw, tokenizer=FakeTokenizer())(PROMPTS)
    pl = TpPlacement(jax.devices()[:2], cfg)
    sharded = StreamingExecutor(fw, device=pl, tokenizer=FakeTokenizer())(
        PROMPTS
    )
    for a, b in zip(single, sharded):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_mixed_serve_parity(dirs_mixed):
    """Mixed precision on the SERVING path: engine completions over the
    mixed checkpoint are token-identical to the offline KV-decode batch
    on the same prompts."""
    from flexible_llm_sharding_tpu.config import ServeConfig
    from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator
    from flexible_llm_sharding_tpu.serve import ServeEngine

    _, _, mixed, _ = dirs_mixed
    prompts = [
        ("The capital of France", (" is Paris", " is Rome")),
        ("Two plus two equals", (" four", " five")),
    ]
    fw = FrameworkConfig(
        model_path=mixed,
        dtype="float32",
        bucket_multiple=8,
        layer_num_per_shard=1,
        storage_location="cpu",
        block_size=2,
        prefetch_depth=0,
        num_gen_token=2,
    )
    off_scores, off_updated = DecodeGenerator(fw, tokenizer=FakeTokenizer())(
        list(prompts)
    )
    engine = ServeEngine(
        fw,
        ServeConfig(max_wave_requests=2, default_max_new_tokens=2),
        tokenizer=FakeTokenizer(),
    )
    try:
        reqs = [engine.submit(p, s) for p, s in prompts]
        results = [r.future.result(timeout=300) for r in reqs]
        assert engine.drain(timeout=120)
    finally:
        engine.shutdown(drain=False)
    assert engine.error is None
    for res, want, upd in zip(results, off_scores, off_updated):
        assert res.updated == upd
        assert (res.scores.argmax(-1) == want.argmax(-1)).all()
        np.testing.assert_allclose(res.scores, want, rtol=1e-5, atol=1e-6)


def test_mixed_fleet_parity(dirs_mixed):
    """Mixed precision under the replica fleet: 2 replicas sharing the
    process host shard cache over the mixed checkpoint, token-identical
    to the offline path."""
    from flexible_llm_sharding_tpu.config import ServeConfig
    from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator
    from flexible_llm_sharding_tpu.serve import ReplicaFleet

    _, _, mixed, _ = dirs_mixed
    prompts = [
        ("The capital of France", (" is Paris", " is Rome")),
        ("Two plus two equals", (" four", " five")),
        ("The sky is", (" blue", " green")),
    ]
    fw = FrameworkConfig(
        model_path=mixed,
        dtype="float32",
        bucket_multiple=8,
        layer_num_per_shard=1,
        storage_location="cpu",
        block_size=2,
        prefetch_depth=0,
        num_gen_token=2,
    )
    off_scores, off_updated = DecodeGenerator(fw, tokenizer=FakeTokenizer())(
        list(prompts)
    )
    fleet = ReplicaFleet(
        fw,
        ServeConfig(
            replicas=2,
            max_wave_requests=2,
            default_max_new_tokens=2,
            router_health_poll_s=0.05,
        ),
        tokenizer=FakeTokenizer(),
    )
    try:
        reqs = [fleet.submit(p, s) for p, s in prompts]
        results = [r.future.result(timeout=300) for r in reqs]
    finally:
        fleet.shutdown(drain=True)
    assert fleet.error is None
    for res, want, upd in zip(results, off_scores, off_updated):
        assert res.updated == upd
        assert (res.scores.argmax(-1) == want.argmax(-1)).all()
        np.testing.assert_allclose(res.scores, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Satellites: tied-head requant amortization + packed byte accounting
# ---------------------------------------------------------------------------

@pytest.fixture()
def tied_q4_dir(tiny_cfg, tmp_path):
    import dataclasses

    cfg = dataclasses.replace(tiny_cfg, tie_word_embeddings=True)
    params = llama.init_params(jax.random.PRNGKey(11), cfg)
    hf = tmp_path / "hf"
    _write_hf_checkpoint(params, cfg, str(hf))
    q4 = tmp_path / "q4"
    ckpt.split_into_layers(str(hf), str(q4), dtype="int4")
    return str(q4), cfg


def test_tied_head_requant_cached_across_loaders(tied_q4_dir, tiny_cfg):
    """Satellite 1 (executor.py lm_head hot path): the tied/quantized
    head's dequant->transpose->requant result is seated in the host
    shard cache, so a WARM process — a fresh loader from a serve source
    restart or a new decode call — performs ZERO requants; the process
    counter and the cache's hit stats prove it."""
    from flexible_llm_sharding_tpu.runtime.executor import (
        _HostShardLoader,
        np_dtype_for,
        process_tied_head_requants,
        reset_process_streamed_bytes,
    )
    from flexible_llm_sharding_tpu.runtime.hostcache import HostShardCache

    q4, cfg = tied_q4_dir
    names = ckpt.layer_names_for(cfg.num_hidden_layers, False)
    head_idx = names.index("lm_head")
    cache = HostShardCache(budget_bytes=1 << 30)
    reset_process_streamed_bytes()
    loader1 = _HostShardLoader(
        q4, names, np_dtype_for("float32"), tied_embeddings=True,
        host_cache=cache,
    )
    cold = loader1.build_host_shard((head_idx,))
    assert process_tied_head_requants() == 1
    loader1.close()

    # Fresh loader, same process cache: zero additional requants AND the
    # warm build's head segments are numerically identical to the cold
    # build's.
    loader2 = _HostShardLoader(
        q4, names, np_dtype_for("float32"), tied_embeddings=True,
        host_cache=cache,
    )
    hits_before = cache.stats()["hits"]
    warm = loader2.build_host_shard((head_idx,))
    loader2.close()
    assert process_tied_head_requants() == 1  # zero requants when warm
    assert cache.stats()["hits"] > hits_before
    ck, cs = cold[0][1]["kernel"]["q8"], cold[0][1]["kernel"]["s"]
    wk, ws = warm[0][1]["kernel"]["q8"], warm[0][1]["kernel"]["s"]
    assert np.array_equal(ck, wk) and np.array_equal(cs, ws)


def test_tied_head_per_loader_memo_without_cache(tied_q4_dir):
    """With no host cache (chaos mode disables it) the per-loader memo
    still bounds the cost at one requant per loader — never per sweep."""
    from flexible_llm_sharding_tpu.runtime.executor import (
        _HostShardLoader,
        np_dtype_for,
        process_tied_head_requants,
        reset_process_streamed_bytes,
    )

    q4, cfg = tied_q4_dir
    names = ckpt.layer_names_for(cfg.num_hidden_layers, False)
    head_idx = names.index("lm_head")
    reset_process_streamed_bytes()
    loader = _HostShardLoader(
        q4, names, np_dtype_for("float32"), tied_embeddings=True
    )
    for _ in range(3):  # three sweeps' worth of head re-streams
        loader.build_host_shard((head_idx,))
    loader.close()
    assert process_tied_head_requants() == 1


def test_layer_stream_bytes_tied_quantized_head(tied_q4_dir):
    """Satellite 2: the tied lm_head over a quantized embedding streams
    the int8 REQUANT (q [D, V] + fp32 scale [V]), not the embed file's
    packed int4 bytes and certainly not the dequantized logical size —
    the planner's estimate must equal the loader's actual built tree."""
    from flexible_llm_sharding_tpu.runtime.executor import (
        _HostShardLoader,
        np_dtype_for,
    )
    from flexible_llm_sharding_tpu.runtime.residency import layer_stream_bytes

    q4, cfg = tied_q4_dir
    names = ckpt.layer_names_for(cfg.num_hidden_layers, False)
    head_idx = names.index("lm_head")
    sizes = layer_stream_bytes(q4, names, tied_embeddings=True)
    v, d = cfg.vocab_size, cfg.hidden_size
    want = d * v + 4 * v  # int8 payload + fp32 per-V-channel scale
    assert sizes[head_idx] == want
    embed_file = os.path.getsize(
        os.path.join(q4, "model.embed_tokens.safetensors")
    )
    assert sizes[head_idx] != embed_file  # int4-packed file underestimates
    # The estimate equals what the loader actually builds for upload.
    loader = _HostShardLoader(
        q4, names, np_dtype_for("float32"), tied_embeddings=True
    )
    segs = loader.build_host_shard((head_idx,))
    loader.close()
    built = sum(
        a.nbytes for _, seg in segs for a in jax.tree.leaves(seg)
    )
    assert built == want


def test_hostcache_charges_packed_bytes(dirs4, tiny_cfg):
    """The hostcache budget charges quantized shard trees at their
    PACKED size (q + scales) — the dequantized logical size would
    overstate the entry ~4x and starve the LRU."""
    from flexible_llm_sharding_tpu.runtime.executor import (
        _HostShardLoader,
        np_dtype_for,
    )
    from flexible_llm_sharding_tpu.runtime.hostcache import HostShardCache

    _, q4 = dirs4
    names = ckpt.layer_names_for(tiny_cfg.num_hidden_layers, False)
    idx = names.index("model.layers.0")
    cache = HostShardCache(budget_bytes=1 << 30)
    loader = _HostShardLoader(
        q4, names, np_dtype_for("float32"), host_cache=cache
    )
    segs = loader.build_host_shard((idx,))
    loader.close()
    packed = sum(a.nbytes for _, seg in segs for a in jax.tree.leaves(seg))
    logical = sum(
        np.asarray(a, np.float32).nbytes
        if a.dtype != np.float32
        else a.nbytes
        for _, seg in segs
        for a in jax.tree.leaves(seg)
    )
    assert cache.stats()["bytes"] == packed
    assert packed < logical  # packing is the whole point


def test_residency_plan_pins_bf16_layers_first(dirs_mixed, tiny_cfg):
    """Residency/plan co-optimization: the bf16 decoder is the most
    expensive to stream (largest packed file), so the size-first pin
    order — with the embedded plan's dtype breaking size ties — buys it
    back first: a budget sized for exactly the always-hot layers plus
    one decoder pins the plan's bf16 decoder, not an int4 one."""
    from flexible_llm_sharding_tpu.runtime.residency import (
        layer_stream_bytes,
        plan_residency,
    )

    _, _, mixed, _ = dirs_mixed
    names = ckpt.layer_names_for(tiny_cfg.num_hidden_layers, False)
    sizes = layer_stream_bytes(mixed, names)
    non_decoder = sum(
        sizes[i]
        for i, n in enumerate(names)
        if not n.startswith("model.layers.")
    )
    bf16_idx = names.index("model.layers.0")
    budget = non_decoder + sizes[bf16_idx]
    plan = plan_residency(mixed, names, budget)
    decoder_pins = [
        i for i in plan.pinned if names[i].startswith("model.layers.")
    ]
    assert decoder_pins == [bf16_idx]


def test_corrupt_plan_typed_at_source_construction(dirs_mixed, tmp_path):
    """A torn/corrupt embedded plan is the same structural defect as a
    plan/manifest mismatch — typed PrecisionMismatch at loader
    construction, never a bare ValueError escaping to the serve loop's
    fatal path."""
    import shutil

    from flexible_llm_sharding_tpu.runtime.executor import _HostShardLoader

    _, _, mixed, _ = dirs_mixed
    broken = tmp_path / "torn"
    shutil.copytree(mixed, broken)
    with open(os.path.join(broken, pp.PLAN_NAME), "w") as f:
        f.write('{"version": 1, "layers": {truncated')
    names = ckpt.layer_names_for(4, False)
    with pytest.raises(PrecisionMismatch, match="corrupt precision plan"):
        _HostShardLoader(str(broken), names, np.float32)


def test_quantize_flat_fp16_oned_upcasts_and_estimator_agrees():
    """Sub-fp32 1-D floats honor the documented "stay exact in float32"
    contract (fp16 used to pass through at 2 B/elem, silently breaking
    the planner's estimate==materialized invariant on fp16 sources);
    the shapes-only estimator matches the materialized bytes."""
    sd = {
        "scale": np.ones(8, np.float16),
        "kern": np.ones((8, 8), np.float16),
    }
    qd = ckpt._quantize_flat(sd, "int8")
    assert qd["scale"].dtype == np.float32
    est = pp.layer_dtype_bytes(sd)
    actual = sum(v.nbytes for v in qd.values())
    assert est["int8"] == actual == 8 * 4 + 8 * 8 * 1 + 8 * 4
