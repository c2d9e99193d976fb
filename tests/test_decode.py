"""KV-cache decode mode: greedy tokens and per-step distributions must match
a token-level monolithic oracle (forward_full re-run on the growing ids)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexible_llm_sharding_tpu.config import FrameworkConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator
from flexible_llm_sharding_tpu.runtime.tokenization import PromptTokenizer
from flexible_llm_sharding_tpu.utils.checkpoint import save_params

from tests.fake_tokenizer import FakeTokenizer

PROMPTS = [
    ("The capital of France", (" is Paris", " is Rome")),
    ("Two plus two equals", (" four", " five", " fish")),
]

N_GEN = 3


@pytest.fixture(scope="module")
def model(tiny_cfg, tmp_path_factory):
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    d = tmp_path_factory.mktemp("tiny_model_decode")
    save_params(jax.tree.map(np.asarray, params), str(d), tiny_cfg)
    return str(d), params


def _oracle(params, cfg, tok, prompts, n_gen):
    """Token-level greedy decode per suffix via the monolithic forward."""
    out_scores, out_tokens = [], []
    for prefix, suffixes in prompts:
        t = tok(prefix, suffixes)
        rows_s, rows_t = [], []
        for s in range(t.num_suffixes):
            ids = np.concatenate(
                [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, : int(t.suffix_eos[s]) + 1]]
            )
            dists, toks_ = [], []
            for _ in range(n_gen):
                logits = llama.forward_full(params, cfg, jnp.asarray(ids[None]))
                dist = np.asarray(jax.nn.softmax(logits[0, -1]))
                nxt = int(dist.argmax())
                dists.append(dist)
                toks_.append(nxt)
                ids = np.concatenate([ids, [nxt]])
            rows_s.append(np.stack(dists))
            rows_t.append(toks_)
        out_scores.append(np.stack(rows_s))  # [S, n_gen, V]
        out_tokens.append(rows_t)
    return out_scores, out_tokens


@pytest.mark.parametrize("storage,lnps", [("cpu", 1), ("tpu", 2), ("cpu", 100)])
def test_decode_matches_token_level_oracle(tiny_cfg, model, storage, lnps):
    model_dir, params = model
    cfg = FrameworkConfig(
        model_path=model_dir,
        layer_num_per_shard=lnps,
        storage_location=storage,
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
        num_gen_token=N_GEN,
    )
    gen = DecodeGenerator(cfg, tokenizer=FakeTokenizer())
    scores, updated = gen(list(PROMPTS))

    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    want_scores, want_tokens = _oracle(params, tiny_cfg, tok, PROMPTS, N_GEN)

    for i, (_, sfx) in enumerate(PROMPTS):
        assert scores[i].shape == (len(sfx), N_GEN, tiny_cfg.vocab_size)
        np.testing.assert_allclose(
            scores[i], want_scores[i], rtol=2e-4, atol=1e-5
        )
        got_tokens = scores[i].argmax(-1)
        assert got_tokens.tolist() == want_tokens[i]

    # Updated prompts grow by the decoded token text.
    for (_, sfx), (_, usfx) in zip(PROMPTS, updated):
        for orig, new in zip(sfx, usfx):
            assert new.startswith(orig) and len(new) > len(orig)


def test_decode_sampling_deterministic(tiny_cfg, model):
    """temperature/top-k/top-p sampling in KV decode: deterministic per
    seed, raw distributions unchanged (step 0 equals the greedy run's),
    suffixes still grow."""
    import dataclasses

    model_dir, _ = model
    fw = FrameworkConfig(
        model_path=model_dir,
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
        num_gen_token=3,
        temperature=0.8,
        top_k=20,
        top_p=0.95,
        seed=3,
    )
    a, ua = DecodeGenerator(fw, tokenizer=FakeTokenizer())(list(PROMPTS))
    b, ub = DecodeGenerator(fw, tokenizer=FakeTokenizer())(list(PROMPTS))
    assert ua == ub
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)

    g, _ = DecodeGenerator(
        dataclasses.replace(fw, temperature=0.0, top_k=0, top_p=0.0),
        tokenizer=FakeTokenizer(),
    )(list(PROMPTS))
    for x, y in zip(a, g):
        np.testing.assert_allclose(x[:, 0], y[:, 0], rtol=1e-6)
    for (_, sfx), (_, usfx) in zip(PROMPTS, ua):
        for orig, new in zip(sfx, usfx):
            assert new.startswith(orig) and len(new) > len(orig)


def test_decode_flash_kernel_matches_oracle(tmp_path_factory):
    """KV decode with the flash decode kernel (use_pallas=True, interpret on
    the CPU mesh): per-step distributions and greedy tokens must match the
    token-level oracle. Needs a flash-eligible head_dim (128)."""
    from flexible_llm_sharding_tpu.config import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=256,
        hidden_size=256,
        intermediate_size=384,
        num_hidden_layers=2,
        num_attention_heads=2,
        num_key_value_heads=2,
        max_position_embeddings=512,
    )
    params = llama.init_params(jax.random.PRNGKey(8), cfg)
    d = tmp_path_factory.mktemp("decode_flash_model")
    save_params(jax.tree.map(np.asarray, params), str(d), cfg)

    fw = FrameworkConfig(
        model_path=str(d),
        dtype="float32",
        bucket_multiple=64,
        block_size=2,
        prefetch_depth=0,
        num_gen_token=N_GEN,
        use_pallas=True,
    )
    scores, _ = DecodeGenerator(fw, tokenizer=FakeTokenizer())(list(PROMPTS))

    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=64)
    want_scores, want_tokens = _oracle(params, cfg, tok, PROMPTS, N_GEN)
    for i in range(len(PROMPTS)):
        np.testing.assert_allclose(scores[i], want_scores[i], rtol=2e-4, atol=1e-5)
        assert scores[i].argmax(-1).tolist() == want_tokens[i]


def test_decode_cli(tiny_cfg, model, tmp_path):
    import pickle

    from flexible_llm_sharding_tpu.cli import main

    model_dir, _ = model
    ppkl, opkl = tmp_path / "p.pkl", tmp_path / "s.pkl"
    with open(ppkl, "wb") as f:
        pickle.dump(PROMPTS[:1], f)
    main(
        [
            "--model_path", model_dir,
            "--prompt_pickle", str(ppkl),
            "--output_file", str(opkl),
            "--num_gen_token", "2",
            "--dtype", "float32",
            "--kv_cache", "true",
            "--num_devices", "1",
        ],
        tokenizer=FakeTokenizer(),
    )
    import pickle as pkl

    with open(opkl, "rb") as f:
        scores = pkl.load(f)
    assert scores[0].shape == (2, 2, tiny_cfg.vocab_size)


def test_decode_dp_matches_single_device(tiny_cfg, model):
    """DP prompt-split decode on 3 virtual chips == single-device decode
    (VERDICT r1 #5: multi-device KV-cache decode)."""
    from flexible_llm_sharding_tpu.runtime.orchestration import run_decode

    model_dir, params = model
    prompts = PROMPTS + [("The sky is", (" blue", " green"))]

    def cfg(dp):
        return FrameworkConfig(
            model_path=model_dir,
            layer_num_per_shard=1,
            storage_location="cpu",
            dtype="float32",
            bucket_multiple=8,
            block_size=2,
            prefetch_depth=1,
            num_gen_token=N_GEN,
            data_parallel=dp,
        )

    want, want_up, want_tok = run_decode(
        cfg(False), prompts, tokenizer=FakeTokenizer(), devices=jax.devices()[:1]
    )
    got, got_up, got_tok = run_decode(
        cfg(True), prompts, tokenizer=FakeTokenizer(), devices=jax.devices()[:3]
    )
    assert len(got) == len(prompts)
    assert got_tok == want_tok > 0
    assert got_up == want_up
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_decode_dp_cli(tiny_cfg, model, tmp_path):
    """CLI accepts --kv_cache with multiple chips when --data_parallel."""
    import pickle

    from flexible_llm_sharding_tpu.cli import main

    model_dir, _ = model
    ppkl, opkl = tmp_path / "p.pkl", tmp_path / "s.pkl"
    with open(ppkl, "wb") as f:
        pickle.dump(PROMPTS, f)
    main(
        [
            "--model_path", model_dir,
            "--prompt_pickle", str(ppkl),
            "--output_file", str(opkl),
            "--num_gen_token", "2",
            "--dtype", "float32",
            "--kv_cache", "true",
            "--data_parallel", "true",
            "--num_devices", "2",
        ],
        tokenizer=FakeTokenizer(),
    )
    with open(opkl, "rb") as f:
        scores = pickle.load(f)
    assert len(scores) == len(PROMPTS)
    assert scores[0].shape == (2, 2, tiny_cfg.vocab_size)


def test_decode_single_token(tiny_cfg, model):
    """n_gen=1 degenerates to a pure scoring pass."""
    model_dir, params = model
    cfg = FrameworkConfig(
        model_path=model_dir,
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        prefetch_depth=0,
        num_gen_token=1,
    )
    gen = DecodeGenerator(cfg, tokenizer=FakeTokenizer())
    scores, _ = gen(list(PROMPTS))
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    want_scores, _ = _oracle(params, tiny_cfg, tok, PROMPTS, 1)
    for got, want in zip(scores, want_scores):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("storage,lnps,nd", [("tpu", 1, 3), ("cpu", 2, 4)])
def test_decode_mp_pipeline_matches_oracle(tiny_cfg, model, storage, lnps, nd):
    """KV-cache decode over the interleaved MP pipeline: per-stage weights
    AND parked KV on each stage's chip, activations hopping over ICI — must
    match the token-level monolithic oracle exactly."""
    model_dir, params = model
    cfg = FrameworkConfig(
        model_path=model_dir,
        layer_num_per_shard=lnps,
        storage_location=storage,
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=1,
        num_gen_token=N_GEN,
    )
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    want_s, want_t = _oracle(params, tiny_cfg, tok, PROMPTS, N_GEN)

    gen = DecodeGenerator(
        cfg, tokenizer=FakeTokenizer(), mp_devices=jax.devices()[:nd]
    )
    got, updated = gen(PROMPTS)
    fake = FakeTokenizer()
    for g, w, toks_w, (_, up_sfx), (_, orig_sfx) in zip(
        got, want_s, want_t, updated, PROMPTS
    ):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
        # Updated suffixes = original + decode of the oracle's greedy tokens.
        for s_i, orig in enumerate(orig_sfx):
            assert up_sfx[s_i] == orig + fake.decode(toks_w[s_i])


def test_decode_mp_cli(tiny_cfg, model, tmp_path):
    """--kv_cache on multiple chips WITHOUT --data_parallel routes through
    the pipeline decode (previously rejected)."""
    import pickle

    from flexible_llm_sharding_tpu.cli import main

    model_dir, params = model
    ppkl, opkl = tmp_path / "p.pkl", tmp_path / "s.pkl"
    with open(ppkl, "wb") as f:
        pickle.dump(PROMPTS, f)
    main(
        [
            "--model_path", model_dir,
            "--prompt_pickle", str(ppkl),
            "--output_file", str(opkl),
            "--num_gen_token", str(N_GEN),
            "--dtype", "float32",
            "--kv_cache", "true",
            "--num_devices", "3",
        ],
        tokenizer=FakeTokenizer(),
    )
    with open(opkl, "rb") as f:
        scores = pickle.load(f)
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=64)
    want_s, _ = _oracle(params, tiny_cfg, tok, PROMPTS, N_GEN)
    for g, w in zip(scores, want_s):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_decode_tensor_parallel_matches_oracle(tiny_cfg, model):
    """--kv_cache + --tensor_parallel: streamed weights Megatron-sharded
    over 2 chips, KV replicated; greedy scores must equal the single-device
    decode (which is itself oracle-pinned above)."""
    import dataclasses

    from flexible_llm_sharding_tpu.runtime.orchestration import run_decode

    model_dir, params = model
    cfg = FrameworkConfig(
        model_path=model_dir,
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
        num_gen_token=N_GEN,
        tensor_parallel=2,
    )
    scores_tp, updated_tp, _ = run_decode(
        cfg, list(PROMPTS), tokenizer=FakeTokenizer()
    )
    single = DecodeGenerator(
        dataclasses.replace(cfg, tensor_parallel=1), tokenizer=FakeTokenizer()
    )
    scores_1, updated_1 = single(list(PROMPTS))
    for a, b in zip(scores_1, scores_tp):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-5)
    assert updated_tp == updated_1


# ---------------------------------------------------------------------------
# Weights-resident decode (decode steps with zero weight transfers)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("storage,lnps", [("cpu", 1), ("tpu", 2)])
def test_decode_resident_matches_streamed(tiny_cfg, model, storage, lnps):
    """decode_resident='on' keeps every placed shard on chip after prefill;
    decode steps then walk the retained segments. Same arrays, same jitted
    programs -> scores must equal the re-streaming path bitwise
    (decode_fused='off' pins the per-step loop; the fused scan compiles a
    different program and is covered by its own tests below)."""
    model_dir, _ = model

    def cfg(resident):
        return FrameworkConfig(
            model_path=model_dir,
            layer_num_per_shard=lnps,
            storage_location=storage,
            dtype="float32",
            bucket_multiple=8,
            block_size=2,
            prefetch_depth=0,
            num_gen_token=N_GEN,
            decode_resident=resident,
            decode_fused="off",
        )

    want, _ = DecodeGenerator(cfg("off"), tokenizer=FakeTokenizer())(list(PROMPTS))
    gen = DecodeGenerator(cfg("on"), tokenizer=FakeTokenizer())
    got, _ = gen(list(PROMPTS))
    assert gen.stats["decode_resident"] == 1.0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_decode_resident_dp(tiny_cfg, model):
    """Resident decode composes with DP: the shared broadcast source runs
    ONE round (the prefill) and every rank keeps its shards on chip."""
    from flexible_llm_sharding_tpu.runtime.orchestration import run_decode

    model_dir, _ = model
    prompts = PROMPTS + [("The sky is", (" blue", " green"))]

    def cfg(resident):
        return FrameworkConfig(
            model_path=model_dir,
            layer_num_per_shard=1,
            storage_location="cpu",
            dtype="float32",
            bucket_multiple=8,
            block_size=2,
            prefetch_depth=1,
            num_gen_token=N_GEN,
            data_parallel=True,
            decode_resident=resident,
            decode_fused="off",
        )

    want, want_up, want_tok = run_decode(
        cfg("off"), prompts, tokenizer=FakeTokenizer(), devices=jax.devices()[:3]
    )
    got, got_up, got_tok = run_decode(
        cfg("on"), prompts, tokenizer=FakeTokenizer(), devices=jax.devices()[:3]
    )
    assert got_tok == want_tok > 0
    assert got_up == want_up
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_decode_resident_mp_pipeline(tiny_cfg, model):
    """Resident decode composes with the interleaved MP pipeline: each
    stage's shards stay on that stage's chip across steps."""
    model_dir, params = model
    cfg = FrameworkConfig(
        model_path=model_dir,
        layer_num_per_shard=1,
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=1,
        num_gen_token=N_GEN,
        decode_resident="on",
    )
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    want_s, _ = _oracle(params, tiny_cfg, tok, PROMPTS, N_GEN)
    gen = DecodeGenerator(
        cfg, tokenizer=FakeTokenizer(), mp_devices=jax.devices()[:3]
    )
    got, _ = gen(PROMPTS)
    for g, w in zip(got, want_s):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_decode_resident_auto_gate(tiny_cfg):
    """The auto gate sizes materialised weights against known HBM: a tiny
    model fits a v5e budget; a 70B-class config does not; unknown device
    kinds (the CPU backend) resolve to off."""
    from flexible_llm_sharding_tpu.config import LlamaConfig

    class FakeDev:  # the v5e as chip_smoke.py saw it (PR 21)
        platform = "tpu"
        device_kind = "TPU v5 lite"

        def memory_stats(self):
            return {"bytes_limit": 16909336064, "bytes_in_use": 0}

    fw = FrameworkConfig(dtype="bfloat16")
    assert fw.decode_resident_enabled(tiny_cfg, 1, FakeDev())
    big = LlamaConfig(
        vocab_size=32000, hidden_size=8192, intermediate_size=28672,
        num_hidden_layers=80, num_attention_heads=64, num_key_value_heads=8,
        max_position_embeddings=4096,
    )
    assert not fw.decode_resident_enabled(big, 1, FakeDev())
    # ...but 70B bf16 split 8-ways under tp is ~17.6 GB/chip - still off at
    # 45% of 16 GB; split over enough chips it turns on.
    assert fw.decode_resident_enabled(big, 32, FakeDev())
    assert not fw.decode_resident_enabled(tiny_cfg, 1, jax.devices()[0])
    assert FrameworkConfig(decode_resident="on").decode_resident_enabled(
        big, 1, FakeDev()
    )
    assert not FrameworkConfig(decode_resident="off").decode_resident_enabled(
        tiny_cfg, 1, FakeDev()
    )


# ---------------------------------------------------------------------------
# Fused resident decode (all steps as one jitted scan per block)
# ---------------------------------------------------------------------------

def test_decode_fused_matches_loop_and_oracle(tiny_cfg, model):
    """decode_fused + resident + greedy runs every decode step inside ONE
    jitted scan per block with an on-device argmax. Same math, different XLA
    fusion boundaries -> allclose scores and identical greedy strings vs the
    per-step loop, and oracle-level agreement with the monolithic forward."""
    model_dir, params = model

    def cfg(fused):
        return FrameworkConfig(
            model_path=model_dir,
            layer_num_per_shard=2,
            storage_location="cpu",
            dtype="float32",
            bucket_multiple=8,
            block_size=2,
            prefetch_depth=0,
            num_gen_token=N_GEN,
            decode_resident="on",
            decode_fused=fused,
        )

    want, want_up = DecodeGenerator(cfg("off"), tokenizer=FakeTokenizer())(
        list(PROMPTS)
    )
    gen = DecodeGenerator(cfg("on"), tokenizer=FakeTokenizer())
    got, got_up = gen(list(PROMPTS))
    assert gen.stats["decode_fused"] == 1.0
    assert got_up == want_up
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    oracle_s, _ = _oracle(params, tiny_cfg, tok, PROMPTS, N_GEN)
    for g, w in zip(got, oracle_s):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # heaviest in its file; tier-1 keeps sibling coverage
def test_decode_fused_multi_segment(tmp_path_factory):
    """A mixed dense/MoE stack (llama4-style) yields SEVERAL decoder
    segments per shard, each with its own KV pytree; the fused program
    chains their layer scans inside the one step body."""
    from flexible_llm_sharding_tpu.config import LlamaConfig

    cfg = LlamaConfig(
        model_type="llama4_text",
        vocab_size=288,
        hidden_size=64,
        intermediate_size=32,
        intermediate_size_mlp=48,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=2,
        explicit_head_dim=16,
        max_position_embeddings=512,
        num_local_experts=2,
        num_experts_per_tok=1,
        moe_layer_pattern=(False, True, True),
        layer_rope=(True, True, False),
        rope_interleaved=True,
        qk_l2_norm=True,
        attn_temperature_tuning=True,
        attn_floor_scale=4.0,
        attn_scale_coef=0.1,
        tie_word_embeddings=False,
    )
    params = llama.init_mixed_params(jax.random.PRNGKey(7), cfg)
    d = tmp_path_factory.mktemp("fused_l4_model")
    save_params(jax.tree.map(np.asarray, params), str(d), cfg)

    def fw(fused):
        return FrameworkConfig(
            model_path=str(d),
            layer_num_per_shard=3,
            storage_location="cpu",
            dtype="float32",
            bucket_multiple=8,
            block_size=2,
            prefetch_depth=0,
            num_gen_token=N_GEN,
            decode_resident="on",
            decode_fused=fused,
        )

    want, want_up = DecodeGenerator(fw("off"), tokenizer=FakeTokenizer())(
        list(PROMPTS)
    )
    gen = DecodeGenerator(fw("auto"), tokenizer=FakeTokenizer())
    got, got_up = gen(list(PROMPTS))
    assert gen.stats["decode_fused"] == 1.0
    assert got_up == want_up
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_decode_fused_on_requires_preconditions(tiny_cfg, model):
    """decode_fused='on' is loud about why fusion can't engage: sampling,
    non-resident streaming, and the MP pipeline all keep the per-step loop."""
    model_dir, _ = model
    base = dict(
        model_path=model_dir,
        layer_num_per_shard=1,
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
        num_gen_token=N_GEN,
    )
    cfg = FrameworkConfig(
        **base, decode_resident="on", decode_fused="on", temperature=0.7
    )
    with pytest.raises(ValueError, match="decode_fused"):
        DecodeGenerator(cfg, tokenizer=FakeTokenizer())(list(PROMPTS))
    cfg = FrameworkConfig(**base, decode_resident="off", decode_fused="on")
    with pytest.raises(ValueError, match="decode_fused"):
        DecodeGenerator(cfg, tokenizer=FakeTokenizer())(list(PROMPTS))
    cfg = FrameworkConfig(**base, decode_resident="on", decode_fused="on")
    with pytest.raises(ValueError, match="decode_fused"):
        DecodeGenerator(
            cfg, tokenizer=FakeTokenizer(), mp_devices=jax.devices()[:3]
        )(list(PROMPTS))


def test_decode_kv_on_device_gate(tiny_cfg, model):
    """KV follows the weights onto the chip only where the HBM budget is
    known: weights + every block's KV within 80%. The CPU backend (unknown
    kind) stays host-parked."""
    from flexible_llm_sharding_tpu.runtime.tokenization import make_blocks

    model_dir, _ = model
    cfg = FrameworkConfig(
        model_path=model_dir,
        num_gen_token=N_GEN,
        bucket_multiple=8,
        block_size=2,
        dtype="float32",
        decode_resident="on",
    )
    gen = DecodeGenerator(cfg, tokenizer=FakeTokenizer())
    toks = [gen.tokenizer(p, s) for p, s in PROMPTS]
    blocks = make_blocks(toks, 2)
    slots = N_GEN - 1
    assert not gen._kv_fits_on_chip(toks, blocks, slots)  # unknown HBM

    class FakeDev:  # the v5e as chip_smoke.py saw it (PR 21)
        platform = "tpu"
        device_kind = "TPU v5 lite"

        def memory_stats(self):
            return {"bytes_limit": 16909336064, "bytes_in_use": 0}

    gen._probe_dev = FakeDev()
    assert gen._kv_fits_on_chip(toks, blocks, slots)
    # Fused budget: fits for the tiny workload on a known chip, refuses when
    # the generated-KV + dists footprint outgrows the HBM, and is always ok
    # on the CPU backend (device memory IS host RAM).
    assert gen._fused_budget_ok(toks, blocks, N_GEN, slots, kv_on_device=True)
    assert not gen._fused_budget_ok(
        toks, blocks, 10**7, 10**7, kv_on_device=True
    )
    gen._probe_dev = None
    assert gen._fused_budget_ok(
        toks, blocks, 10**7, 10**7, kv_on_device=False
    )


# ---------------------------------------------------------------------------
# Speculative decode (prompt-lookup drafts verified per streamed pass)
# ---------------------------------------------------------------------------

# Repetition-heavy prompts: prompt-lookup drafting's home turf (the
# reference's continuation-scoring workloads echo prompt phrases constantly).
SPEC_PROMPTS = [
    (
        "the cat sat on the mat the cat sat on the mat",
        (" the cat sat", " on the mat"),
    ),
    ("alpha beta gamma alpha beta gamma alpha", (" beta gamma alpha", " delta")),
]


def _spec_cfg(model_dir, k, n_gen=6, resident="off", **kw):
    return FrameworkConfig(
        model_path=model_dir,
        layer_num_per_shard=1,
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
        num_gen_token=n_gen,
        speculative_k=k,
        decode_resident=resident,
        decode_fused="off",
        **kw,
    )


@pytest.mark.parametrize("resident", ["off", "on"])
def test_decode_speculative_matches_plain(tiny_cfg, model, resident):
    """Speculative verification is greedy-exact: tokens, strings and
    per-step distributions equal plain KV decode (streamed or resident),
    while the pass count drops below n_gen-1 on accepting prompts."""
    model_dir, _ = model
    want, want_up = DecodeGenerator(
        _spec_cfg(model_dir, 0), tokenizer=FakeTokenizer()
    )(list(SPEC_PROMPTS))
    gen = DecodeGenerator(
        _spec_cfg(model_dir, 4, resident=resident), tokenizer=FakeTokenizer()
    )
    got, got_up = gen(list(SPEC_PROMPTS))
    assert gen.stats["decode_speculative"] == 1.0
    assert gen.stats["spec_passes"] < 5  # n_gen-1 sequential steps beaten
    assert gen.stats["spec_accepted"] > 0
    assert got_up == want_up
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_decode_speculative_hostile_prompts(tiny_cfg, model):
    """Zero-repetition prompts reject (nearly) every draft — the mode must
    still be exact, paying at worst one pass per token like plain decode."""
    model_dir, _ = model
    prompts = list(PROMPTS)  # the no-repetition standard set
    want, want_up = DecodeGenerator(
        _spec_cfg(model_dir, 0, n_gen=N_GEN), tokenizer=FakeTokenizer()
    )(prompts)
    gen = DecodeGenerator(
        _spec_cfg(model_dir, 3, n_gen=N_GEN), tokenizer=FakeTokenizer()
    )
    got, got_up = gen(prompts)
    assert gen.stats["spec_passes"] <= N_GEN - 1
    assert got_up == want_up
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_decode_speculative_k_exceeds_gen(tiny_cfg, model):
    """spec_k larger than the remaining budget: emissions truncate at n_gen
    and the gen-KV capacity covers the overshooting writes."""
    model_dir, _ = model
    want, want_up = DecodeGenerator(
        _spec_cfg(model_dir, 0, n_gen=2), tokenizer=FakeTokenizer()
    )(list(SPEC_PROMPTS))
    gen = DecodeGenerator(
        _spec_cfg(model_dir, 8, n_gen=2), tokenizer=FakeTokenizer()
    )
    got, got_up = gen(list(SPEC_PROMPTS))
    assert gen.stats["spec_passes"] == 1.0
    assert got_up == want_up
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_decode_speculative_mp_pipeline(tiny_cfg, model):
    """Speculative passes ride the interleaved MP pipeline the same way the
    per-step loop does (per-stage KV, activation hops)."""
    model_dir, _ = model
    want, want_up = DecodeGenerator(
        _spec_cfg(model_dir, 0), tokenizer=FakeTokenizer()
    )(list(SPEC_PROMPTS))
    gen = DecodeGenerator(
        _spec_cfg(model_dir, 4),
        tokenizer=FakeTokenizer(),
        mp_devices=jax.devices()[:3],
    )
    got, got_up = gen(list(SPEC_PROMPTS))
    assert gen.stats["decode_speculative"] == 1.0
    assert got_up == want_up
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_decode_speculative_guards(tiny_cfg, model):
    """Loud rejects: sampling, DP broadcast source, bad k."""
    model_dir, _ = model
    with pytest.raises(ValueError, match="speculative_k requires greedy"):
        FrameworkConfig(speculative_k=4, temperature=0.7)
    with pytest.raises(ValueError, match="speculative_k must be"):
        FrameworkConfig(speculative_k=-1)
    with pytest.raises(ValueError, match="data_parallel"):
        DecodeGenerator(
            _spec_cfg(model_dir, 4),
            tokenizer=FakeTokenizer(),
            weight_source_factory=lambda: iter(()),
            resident=False,
        )


def test_propose_draft():
    """Prompt-lookup drafting: last-match continuation, exact-k padding."""
    from flexible_llm_sharding_tpu.runtime.decode import propose_draft

    ids = np.array([5, 6, 7, 8, 5, 6, 7, 9, 5, 6])
    # Final bigram (5, 6): last earlier occurrence at index 4 -> continues
    # with 7, 9, 5.
    assert propose_draft(ids, 3).tolist() == [7, 9, 5]
    # Continuation shorter than k: pads by repeating the last token.
    assert propose_draft(np.array([1, 2, 3, 1, 2]), 4).tolist() == [3, 1, 2, 2]
    # No match at all: falls back to repeating the final token.
    assert propose_draft(np.array([1, 2, 3, 4]), 2).tolist() == [4, 4]
    # Degenerate single-token context.
    assert propose_draft(np.array([7]), 2).tolist() == [7, 7]


def test_decode_speculative_cli(tiny_cfg, model, tmp_path):
    """--speculative_k flows through the CLI into the decode path and the
    output pickle keeps the exact plain-decode contract."""
    import pickle

    from flexible_llm_sharding_tpu.cli import main

    model_dir, _ = model
    ppkl, opkl = tmp_path / "p.pkl", tmp_path / "s.pkl"
    with open(ppkl, "wb") as f:
        pickle.dump(SPEC_PROMPTS[:1], f)
    main(
        [
            "--model_path", model_dir,
            "--prompt_pickle", str(ppkl),
            "--output_file", str(opkl),
            "--num_gen_token", "4",
            "--dtype", "float32",
            "--kv_cache", "true",
            "--speculative_k", "3",
            "--decode_resident", "off",
            "--num_devices", "1",
        ],
        tokenizer=FakeTokenizer(),
    )
    with open(opkl, "rb") as f:
        scores = pickle.load(f)
    assert scores[0].shape == (2, 4, tiny_cfg.vocab_size)
