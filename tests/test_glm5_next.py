"""GLM-5.3-Flash (``glm5_next_text``) against its plain float32 reference
(``benchmark/families/glm5_next_text/reference.py``, which shares no code with
the package and walks the KDA layers one token at a time), on seeded random
weights at a small size: delta-rule linear attention behind a short
convolution, its state AND the convolution's last inputs handed from a prefix
to its suffixes at a dynamic length inside a bucket; latent attention with no
rotary part; the four-stream residual with Sinkhorn-normalised mixes; the
clamped SwiGLU; a held share of the experts; the chunked XLA op and the
Pallas kernel against the recurrence; what the model is refused."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import traffic as tr
from benchmark.families.glm5_next_text import reference, weights
from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.ops import kda_attention as ka
from flexible_llm_sharding_tpu.runtime import executor, tokenization
from flexible_llm_sharding_tpu.runtime.orchestration import run_prompts
from flexible_llm_sharding_tpu.utils import checkpoint as ckpt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 11


def published() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", "glm-5.3-flash.json")) as f:
        m = json.load(f)
    m.pop("rehearsal")
    return m


def small_model(**over) -> dict:
    """The benchmark's configuration at its rehearsal widths: 8 layers
    [3 KDA, latent, 3 KDA, latent], the first with a dense MLP, the others
    with 4 of 16 experts held (1 share of 4)."""
    with open(os.path.join(ROOT, "benchmark", "configs", "glm-5.3-flash.json")) as f:
        m = json.load(f)
    m.update(m.pop("rehearsal"))
    m.update(over)
    return m


def program_cfg(model: dict) -> LlamaConfig:
    return LlamaConfig.from_hf_config(weights.hf_config(model))


def layer_params(model: dict, i: int) -> dict:
    """Layer ``i``'s weights as the program holds them (float32 of the
    bfloat16 files)."""
    flat = weights.layer_tensors(model, SEED, f"model.layers.{i}")
    return weights.unflatten({k: jnp.asarray(a, jnp.float32) for k, a in flat.items()})


# --- config ---------------------------------------------------------------

def test_config_parse_published():
    m = published()
    cfg = program_cfg(m)
    assert cfg.num_hidden_layers == 12 and cfg.hidden_size == 4096
    assert cfg.layer_linear == (True, True, True, False) * 3 and cfg.linear_kind == "kda"
    assert cfg.linear_attn_shape == (64, 64, 128, 128) and cfg.linear_conv_size == 4
    assert cfg.linear_gate_lower_bound == -5.0
    assert cfg.linear_output_norm and cfg.linear_output_gate
    assert cfg.attn_shape() == (64, 64, 256, 256) and cfg.qk_rope_head_dim == 0
    assert cfg.kv_lora_rank == 512 and cfg.q_lora_rank == 1536
    assert cfg.attn_scale == pytest.approx(256 ** -0.5)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps) == (4, 20, 1e-6)
    assert cfg.swiglu_limit == 10.0 and cfg.sparse_attn_from == 2049
    assert cfg.moe_layer_pattern == (False,) * 3 + (True,) * 9
    assert cfg.num_local_experts == 288 and cfg.held_experts == range(0, 36)
    assert (cfg.intermediate_size, cfg.intermediate_size_mlp) == (2048, 12288)
    assert cfg.num_experts_per_tok == 8 and cfg.moe_routed_scaling_factor == 2.5
    assert cfg.moe_n_group == 1 and cfg.n_shared_experts == 1 and cfg.moe_norm_topk_prob
    assert cfg.vocab_size == 19360 and not cfg.tie_word_embeddings
    assert cfg.layer_rope is None and llama.layer_log_decay(cfg) is None
    # every other family keeps the plain residual, the unclamped SwiGLU
    plain = LlamaConfig()
    assert (plain.hc_mult, plain.swiglu_limit, plain.linear_kind) == (1, None, "lightning")


def test_the_file_keeps_the_catalog_row_but_for_the_three_cuts():
    """``reduced`` = depth, experts held, vocabulary slice; every other key of
    the catalog's ``config`` is in the file as published (the per-layer lists
    whole: 45 entries, read up to the depth)."""
    m = published()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "glm-5.3-flash")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == m["source"]
    assert m["published"] == {"num_hidden_layers": 45, "n_routed_experts": 288,
                              "vocab_size": 154880}
    assert (m["num_hidden_layers"], m["n_routed_experts"], m["vocab_size"]) == (12, 36, 19360)
    assert m["n_routed_experts"] * m["ep_size"] == 288 and m["vocab_size"] * 8 == 154880
    assert len(m["layer_types"]) == len(m["mlp_layer_types"]) == len(m["indexer_types"]) == 45
    assert m["index_topk"] == 2048 and m["index_kpool"] == 4 and m["mhc"] is True
    assert m["deployment"]["chips_per_layer"] == 8
    for key in ("indexer", "mhc", "kda", "latent", "mlp", "scope", "tensor_names"):
        assert key in m["assumed"]
    kinds = [weights.layer_kind(m, i) for i in range(12)]
    assert kinds == ["kda_dense"] * 3 + ["latent_moe"] + (["kda_moe"] * 3 + ["latent_moe"]) * 2


def test_config_native_round_trip_and_errors():
    cfg = program_cfg(small_model())
    d = {**dataclasses.asdict(cfg), "fls_native": True}
    assert LlamaConfig.from_hf_config(json.loads(json.dumps(d))) == cfg
    hf = weights.hf_config(small_model())
    with pytest.raises(ValueError, match="layer_types"):
        LlamaConfig.from_hf_config({**hf, "layer_types": ["linear_attention"]})
    with pytest.raises(ValueError, match="mamba"):
        LlamaConfig.from_hf_config({**hf, "layer_types": ["mamba"] * 8})
    with pytest.raises(NotImplementedError, match="rotary part"):
        LlamaConfig.from_hf_config({**hf, "qk_rope_head_dim": 64})
    with pytest.raises(NotImplementedError, match="gate_lower_bound"):
        LlamaConfig.from_hf_config({**hf, "linear_attn_config": {
            **hf["linear_attn_config"], "gate_lower_bound": -8}})
    with pytest.raises(ValueError, match="ep_size"):
        LlamaConfig.from_hf_config({**hf, "ep_size": 5})
    assert LlamaConfig.from_hf_config({**hf, "index_topk": 512}).sparse_attn_from == 513
    with pytest.raises(NotImplementedError, match="glm5_next_text"):
        LlamaConfig.from_hf_config({**hf, "model_type": "glm9"})


REFUSED = ["KV-cache decoding", "the serve engine", "the pipeline runner",
           "the long-context scorer", "tensor parallelism"]


@pytest.mark.parametrize("path", REFUSED)
def test_paths_that_keep_kv_by_layer_refuse_the_model(path):
    cfg = program_cfg(small_model())
    with pytest.raises(NotImplementedError, match=path):
        cfg.require_one_attention_shape(path)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        cfg.require_one_attention_shape(path, layer_fn=True)
    cfg.require_single_visit(path)  # visited once: that rule has nothing against it


def test_layer_functions_of_a_kv_cache_refuse_the_model():
    model = small_model()
    cfg = program_cfg(model)
    wide = cfg.hc_mult * cfg.hidden_size
    ph, sh = jnp.zeros((64, wide)), jnp.zeros((1, 8, wide))
    for i in (0, 3):  # a KDA layer and a latent one alike
        lyr = layer_params(model, i)
        with pytest.raises(NotImplementedError, match="return_kv"):
            llama.prefix_suffix_layer(lyr, cfg, ph, sh, jnp.int32(9), return_kv=True)
        with pytest.raises(NotImplementedError, match="suffix_only_layer"):
            llama.suffix_only_layer(lyr, cfg, None, None, sh, jnp.int32(9))
        with pytest.raises(NotImplementedError, match="decode_step_layer"):
            llama.decode_step_layer(lyr, cfg, sh, {}, jnp.int32(9), None, None)


# --- HF names -------------------------------------------------------------

HF_OF = {
    "input_layernorm.scale": "input_layernorm.weight",
    "post_attention_layernorm.scale": "post_attention_layernorm.weight",
    "hc_attn.phi": "attn_hc.fn.weight", "hc_attn.b": "attn_hc.base", "hc_attn.a": "attn_hc.scale",
    "hc_mlp.phi": "mlp_hc.fn.weight", "hc_mlp.b": "mlp_hc.base", "hc_mlp.a": "mlp_hc.scale",
    "attn.wq": "self_attn.q_proj.weight", "attn.wk": "self_attn.k_proj.weight",
    "attn.wv": "self_attn.v_proj.weight", "attn.wo": "self_attn.o_proj.weight",
    "attn.conv_q": "self_attn.q_conv1d.weight", "attn.conv_k": "self_attn.k_conv1d.weight",
    "attn.conv_v": "self_attn.v_conv1d.weight",
    "attn.f_a": "self_attn.f_a_proj.weight", "attn.f_b": "self_attn.f_b_proj.weight",
    "attn.wg_a": "self_attn.g_a_proj.weight", "attn.wg_b": "self_attn.g_b_proj.weight",
    "attn.wb": "self_attn.b_proj.weight", "attn.A_log": "self_attn.A_log",
    "attn.dt_bias": "self_attn.dt_bias", "attn.o_norm": "self_attn.o_norm.weight",
    "attn.q_a": "self_attn.q_a_proj.weight", "attn.q_a_norm": "self_attn.q_a_layernorm.weight",
    "attn.q_b": "self_attn.q_b_proj.weight", "attn.kv_a": "self_attn.kv_a_proj_with_mqa.weight",
    "attn.kv_a_norm": "self_attn.kv_a_layernorm.weight", "attn.kv_b": "self_attn.kv_b_proj.weight",
    "mlp.gate": "mlp.gate_proj.weight", "mlp.up": "mlp.up_proj.weight",
    "mlp.down": "mlp.down_proj.weight", "mlp.router": "mlp.gate.weight",
    "mlp.correction_bias": "mlp.gate.e_score_correction_bias",
    "mlp.shared_gate": "mlp.shared_experts.gate_proj.weight",
    "mlp.shared_up": "mlp.shared_experts.up_proj.weight",
    "mlp.shared_down": "mlp.shared_experts.down_proj.weight",
}


@pytest.mark.parametrize("layer", [0, 1, 3])
def test_hf_names_convert_to_native(layer):
    """A KDA layer with a dense MLP, one with experts, a latent one with
    experts, under the names the configuration file assumes; the experts
    stacked from per-expert tensors and cut to the held share, the
    convolutions' taps from ``[C, 1, K]``, the indexer's tensors dropped."""
    model = small_model()
    name = f"model.layers.{layer}"
    rng = np.random.default_rng(layer)
    native = {k: rng.standard_normal(shape).astype(np.float32)
              for k, shape, _ in weights.tensor_specs(model, name)}
    sd = {}
    for k, a in native.items():
        if k in ("mlp.gate", "mlp.up", "mlp.down") and a.ndim == 3:
            sub = {"mlp.gate": "gate_proj", "mlp.up": "up_proj", "mlp.down": "down_proj"}[k]
            for e in range(weights.router_width(model)):  # every expert is in the checkpoint
                held = weights.held_experts(model)
                w = a[e - held.start] if e in held else np.zeros_like(a[0])
                sd[f"{name}.mlp.experts.{e}.{sub}.weight"] = w.T
        elif k.startswith("attn.conv_"):
            sd[f"{name}.{HF_OF[k]}"] = a.T[:, None, :]
        else:
            sd[f"{name}.{HF_OF[k]}"] = a.T if a.ndim == 2 else a
    if layer == 3:
        sd[f"{name}.self_attn.indexer.wk.weight"] = np.zeros((4, 4), np.float32)
    got = ckpt.hf_layer_to_native(
        name, sd, weights.held_experts(model) if weights.is_moe_layer(model, layer) else None)
    assert sorted(got) == sorted(native)
    for k in native:
        np.testing.assert_array_equal(got[k], native[k])
    with pytest.raises(ValueError, match="no native-layout slot"):
        ckpt.hf_layer_to_native(name, {**sd, f"{name}.self_attn.extra": np.zeros(3)},
                                weights.held_experts(model) if "mlp.router" in native else None)


# --- the whole model through run_prompts -----------------------------------

@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    model = small_model()
    d = str(tmp_path_factory.mktemp("glm") / "model")
    weights.write_model(model, SEED, d)
    return model, d


# Prefix lengths WITH the BOS, all inside one 64-row bucket: a prefix that is
# the BOS alone (the convolution's tail is all zeros), one of 2 (one zero row
# still in the tail), one well inside, one within 3 rows of the bucket's edge
# (the tail is the bucket's last rows but one), one AT the edge.
PREFIX_LENS = [1, 2, 37, 62, 64]


def _prompts(model, lens=PREFIX_LENS, seed=3):
    rng = np.random.default_rng(seed)
    text = lambda n: " ".join(f"t{i}" for i in rng.integers(3, int(model["vocab_size"]), n))  # noqa: E731
    return [(text(n - 1), (text(5), text(9), text(1))) for n in lens]


def _reference_logp(model, prompts, tok, **kw):
    seqs = []
    for prefix, suffixes in prompts:
        pids = tok(prefix)["input_ids"]
        sids = [x[1:] for x in tok(list(suffixes))["input_ids"]]
        seqs.append(reference.scoring_sequence(pids, sids, 128))
    return [jax.nn.log_softmax(jnp.asarray(l), -1) for l in reference.forward_rows(
        model, SEED, seqs, **kw)]


@pytest.fixture(scope="module")
def want(model_dir):
    model, _ = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    return _reference_logp(model, _prompts(model), tok)


@pytest.mark.parametrize("layers_per_shard,use_pallas", [(1, False), (4, False), (1, True)])
def test_run_prompts_matches_reference(model_dir, want, layers_per_shard, use_pallas):
    """float32 compute over the bfloat16 files against the float32 reference
    over the same weights, the five prefix lengths in ONE 64-row bucket (one
    block of five prompts): what is left is the order of float32 sums (the
    chunked solve against the token-by-token recurrence, the flash kernels'
    online softmax), so 5e-5 in log-probability holds with room (measured
    4e-6); the controls below move it by 0.1 and more. Four layers a shard
    puts both mixers and both MLP kinds in one shard: the builder breaks the
    run on structure, [dense KDA], [2 expert KDA], [latent] ..."""
    model, d = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)
    cfg = FrameworkConfig(
        model_path=d, dtype="float32", layer_num_per_shard=layers_per_shard,
        use_pallas=use_pallas, storage_location="cpu", host_cache_gb=0,
    )
    got = run_prompts(cfg, prompts, tokenizer=tok, devices=jax.devices()[:1])
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.log(np.asarray(g)[:, 0, :]), w, atol=5e-5)
    rec = executor.process_sweep_log()[-1]
    assert (rec["linear_layers"], rec["kda_layers"], rec["softmax_layers"]) == (6, 6, 2)
    assert (rec["experts_held"], rec["router_width"], rec["hc_streams"]) == (4, 16, 4)
    # 6 KDA layers x every row computed: five prefixes in one 64-row bucket
    # and their 3 suffixes each in a bucket of 4 x 64 rows, padding included
    rows = 6 * (5 * 64 + 5 * 4 * 64)
    kernel, xla = (rows, 0) if use_pallas else (0, rows)
    assert (rec["kda_rows_kernel"], rec["kda_rows_xla"]) == (kernel, xla)
    assert rec["linear_rows_kernel"] == rec["linear_rows_xla"] == 0
    assert rec["kda_state_bytes"] == 5 * 2 * 128 * 128 * 4  # five prompts a block
    assert rec["conv_tail_bytes"] == 3 * 3 * 2 * 128 * 2
    # 7 expert layers over the same rows, each row once (not once a stream)
    assert rec["expert_rows_grouped"] == 7 * (5 * 64 + 5 * 4 * 64)
    assert rec["expert_rows_dense"] == 0 and 0 < rec["held_expert_hits"] < rec["routed_assignments"]


PARTS = ["mhc", "sinkhorn", "decay", "beta", "conv", "clamp", "nope"]


@pytest.mark.parametrize("part", PARTS)
def test_reference_controls_differ(model_dir, want, part):
    """Each part of the mathematics moves the answers by far more than the
    tolerance above: leaving one out of the program could not pass."""
    model, _ = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    cut = _reference_logp(model, _prompts(model), tok, leave_out=(part,))
    assert max(float(jnp.abs(a - b).max()) for a, b in zip(want, cut)) > 0.1


def test_forward_full_matches_reference_on_a_plain_sequence(model_dir):
    """The monolithic forward (one causal sequence, no prefix/suffix split,
    the compute-all expert body) against the same reference."""
    model, _ = model_dir
    cfg = program_cfg(model)
    names = weights.layer_names(model)
    load = lambda n: weights.unflatten(  # noqa: E731
        {k: jnp.asarray(a, jnp.float32) for k, a in weights.layer_tensors(model, SEED, n).items()})
    params = {"embed": load(names[0]), "layers": [load(n) for n in names[1:-2]],
              "norm": load(names[-2]), "lm_head": load(names[-1])}
    ids = np.random.default_rng(5).integers(3, cfg.vocab_size, 40)
    got = llama.forward_full(params, cfg, jnp.asarray(ids)[None])[0]
    seq = reference.causal_sequence(ids, list(range(40)), 40)
    ref = reference.forward_rows(model, SEED, [seq])[0]
    np.testing.assert_allclose(jax.nn.log_softmax(got), jax.nn.log_softmax(ref), atol=5e-5)


def test_2048_tokens_are_admitted_and_2049_refused():
    """Up to ``index_topk`` = 2048 tokens a sparse layer's top-k is every
    key, so the layer is dense; past that the indexer decides, which nothing
    here computes: the prompt is refused, neither truncated nor run dense."""
    cfg = program_cfg(published())

    def prompt(prefix_len, last):
        return tokenization.TokenizedPrompt(
            prefix_ids=np.zeros(2048, np.int32), suffix_ids=np.zeros((1, 64), np.int32),
            prefix_len=prefix_len, suffix_eos=np.asarray([last]), num_suffixes=1)

    tokenization.check_dense_len(cfg, [prompt(1984, 63)])  # 1984 + 64 = 2048
    with pytest.raises(NotImplementedError, match="prompt 7: 2049 tokens"):
        tokenization.check_dense_len(cfg, [prompt(1985, 63)], labels=[7])
    seq = reference.causal_sequence(np.zeros(2049, np.int32), [0], 2049)
    with pytest.raises(AssertionError, match="index_topk"):
        reference.forward_rows(published(), SEED, [seq])


# --- the four-stream residual ---------------------------------------------------

def test_sinkhorn_is_doubly_stochastic_and_20_rounds_are_not_one():
    """At logits of spread 0.7 the published 20 rounds reach a doubly
    stochastic matrix to 1e-5, rows and columns; at the benchmark's own spread
    (about 1.6: ``hc_spread`` in the configuration file) the columns, which
    the last half-round normalises, are exact and the rows are where 20
    rounds leave them (within 3e-2 of 1): the model's H_res is what 20
    rounds give, in the program and in the reference alike."""
    cfg = program_cfg(small_model())
    noise = jax.random.normal(jax.random.PRNGKey(0), (4, 4, 300))
    m = jnp.exp(0.7 * noise)
    out = llama.sinkhorn(m, cfg.hc_sinkhorn_iters, cfg.hc_eps)
    np.testing.assert_allclose(out.sum(axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, atol=1e-5)
    once = llama.sinkhorn(m, 1, cfg.hc_eps)
    assert float(jnp.abs(once.sum(axis=1) - 1.0).max()) > 0.05  # rows are off after one
    m = jnp.exp(1.6 * noise)
    out = llama.sinkhorn(m, cfg.hc_sinkhorn_iters, cfg.hc_eps)
    np.testing.assert_allclose(out.sum(axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, atol=3e-2)
    ref = reference.sinkhorn(jnp.moveaxis(m, -1, 0), 20, cfg.hc_eps)
    np.testing.assert_allclose(jnp.moveaxis(out, -1, 0), ref, atol=1e-6)


def test_hc_mixes_and_the_sublayer_wrap_match_the_reference():
    """``_hc_pre`` / ``_hc_post`` around the dense MLP of layer 0 against the
    reference's ``_sublayer``, on rows that are no embedding (four different
    streams); the H_res the program used is doubly stochastic."""
    model = small_model()
    cfg, p = program_cfg(model), layer_params(model, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 4, cfg.hidden_size))
    flat = x.reshape(50, -1)
    _, (h_post, h_res) = llama._hc_pre(p["hc_mlp"], cfg, flat)
    np.testing.assert_allclose(h_res.sum(axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(h_res.sum(axis=1), 1.0, atol=3e-2)
    assert float(jnp.abs(h_res - 0.25).max()) > 0.2  # no uniform mix: the spread shows
    _, want_post, want_res = reference.hc_mixes(model, p["hc_mlp"], x)
    np.testing.assert_allclose(h_post.T, want_post, atol=1e-5)
    np.testing.assert_allclose(jnp.moveaxis(h_res, -1, 0), want_res, atol=1e-5)
    got = llama._residual_mlp(p, cfg, flat)
    mlp = lambda h: reference.swiglu(model, h, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"])  # noqa: E731
    ref = reference._sublayer(model, p, x, "hc_mlp", "post_attention_layernorm", mlp, None, ())
    np.testing.assert_allclose(got.reshape(50, 4, -1), ref, atol=2e-4, rtol=1e-5)
    # embedding: every stream starts as e; the final norm reads the streams' sum
    e = jnp.ones((3, cfg.hidden_size))
    tiled = jnp.tile(e, 4)
    assert tiled.shape == (3, 4 * cfg.hidden_size)
    out = llama.final_norm({"scale": jnp.ones((cfg.hidden_size,))}, cfg, flat)
    np.testing.assert_allclose(
        out, reference.base.rms_norm(x.sum(1), jnp.ones((cfg.hidden_size,)), cfg.rms_norm_eps),
        atol=1e-5)


# --- the clamp ----------------------------------------------------------------

def test_swiglu_clamp_driven_past_its_limit():
    """gate and up pre-activations far past +-10: the clamped product is
    ``silu(min(g, 10)) * clip(u, -10, 10)`` and differs from the unclamped one;
    a negative gate is not clamped from below."""
    cfg = dataclasses.replace(LlamaConfig(hidden_size=8, intermediate_size=4), swiglu_limit=10.0)
    eye = jnp.eye(8)[:, :4]
    mlp = {"gate": 30.0 * eye, "up": 30.0 * eye, "down": jnp.eye(8)[:4]}
    x = jnp.asarray([[1.0, -1.0, 0.2, 0.5, 0, 0, 0, 0]])
    got = llama._mlp(mlp, x, cfg)[0, :4]
    g = u = 30.0 * x[0, :4]
    want = jax.nn.silu(jnp.minimum(g, 10.0)) * jnp.clip(u, -10.0, 10.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[0], jax.nn.silu(10.0) * 10.0, rtol=1e-6)
    np.testing.assert_allclose(got[1], jax.nn.silu(-30.0) * -10.0, rtol=1e-5)
    free = llama._mlp(mlp, x, dataclasses.replace(cfg, swiglu_limit=None))[0, :4]
    assert float(jnp.abs(free - got).max()) > 100
    # and at the benchmark's own spread some pre-activations do pass the limit
    model = small_model()
    p = layer_params(model, 0)
    h = reference.base.rms_norm(
        jax.random.normal(jax.random.PRNGKey(2), (200, int(model["hidden_size"]))),
        p["post_attention_layernorm"]["scale"], 1e-5)
    assert float((jnp.abs(h @ p["mlp"]["gate"]) > 10).mean()) > 0.01


# --- the share of the experts ---------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's share, tied to the model: the routed parts that
    the 4 shares give (each its own experts' part for the tokens routed to
    them) plus the shared expert ONCE add up to what the uncut layer gives,
    in the program (both bodies) and in the reference."""
    model = small_model()
    d = int(model["hidden_size"])
    whole = {**model, "n_routed_experts": weights.router_width(model), "ep_size": 1}
    spec = {k: s for k, s, _ in weights.tensor_specs(whole, "model.layers.1")}
    ks = jax.random.split(jax.random.PRNGKey(4), len(spec))
    mlp = {k.split(".", 1)[1]: 0.2 * jax.random.normal(kk, spec[k])
           for kk, k in zip(ks, spec) if k.startswith("mlp.")}
    x = jax.random.normal(jax.random.PRNGKey(9), (70, d))
    cfg_whole = program_cfg(whole)
    uncut = llama._mlp(mlp, x, cfg_whole, grouped=True)
    np.testing.assert_allclose(uncut, reference.moe(whole, mlp, x), atol=2e-5)
    shared = reference.swiglu(model, x, mlp["shared_gate"], mlp["shared_up"], mlp["shared_down"])
    total = jnp.zeros_like(x)
    for rank in range(4):
        share = {**model, "ep_rank": rank}
        held = weights.held_experts(share)
        part = {k: (v[held.start:held.stop] if k in ("gate", "up", "down") else v)
                for k, v in mlp.items()}
        cfg = program_cfg(share)
        assert cfg.held_experts == held
        for grouped in (True, False):
            stats = []
            got = llama._deepseek_moe_mlp(part, cfg, x, stats, grouped=grouped)
            np.testing.assert_allclose(got, reference.moe(share, part, x), atol=2e-5)
        total = total + got - shared
        hits, routed = np.asarray(stats[0])
        assert routed == 70 * int(model["num_experts_per_tok"]) and 0 < hits < routed
    np.testing.assert_allclose(total + shared, uncut, atol=5e-5)


# --- the kernel, the XLA op, the recurrence --------------------------------------

def _qkvgb(n, length, h, seed, g_fixed=None, real=None):
    d = 128
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (n, length, h, d)) for i in range(3))
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q, k = unit(q) * d ** -0.5, unit(k)
    if g_fixed is None:
        g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (n, length, h, d)))
    else:
        g = jnp.full((n, length, h, d), g_fixed)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (n, length, h)))
    if real is not None:  # the clock stops at ``real``
        live = jnp.arange(length) < real
        g = jnp.where(live[None, :, None, None], g, 0.0)
        beta = jnp.where(live[None, :, None], beta, 0.0)
    return q, k, v, g, beta, jax.random.normal(ks[5], (h, d, d))


# (sequences, length, heads, every g, an initial state?, real rows)
KERNEL_CASES = {
    "decay-at-the-bound-every-row": (1, 192, 2, -5.0, True, None),
    "decays-spread-over-the-range": (2, 128, 2, None, True, None),
    "almost-no-decay": (1, 128, 2, -1e-3, False, None),
    "a-stopped-clock-inside-the-bucket": (1, 256, 3, None, False, 131),
    "suffixes-from-a-state": (4, 64, 2, None, True, None),
}


def test_kernel_with_keys_nearly_parallel():
    """The kernel inverts a chunk's 16-row diagonal blocks by the finite
    product (I + N)(I + N^2)(I + N^4)(I + N^8), exact for a nilpotent N but
    rounded where N's powers grow: keys at a cosine of 0.99 to each other,
    beta near 1 and almost no decay are the worst a layer can hand it. 5e-4 of
    the largest output (measured 1e-4; bfloat16 rounds at 4e-3); the XLA op's
    triangular solve reads 2e-6 on the same rows."""
    q, k, v, g, beta, s0 = _qkvgb(2, 128, 2, 3, -0.01)
    shared = jax.random.normal(jax.random.PRNGKey(9), (2, 1, 2, 128))
    k = 0.01 * k + shared / jnp.linalg.norm(shared, axis=-1, keepdims=True)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = 0.95 + 0.05 * beta
    want_o, want_s = ka.kda_recurrence(q, k, v, g, beta, s0)
    for op, kw, tol in ((ka.kda_attention_xla, {}, 2e-5), (ka.kda_attention, {"interpret": True}, 5e-4)):
        o, s = op(q, k, v, g, beta, s0, **kw)
        np.testing.assert_allclose(o, want_o, atol=tol * float(jnp.abs(want_o).max()))
        np.testing.assert_allclose(s, want_s, atol=tol * float(jnp.abs(want_s).max()))


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_and_xla_op_against_the_recurrence(case):
    """float32 (the kernel in interpret mode). g = -5 on every row is the
    layer's bound: over 16 rows the factored form holds exp(+-40) about a
    sub-block's middle row, and over a 64-row chunk exp(-320) would be a
    denormal's denormal, which the pairs across sub-blocks never form. 2e-5
    of the largest output or state is float32's rounding through a 64-row
    solve and factors of exp(+-40) (measured 9e-6)."""
    n, length, h, g_fixed, with_state, real = KERNEL_CASES[case]
    q, k, v, g, beta, s0 = _qkvgb(n, length, h, length + h, g_fixed, real)
    s0 = s0 if with_state else None
    want_o, want_s = ka.kda_recurrence(q, k, v, g, beta, s0)
    assert ka.supports(128, 128, length)
    for op, kw in ((ka.kda_attention_xla, {}), (ka.kda_attention, {"interpret": True})):
        o, s = op(q, k, v, g, beta, s0, **kw)
        assert o.shape == q.shape and s.shape == (n, h, 128, 128) and s.dtype == jnp.float32
        assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
        np.testing.assert_allclose(o, want_o, atol=2e-5 * float(jnp.abs(want_o).max()) + 1e-9)
        np.testing.assert_allclose(s, want_s, atol=2e-5 * float(jnp.abs(want_s).max()) + 1e-9)
    if real is not None:  # the clock stopped: the state is the state at ``real``
        _, at_real = ka.kda_recurrence(
            q[:, :real], k[:, :real], v[:, :real], g[:, :real], beta[:, :real], s0)
        np.testing.assert_allclose(want_s, at_real, atol=1e-7)


def test_kernel_under_vmap_and_in_bfloat16():
    """As ``_decoder_block`` calls it: under ``vmap`` over a block's prompts,
    each with its own real length. bfloat16 inputs round the chunk's pair
    products and the state's read-out to bfloat16 (the solve stays float32):
    2% of the largest output."""
    b, length, h = 2, 128, 2
    parts = [_qkvgb(1, length, h, 7 + i, None, real) for i, real in enumerate((128, 77))]
    q, k, v, g, beta, _ = (jnp.stack(x) for x in zip(*parts))
    run = lambda op, *a, **kw: jax.vmap(lambda *xs: op(*xs, **kw))(*a)  # noqa: E731
    o1, s1 = run(ka.kda_attention_xla, q, k, v, g, beta)
    o2, s2 = run(ka.kda_attention, q, k, v, g, beta, interpret=True)
    np.testing.assert_allclose(o2, o1, atol=2e-5 * float(jnp.abs(o1).max()))
    np.testing.assert_allclose(s2, s1, atol=2e-5 * float(jnp.abs(s1).max()))
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    o3, s3 = run(ka.kda_attention, qb, kb, vb, g, beta, interpret=True)
    assert o3.dtype == jnp.bfloat16 and s3.dtype == jnp.float32 and b == o3.shape[0]
    np.testing.assert_allclose(o3.astype(jnp.float32), o1, atol=0.02 * float(jnp.abs(o1).max()))
    np.testing.assert_allclose(s3, s1, atol=0.02 * float(jnp.abs(s1).max()))
    assert not ka.supports(96, 96, 128) and not ka.supports(128, 128, 100)
    assert not ka.supports(128, 256, 128)


def test_causal_conv_carries_its_tail():
    """A sequence convolved whole equals its two halves convolved in turn,
    the second given the first's last three rows."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, 6))
    taps = jax.random.normal(jax.random.PRNGKey(4), (4, 6))
    whole = llama._causal_conv(x, taps, None)
    np.testing.assert_allclose(whole[:, 0], taps[3] * x[:, 0], atol=1e-6)  # the last tap: the row itself
    first = llama._causal_conv(x[:, :11], taps, None)
    second = llama._causal_conv(x[:, 11:], taps, x[:, 8:11])
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole, atol=1e-6)


# --- a wide residual beside the tier's pins --------------------------------------

class _Chip:
    platform, device_kind, id = "tpu", "TPU v5 lite", 0

    def __init__(self, limit, in_use=0):
        self.limit, self.in_use = limit, in_use

    def memory_stats(self):
        return {"bytes_limit": self.limit, "bytes_in_use": self.in_use}


def test_an_auto_tier_gives_up_pins_for_a_generation_of_blocks(model_dir, monkeypatch):
    """A pass whose blocks do not fit what the plan leaves free: ONCE, before
    anything is seated, an auto-sized tier is re-planned to the chip less the
    shards in flight less TWICE the need (the store takes half of the rest)
    less the stated headroom for the programs the pass will compile, and the
    next auto budget does not grow it back; a pass that fits, an explicit
    budget and a tier with seats change nothing, so that a later pass, which
    finds the programs on the chip, still fits under the plan it was given."""
    from flexible_llm_sharding_tpu.runtime import residency

    model, d = model_dir
    names = weights.layer_names(model)
    sizes = list(dict(residency.layer_stream_bytes(d, names, False)).values())
    total, in_flight = sum(sizes), 2 * max(sizes)
    chip = _Chip(total + in_flight)  # the whole model would fit beside the shards in flight
    programs = -int(-residency.PROGRAM_HEADROOM_FRACTION * chip.limit // 1)  # rounded up
    cfg = FrameworkConfig(model_path=d, dtype="bfloat16")
    monkeypatch.setattr(
        FrameworkConfig, "effective_hbm_pin_bytes",
        lambda self, device=None, in_flight_bytes=0: total)
    residency.reset_process_tier()
    try:
        tier = residency.tier_for(cfg, names, False, chip)
        assert tier.plan.budget_bytes == total and len(tier.plan.pinned) == len(names)
        fits = residency.activation_budget_bytes(chip, tier, in_flight)
        assert fits == 0  # the pins take everything the shards in flight leave
        assert residency.make_room_for_activations(chip, tier, in_flight, 0, False) == 0
        assert tier.plan.budget_bytes == total  # a pass that needs nothing: unchanged
        need = max(sizes)
        got = residency.make_room_for_activations(chip, tier, in_flight, need, False)
        cut = total - 2 * need - programs
        assert tier.plan.budget_bytes == cut
        assert tier.plan.pinned_bytes_est <= cut and got >= need + programs // 2
        assert tier.activation_reserve_bytes == total - cut
        # the next call's auto budget (the same 'total') does not grow the plan back
        assert residency.tier_for(cfg, names, False, chip) is tier
        assert tier.plan.budget_bytes == cut
        # A later pass finds the seats and, beside them, the compiled programs:
        # the plan stands (a source froze its pin set on it) and, the programs
        # being what the headroom was kept for, the blocks still fit.
        key = residency.placement_key(chip)
        seats = {i: [("decoders", {"w": jnp.zeros((sizes[i] // 4,), jnp.float32)})]
                 for i in tier.plan.pinned}
        with tier._lock:
            tier._placed[key] = dict(seats)
            tier._dev_bytes[key] = sum(residency._placed_device_nbytes(v) for v in seats.values())
        seated = tier.pinned_device_bytes(chip)
        before = tier.plan
        chip.in_use = seated + programs
        assert residency.make_room_for_activations(chip, tier, in_flight, need, False) >= need
        # ... and more than the headroom beside the seats moves no seat: what
        # does not fit goes the cpu way, as before
        chip.in_use = seated + programs + need
        assert residency.make_room_for_activations(chip, tier, in_flight, need, False) < need
        assert tier.plan is before and tier.pinned_device_bytes(chip) == seated
        assert tier.activation_reserve_bytes == total - cut
    finally:
        residency.reset_process_tier()


def test_a_cycle_longer_than_the_cache_keeps_its_first_shards(tmp_path):
    """A sweep reads a model's streamed shards in a cycle. Under plain LRU a
    cycle one shard longer than the budget misses EVERY build, sweep after
    sweep (each shard is pushed out just before its next use); a loader
    spares its own model's shards, so the cycle's first shards stay and only
    what is over the budget is read again. Another model's shards are pushed
    out as before."""
    from flexible_llm_sharding_tpu.runtime.hostcache import HostShardCache

    paths = []
    for i in range(5):
        p = tmp_path / f"f{i}.bin"
        p.write_bytes(b"x")
        paths.append(str(p))
    segs = [("decoders", {"w": np.zeros(25, np.uint8)})]

    def sweeps(spare):
        cache = HostShardCache(budget_bytes=300)  # three shards of four
        for _ in range(3):
            for i in range(4):
                if cache.get(("m", i)) is None:
                    cache.put(("m", i), segs, paths=[paths[i]], nbytes=100, spare=spare)
        return cache

    lru = sweeps(None).stats()
    assert lru["hits"] == 0 and lru["evictions"] == 9  # the thrash
    kept = sweeps(lambda key: key[0] == "m")
    s = kept.stats()
    assert (s["hits"], s["misses"], s["evictions"], s["scan_refusals"]) == (6, 6, 0, 3)
    # another model's shard pushes out the least recently used one, as before
    assert kept.put(("other", 0), segs, paths=[paths[4]], nbytes=100,
                    spare=lambda key: key[0] == "other")
    assert kept.stats()["evictions"] == 1 and kept.get(("m", 0)) is None

    # a model whose streamed shards fit (every accepted cell: 7-8 GB under an
    # 11 GB budget) never meets the rule: the predicate is not even asked
    def never(key):
        raise AssertionError(key)

    roomy = HostShardCache(budget_bytes=400)
    for _ in range(2):
        for i in range(4):
            if roomy.get(("m", i)) is None:
                assert roomy.put(("m", i), segs, paths=[paths[i]], nbytes=100, spare=never)
    s = roomy.stats()
    assert (s["hits"], s["evictions"], s["scan_refusals"]) == (4, 0, 0)
