"""MiniCPM-SALA (``minicpm_sala``) against its plain float32 reference
(``benchmark/families/minicpm_sala/reference.py``, which shares no code with
the package and computes the linear layers as a masked quadratic form), on
seeded random weights at a small size: linear-attention layers with a per-head
decay beside gated NoPE softmax layers in runs of unequal length, the state
handed from a prefix to its suffixes, a per-head output norm, MiniCPM's muP
scalings; the chunked kernel against the XLA op against the quadratic form;
what the model is refused; and that the families on the same code path give
what they gave before."""

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
from benchmark.families.minicpm_sala import reference, weights
from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.ops import lightning_attention as la
from flexible_llm_sharding_tpu.ops import rms_norm
from flexible_llm_sharding_tpu.runtime import executor, tokenization
from flexible_llm_sharding_tpu.runtime.orchestration import run_prompts
from flexible_llm_sharding_tpu.utils import checkpoint as ckpt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def published() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", "minicpm-sala.json")) as f:
        m = json.load(f)
    m.pop("rehearsal")
    return m


def small_model(**over) -> dict:
    """The benchmark's configuration at its rehearsal widths: 8 layers
    [softmax, 3 linear, 2 softmax, linear, softmax]."""
    with open(os.path.join(ROOT, "benchmark", "configs", "minicpm-sala.json")) as f:
        m = json.load(f)
    m.update(m.pop("rehearsal"))
    m.update(over)
    return m


def program_cfg(model: dict) -> LlamaConfig:
    return LlamaConfig.from_hf_config(weights.hf_config(model))


# --- config ---------------------------------------------------------------

def test_config_parse_published():
    m = published()
    cfg = program_cfg(m)
    linear = tuple(t == "lightning-attn" for t in m["mixer_types"])
    assert sum(linear) == 24 and cfg.layer_linear == linear
    assert cfg.layer_rope == linear  # the softmax layers are NoPE, the linear ones rotate
    assert cfg.attn_shape() == (32, 2, 128, 128)
    assert cfg.attn_shape(linear=True) == cfg.linear_attn_shape == (32, 32, 128, 128)
    assert cfg.qk_norm and cfg.attn_output_gate and cfg.linear_output_gate
    assert cfg.linear_output_norm and cfg.sliding_window is None
    assert cfg.sparse_attn_from == 8192 and cfg.rms_norm_eps == 1e-6
    assert cfg.embed_multiplier == 12.0 and cfg.logit_divisor == 16.0
    assert cfg.residual_multiplier == pytest.approx(1.4 / 32**0.5)
    assert cfg.vocab_size == 73448 and cfg.intermediate_size == 16384
    assert cfg.num_local_experts == 0 and not cfg.tie_word_embeddings
    decay = llama.layer_log_decay(cfg)
    assert decay.shape == (32, 32) and decay.dtype == np.float32
    assert (decay[~np.asarray(linear)] == 0).all()
    # layer 1, the first linear one: 2^(-8 (n+1) / 32) * (1 - 1/31 + 1e-5)
    np.testing.assert_allclose(
        decay[1], -(2.0 ** (-(np.arange(32) + 1) / 4.0)) * (1 - 1 / 31 + 1e-5), rtol=1e-6)
    for i in range(32):  # the reference's own formula, layer by layer
        if linear[i]:
            np.testing.assert_array_equal(decay[i], weights.log_decay(m, i))
    assert llama.layer_log_decay(LlamaConfig()) is None


def test_config_native_round_trip_and_errors():
    cfg = program_cfg(small_model())
    d = {**dataclasses.asdict(cfg), "fls_native": True}
    assert LlamaConfig.from_hf_config(json.loads(json.dumps(d))) == cfg
    hf = weights.hf_config(small_model())
    with pytest.raises(ValueError, match="mixer_types"):
        LlamaConfig.from_hf_config({**hf, "mixer_types": ["minicpm4"]})
    with pytest.raises(ValueError, match="mamba"):
        LlamaConfig.from_hf_config({**hf, "mixer_types": ["mamba"] * 8})
    with pytest.raises(NotImplementedError, match="lightning_nkv"):
        LlamaConfig.from_hf_config({**hf, "lightning_nkv": 1})
    with pytest.raises(NotImplementedError, match="lightning_scale"):
        LlamaConfig.from_hf_config({**hf, "lightning_scale": "1"})
    with pytest.raises(NotImplementedError, match="cannot say its kind"):
        LlamaConfig.from_hf_config({
            **hf, "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 64,
            "num_key_value_heads": 4, "use_output_norm": False})
    sparse = LlamaConfig.from_hf_config({**hf, "sparse_config": {"dense_len": 2048}})
    assert sparse.sparse_attn_from == 2048


REFUSED = ["KV-cache decoding", "the serve engine", "the pipeline runner",
           "the long-context scorer", "tensor parallelism"]


@pytest.mark.parametrize("path", REFUSED)
def test_paths_that_assume_pages_of_kv_refuse_the_model(path):
    cfg = program_cfg(small_model())
    with pytest.raises(NotImplementedError, match=path):
        cfg.require_one_attention_shape(path)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        cfg.require_one_attention_shape(path, layer_fn=True)
    LlamaConfig().require_one_attention_shape(path)  # one shape, no state: fine
    # per-kind shapes alone: the paths refuse, the layer functions do not
    mimo = LlamaConfig(local_attn_shape=(4, 2, 96, 64))
    with pytest.raises(NotImplementedError, match=path):
        mimo.require_one_attention_shape(path)
    mimo.require_one_attention_shape(path, layer_fn=True)


# --- HF names -------------------------------------------------------------

@pytest.mark.parametrize("layer", [0, 1])
def test_hf_names_convert_to_native(layer):
    model = small_model()
    name = f"model.layers.{layer}"
    rng = np.random.default_rng(layer)
    native = {k: rng.standard_normal(shape).astype(np.float32)
              for k, shape, _ in weights.tensor_specs(model, name)}
    assert ("attn.o_norm" in native) == weights.is_linear_layer(model, layer)
    hf_of = {"input_layernorm.scale": "input_layernorm.weight",
             "post_attention_layernorm.scale": "post_attention_layernorm.weight",
             "attn.wq": "self_attn.q_proj.weight", "attn.wk": "self_attn.k_proj.weight",
             "attn.wv": "self_attn.v_proj.weight", "attn.wo": "self_attn.o_proj.weight",
             "attn.q_norm": "self_attn.q_norm.weight", "attn.k_norm": "self_attn.k_norm.weight",
             "attn.o_norm": "self_attn.o_norm.weight", "attn.wg": "self_attn.o_gate.weight",
             "mlp.gate": "mlp.gate_proj.weight", "mlp.up": "mlp.up_proj.weight",
             "mlp.down": "mlp.down_proj.weight"}
    sd = {f"{name}.{hf_of[k]}": (a.T if a.ndim == 2 else a) for k, a in native.items()}
    got = ckpt.hf_layer_to_native(name, sd)
    assert sorted(got) == sorted(native)
    for k in native:
        np.testing.assert_array_equal(got[k], native[k])
    with pytest.raises(ValueError, match="no native-layout slot"):
        ckpt.hf_layer_to_native(name, {**sd, f"{name}.self_attn.extra": sd[
            f"{name}.input_layernorm.weight"]})


# --- the whole model through run_prompts -----------------------------------

@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    model = small_model()
    d = str(tmp_path_factory.mktemp("sala") / "model")
    weights.write_model(model, 11, d)
    return model, d


def _prompts(model, seed=3):
    t = {"prompts": 3, "suffixes": 2,
         "prefix_tokens": {"dist": "fixed", "values": [20, 70, 130]},
         "suffix_tokens": {"dist": "uniform", "lo": 3, "hi": 9}}
    return tr.make_batch(t, int(model["vocab_size"]), seed, 0)


def _reference_logp(model, prompts, tok, **kw):
    seqs = []
    for prefix, suffixes in prompts:
        pids = tok(prefix)["input_ids"]
        sids = [x[1:] for x in tok(list(suffixes))["input_ids"]]
        seqs.append(reference.scoring_sequence(pids, sids, 192))
    return [jax.nn.log_softmax(jnp.asarray(l), -1) for l in reference.forward_rows(
        model, 11, seqs, **kw)]


@pytest.mark.parametrize("layers_per_shard,use_pallas", [(1, False), (4, False), (1, True)])
def test_run_prompts_matches_reference(model_dir, layers_per_shard, use_pallas):
    """float32 compute over the bfloat16 files against the float32 reference
    over the same weights: what is left is the order of float32 sums (chunked
    recurrence against the quadratic form, the flash kernels' online
    softmax), so 2e-5 in log-probability holds with room (measured 1.5e-6);
    the controls below move it by 0.18 and more. Four layers a shard puts
    linear and softmax layers in one shard: the builder has to break the run
    on shape, in runs of 1, 3, 2, 1, 1."""
    model, d = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)
    cfg = FrameworkConfig(
        model_path=d, dtype="float32", layer_num_per_shard=layers_per_shard,
        use_pallas=use_pallas, storage_location="cpu", host_cache_gb=0,
    )
    got = run_prompts(cfg, prompts, tokenizer=tok, devices=jax.devices()[:1])
    want = _reference_logp(model, prompts, tok)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.log(np.asarray(g)[:, 0, :]), w, atol=2e-5)
    rec = executor.process_sweep_log()[-1]
    assert (rec["linear_layers"], rec["softmax_layers"]) == (4, 4)
    assert (rec["window_layers"], rec["full_layers"]) == (0, 4)
    # 4 linear layers x every row computed: the prefixes' 64-token buckets
    # and a prompt's 2 suffixes in a bucket of 4 x 64 tokens, padding included
    rows = 4 * (64 + 128 + 192 + 3 * 4 * 64)
    kernel, xla = (rows, 0) if use_pallas else (0, rows)
    assert (rec["linear_rows_kernel"], rec["linear_rows_xla"]) == (kernel, xla)
    assert rec["linear_state_bytes"] == 2 * 128 * 128 * 4  # one prompt a block
    assert rec["expert_rows_grouped"] == rec["expert_rows_dense"] == 0


PARTS = ["decay", "gate", "output_norm", "qk_norm", "mup", "nope"]


@pytest.mark.parametrize("part", PARTS)
def test_reference_controls_differ(model_dir, part):
    """Each part of the mathematics moves the answers by far more than the
    tolerance above: leaving one out of the program could not pass."""
    model, _ = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)
    full = _reference_logp(model, prompts, tok)
    cut = _reference_logp(model, prompts, tok, leave_out=(part,))
    assert max(float(jnp.abs(a - b).max()) for a, b in zip(full, cut)) > 0.05


def test_a_prompt_of_dense_len_tokens_is_refused(model_dir, tmp_path):
    """From ``dense_len`` tokens on the published model's softmax layers pick
    blocks of keys; nothing here computes that, so the prompt is refused:
    neither truncated nor run dense."""
    model, d = model_dir
    short = str(tmp_path / "model")
    os.makedirs(short)
    for fn in os.listdir(d):
        os.link(os.path.join(d, fn), os.path.join(short, fn))
    os.remove(os.path.join(short, "config.json"))
    with open(os.path.join(short, "config.json"), "w") as f:
        json.dump({**weights.hf_config(model), "sparse_config": {"dense_len": 100}}, f)
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    cfg = FrameworkConfig(model_path=short, dtype="float32", storage_location="cpu",
                          host_cache_gb=0, verify_weights=False)
    fits = [p for p in _prompts(model) if len(p[0].split()) < 80]
    assert len(run_prompts(cfg, fits, tokenizer=tok, devices=jax.devices()[:1])) == len(fits)
    with pytest.raises(NotImplementedError, match="block-sparse"):
        run_prompts(cfg, _prompts(model), tokenizer=tok, devices=jax.devices()[:1])
    t = tokenization.TokenizedPrompt(
        prefix_ids=np.zeros(128, np.int32), suffix_ids=np.zeros((1, 64), np.int32),
        prefix_len=95, suffix_eos=np.asarray([3]), num_suffixes=1)
    tokenization.check_dense_len(program_cfg(small_model()), [t])  # 99 < 8192
    with pytest.raises(NotImplementedError, match="prompt 7: 100 tokens"):
        tokenization.check_dense_len(
            dataclasses.replace(program_cfg(small_model()), sparse_attn_from=100),
            [dataclasses.replace(t, suffix_eos=np.asarray([4]))], labels=[7])
    tokenization.check_dense_len(LlamaConfig(), [t])


# --- the state handed from a prefix to its suffixes ---------------------------

@pytest.fixture(scope="module")
def params_cfg():
    cfg = program_cfg(small_model())
    return llama.init_params(jax.random.PRNGKey(5), cfg), cfg


def _head(params, cfg, h):
    return llama._mm(llama.final_norm(params["norm"], cfg, h), params["lm_head"]["kernel"])


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("plen", [1, 37, 64])
def test_suffix_continues_from_the_prefix_state(params_cfg, plen, use_pallas):
    """``prefix_suffix_layer`` layer by layer, each layer of its own kind,
    against ``forward_full`` over prefix + suffix as one sequence: the real
    prefix ends strictly inside its 64-row bucket, at the bucket's first row
    and at its last, and the rows after it hold noise that must reach
    nothing (they neither decay the state nor add to it). float32: 2e-5
    covers the order of the sums."""
    params, cfg = params_cfg
    rng = np.random.default_rng(plen)
    lp, ls, n_suf = 64, 64, 9
    ids = jnp.asarray(rng.integers(3, cfg.vocab_size, plen + n_suf))
    want = llama.forward_full(params, cfg, ids[None])[0]  # [L, V] logits
    ph = llama.embed(params["embed"], ids[:plen], jnp.float32, cfg)
    noise = jnp.asarray(rng.standard_normal((lp - plen, cfg.hidden_size)), jnp.float32)
    ph = jnp.concatenate([ph, 3.0 * noise])
    sh = llama.embed(params["embed"], ids[plen:], jnp.float32, cfg)
    sh = jnp.pad(sh, ((0, ls - n_suf), (0, 0)))[None]
    decay, rope = llama.layer_log_decay(cfg), llama.layer_rope_pattern(cfg)
    for i, lyr in enumerate(params["layers"]):
        assert llama.is_linear(cfg, lyr["attn"]) == cfg.layer_linear[i]
        ph, sh = llama.prefix_suffix_layer(
            lyr, cfg, ph, sh, jnp.int32(plen), use_pallas=use_pallas,
            rope_on=rope[i], log_decay=jnp.asarray(decay[i]))
    np.testing.assert_allclose(_head(params, cfg, sh[0, :n_suf]), want[plen:], atol=2e-5)
    np.testing.assert_allclose(_head(params, cfg, ph[:plen]), want[:plen], atol=2e-5)


def test_layer_functions_of_a_kv_cache_refuse_the_model(params_cfg):
    params, cfg = params_cfg
    ph, sh = jnp.zeros((64, cfg.hidden_size)), jnp.zeros((1, 8, cfg.hidden_size))
    for lyr in params["layers"][:2]:  # a softmax layer and a linear one alike
        with pytest.raises(NotImplementedError, match="return_kv"):
            llama.prefix_suffix_layer(lyr, cfg, ph, sh, jnp.int32(9), return_kv=True)
        with pytest.raises(NotImplementedError, match="suffix_only_layer"):
            llama.suffix_only_layer(lyr, cfg, None, None, sh, jnp.int32(9))
        with pytest.raises(NotImplementedError, match="decode_step_layer"):
            llama.decode_step_layer(lyr, cfg, sh, {}, jnp.int32(9), None, None)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params["layers"][1:3])
    with pytest.raises(NotImplementedError, match="one scan"):
        llama.forward_full({**params, "layers": stacked}, cfg, jnp.zeros((1, 8), jnp.int32))


def test_attn_only_and_moe_stats_keep_working(params_cfg):
    params, cfg = params_cfg
    rng = np.random.default_rng(0)
    ph = jnp.asarray(rng.standard_normal((64, cfg.hidden_size)), jnp.float32)
    sh = jnp.asarray(rng.standard_normal((2, 8, cfg.hidden_size)), jnp.float32)
    decay = jnp.asarray(llama.layer_log_decay(cfg)[1])
    lyr = params["layers"][1]
    whole = llama.prefix_suffix_layer(lyr, cfg, ph, sh, jnp.int32(40), log_decay=decay)
    p, s, counts = llama.prefix_suffix_layer(
        lyr, cfg, ph, sh, jnp.int32(40), log_decay=decay, attn_only=True, moe_stats=True)
    assert counts.tolist() == [0, 0]
    np.testing.assert_allclose(llama._residual_mlp(lyr, cfg, p), whole[0], atol=1e-6)
    np.testing.assert_allclose(llama._residual_mlp(lyr, cfg, s), whole[1], atol=1e-6)


# --- the kernel, the XLA op, the quadratic form --------------------------------

def _quadratic(q, k, v, g, s0, scale):
    """float64 numpy: o_t = scale * (sum_{j<=t} exp(G_t - G_j) (q_t.k_j) v_j +
    exp(G_t) q_t S_0) with G the running sum of the per-row log-decay."""
    q, k, v, g = (np.asarray(a, np.float64) for a in (q, k, v, g))
    n, length, h, d = q.shape
    big = np.cumsum(g, axis=0)  # [L, H]
    o = np.zeros((n, length, h, v.shape[-1]))
    s = np.zeros((n, h, d, v.shape[-1]))
    tril = np.tril(np.ones((length, length)))
    for hh in range(h):
        w = np.exp(np.tril(big[:, None, hh] - big[None, :, hh])) * tril
        for nn in range(n):
            first = np.zeros((d, v.shape[-1])) if s0 is None else np.asarray(s0, np.float64)[hh]
            o[nn, :, hh] = ((q[nn, :, hh] @ k[nn, :, hh].T) * w) @ v[nn, :, hh] + np.exp(
                big[:, hh])[:, None] * (q[nn, :, hh] @ first)
            left = np.exp(big[-1, hh] - big[:, hh])[:, None]
            s[nn, hh] = np.exp(big[-1, hh]) * first + (k[nn, :, hh] * left).T @ v[nn, :, hh]
    return o * scale, s


# (sequences, length, heads, chunk, fastest decay, an initial state?, real rows)
KERNEL_CASES = {
    "fastest-decay-256-row-chunks": (1, 512, 2, 256, 0.84, False, 512),
    "slowest-decay": (2, 320, 2, 256, 1e-5, True, 320),
    "ragged-tail-and-a-stopped-clock": (1, 448, 3, 256, 0.3, False, 301),
    "suffixes-from-a-state": (3, 64, 2, 256, 0.05, True, 64),
    "64-row-chunks": (1, 192, 2, 64, 0.84, True, 100),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_and_xla_op_against_the_quadratic_form(case):
    """float32 in interpret mode. s = 0.84 over a 256-row chunk is where the
    factored form exp(G_i) * exp(-G_j) leaves float32 (exp(215)); s = 1e-5
    is the last layer's rate. 1e-5 of the largest output is float32's
    rounding over up to 512 keys."""
    n, length, h, chunk, rate, with_state, real = KERNEL_CASES[case]
    d = 128
    ks = jax.random.split(jax.random.PRNGKey(length), 4)
    q, k, v = (jax.random.normal(ks[i], (n, length, h, d)) for i in range(3))
    live = jnp.arange(length) < real
    g = jnp.where(live[:, None], -rate * (jnp.arange(h) + 1.0) / h, 0.0)
    k = jnp.where(live[None, :, None, None], k, 0.0)
    s0 = jax.random.normal(ks[3], (h, d, d)) if with_state else None
    want_o, want_s = _quadratic(q, k, v, g, s0, d ** -0.5)
    assert la.supports(d, d, length)
    for op, kw in ((la.lightning_attention_xla, {}), (la.lightning_attention, {"interpret": True})):
        o, s = op(q, k, v, g, s0, chunk=chunk, **kw)
        assert o.shape == q.shape and s.shape == (n, h, d, d) and s.dtype == jnp.float32
        assert bool(jnp.isfinite(o).all())
        np.testing.assert_allclose(o, want_o, atol=1e-5 * np.abs(want_o).max())
        np.testing.assert_allclose(s, want_s, atol=1e-5 * np.abs(want_s).max())
    if real < length:  # the clock stopped: the state is the state at ``real``
        _, at_real = _quadratic(q[:, :real], k[:, :real], v[:, :real], g[:real], s0, 1.0)
        np.testing.assert_allclose(want_s, at_real, atol=1e-9)


def test_kernel_under_vmap_and_in_bfloat16():
    """As ``_decoder_block`` calls it: under ``vmap`` over a block's prompts,
    each with its own real length. bfloat16 inputs round the masked scores
    and the state's read-out to bfloat16: 2% of the largest output."""
    b, n, length, h, d = 2, 1, 320, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(ks[i], (b, n, length, h, d)) for i in range(3))

    def one(q, k, v, real, op, **kw):
        live = jnp.arange(length) < real
        g = jnp.where(live[:, None], -jnp.asarray([0.5, 0.01])[None], 0.0)
        return op(q, jnp.where(live[None, :, None, None], k, 0), v, g, **kw)

    real = jnp.asarray([300, 111])
    run = lambda op, **kw: jax.vmap(lambda *a: one(*a, op, **kw))(q, k, v, real)  # noqa: E731
    (o1, s1), (o2, s2) = run(la.lightning_attention_xla), run(la.lightning_attention, interpret=True)
    np.testing.assert_allclose(o2, o1, atol=2e-5 * float(jnp.abs(o1).max()))
    np.testing.assert_allclose(s2, s1, atol=2e-5 * float(jnp.abs(s1).max()))
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    o3, s3 = run(la.lightning_attention, interpret=True)
    assert o3.dtype == jnp.bfloat16 and s3.dtype == jnp.float32
    np.testing.assert_allclose(o3.astype(jnp.float32), o1, atol=0.02 * float(jnp.abs(o1).max()))
    assert not la.supports(96, 96, 320) and not la.supports(128, 128, 100)


# --- the families on the same code path are where they were ------------------

def _residual_attn_before(params, cfg, x, attn_out, h=None):
    """``_residual_attn``, ``_residual_mlp``, ``embed`` and
    ``select_eos_and_norm`` as they were before a model could gate its heads
    or scale its residuals (PR 30), word for word: the yardstick of
    'unchanged'."""
    y = llama._out_proj(params["attn"], attn_out)
    if cfg.ffw_sandwich_norms:
        y = rms_norm(y, params["post_attention_layernorm"]["scale"], cfg.rms_norm_eps,
                     cfg.norm_unit_offset)
    return x + y


def _residual_mlp_before(params, cfg, x, stats=None, grouped=False, use_pallas=False):
    pre = "pre_feedforward_layernorm" if cfg.ffw_sandwich_norms else "post_attention_layernorm"
    h = rms_norm(x, params[pre]["scale"], cfg.rms_norm_eps, cfg.norm_unit_offset)
    y = llama._mlp(params["mlp"], h, cfg, stats, grouped, use_pallas)
    if cfg.ffw_sandwich_norms:
        y = rms_norm(y, params["post_feedforward_layernorm"]["scale"], cfg.rms_norm_eps,
                     cfg.norm_unit_offset)
    return x + y


def _embed_before(params, ids, dtype, cfg=None):
    x = params["embedding"].astype(dtype)[ids]
    if cfg is not None and cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size**0.5, dtype)
    return x


def _select_eos_and_norm_before(params, cfg, suffix_h, suffix_eos):
    last = jnp.take_along_axis(suffix_h, suffix_eos[:, None, None], axis=1)
    return rms_norm(last, params["scale"], cfg.rms_norm_eps, cfg.norm_unit_offset)


def _other_family(name):
    if name == "deepseek_v3":
        return LlamaConfig(
            model_type="deepseek_v3", hidden_size=64, intermediate_size=32,
            intermediate_size_mlp=96, num_hidden_layers=2, num_attention_heads=4,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_local_experts=8, num_experts_per_tok=2, moe_n_group=1, moe_topk_group=1,
            moe_routed_scaling_factor=2.5, n_shared_experts=2, vocab_size=256,
            moe_layer_pattern=(False, True))
    from benchmark.families.mimo_v2_flash import weights as mimo

    with open(os.path.join(ROOT, "benchmark", "configs", "mimo-v2-flash.json")) as f:
        m = json.load(f)
    m.update(m.pop("rehearsal"))
    return LlamaConfig.from_hf_config(mimo.hf_config(m))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("family", ["deepseek_v3", "mimo_v2_flash"])
def test_other_families_are_unchanged_bit_for_bit(family, dtype, monkeypatch):
    """``embed`` -> every layer's ``prefix_suffix_layer`` (each of its own
    kind) -> ``select_eos_and_norm`` -> the head, jitted, with the functions
    this family's support touched against their bodies of before."""
    cfg = _other_family(family)
    params = llama.init_mixed_params(jax.random.PRNGKey(0), cfg, dtype)
    rng = np.random.default_rng(1)
    pids = jnp.asarray(rng.integers(3, cfg.vocab_size, 64))
    sids = jnp.asarray(rng.integers(3, cfg.vocab_size, (2, 8)))
    pattern = llama.layer_sliding_pattern(cfg)

    def score(params, pids, sids):
        ph = llama.embed(params["embed"], pids, dtype, cfg)
        sh = llama.embed(params["embed"], sids, dtype, cfg)
        for lyr, sl in zip(params["layers"], pattern):
            ph, sh = llama.prefix_suffix_layer(lyr, cfg, ph, sh, jnp.int32(50), sliding=sl)
        last = llama.select_eos_and_norm(params["norm"], cfg, sh, jnp.asarray([7, 4]))
        return llama.lm_head_scores(params["lm_head"], last), ph

    now = jax.jit(score)(params, pids, sids)
    monkeypatch.setattr(llama, "_residual_attn", _residual_attn_before)
    monkeypatch.setattr(llama, "_residual_mlp", _residual_mlp_before)
    monkeypatch.setattr(llama, "embed", _embed_before)
    monkeypatch.setattr(llama, "select_eos_and_norm", _select_eos_and_norm_before)
    before = jax.jit(score)(params, pids, sids)
    for a, b in zip(now, before):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_a_segment_carries_indices_and_the_decay_stays_float32(model_dir):
    """A float leaf of a segment goes through the placement's cast to the
    compute dtype: the decays are float32 constants of the step, picked by
    the layers' int32 indices, and a bfloat16 run has nothing to cast."""
    model, d = model_dir
    cfg = program_cfg(model)
    loader = executor._HostShardLoader(
        d, weights.layer_names(model), np.dtype("bfloat16"),
        layer_rope=cfg.layer_rope, layer_linear=cfg.layer_linear, verify_weights=False)
    try:
        segs = loader._build_host_shard((1, 2, 3, 4, 5))
    finally:
        loader.close()
    assert [k for k, _ in segs] == ["decoders"] * 3  # softmax, three linear, softmax
    assert [s["index"].tolist() for _, s in segs] == [[0], [1, 2, 3], [4]]
    assert all(s["index"].dtype == np.int32 for _, s in segs)
    assert not any(executor._needs_device_cast(s, np.dtype("bfloat16")) for _, s in segs)
    plain = executor._HostShardLoader(
        d, weights.layer_names(model), np.dtype("bfloat16"), verify_weights=False)
    try:
        assert "index" not in plain._build_host_shard((1,))[0][1]
    finally:
        plain.close()
