"""MiMo-V2-Flash (``mimo_v2_flash``) against its plain float32 reference
(``benchmark/families/mimo_v2_flash/reference.py``, which shares no code with
the package), on seeded random weights at a small size: window and full
attention layers of different KV-head counts in one model, qk and v head dims
that differ without MLA, partial rotary, scaled values, the window layers'
sink logit, a dense first layer, and an expert layer that holds a share of
its experts."""

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
from benchmark.families.mimo_v2_flash import reference, weights
from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.ops import pallas_attention as pa
from flexible_llm_sharding_tpu.runtime import executor
from flexible_llm_sharding_tpu.runtime.orchestration import run_prompts
from flexible_llm_sharding_tpu.utils import checkpoint as ckpt

# ops/__init__.py binds the name ``attention`` to the function: the module
# of the XLA ops comes from sys.modules.
xla_attn = sys.modules["flexible_llm_sharding_tpu.ops.attention"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_model(**over) -> dict:
    """The benchmark's configuration at its rehearsal widths: 4 layers
    [full+dense, window, window, full], 4 of 16 experts held (rank 0)."""
    with open(os.path.join(ROOT, "benchmark", "configs", "mimo-v2-flash.json")) as f:
        m = json.load(f)
    m.update(m.pop("rehearsal"))
    m.update(over)
    return m


def program_cfg(model: dict) -> LlamaConfig:
    return LlamaConfig.from_hf_config(weights.hf_config(model))


# --- config ---------------------------------------------------------------

def test_config_parse_published():
    with open(os.path.join(ROOT, "benchmark", "configs", "mimo-v2-flash.json")) as f:
        m = json.load(f)
    m.pop("rehearsal")
    cfg = program_cfg(m)
    assert cfg.attn_shape(False) == (64, 4, 192, 128)
    assert cfg.attn_shape(True) == (64, 8, 192, 128)
    assert cfg.rotary_dim == 64 and cfg.attn_value_scale == 0.707
    assert cfg.sliding_window == 128 and cfg.rope_local_theta == 10000.0
    assert cfg.rope_theta == 5e6 and cfg.rms_norm_eps == 1e-5
    assert cfg.layer_sliding == tuple(bool(x) for x in m["hybrid_layer_pattern"][:18])
    assert cfg.moe_layer_pattern == (False,) + (True,) * 17
    assert cfg.attn_sink_local and not cfg.attn_sink_global
    assert cfg.num_local_experts == 256 and cfg.held_experts == range(0, 16)
    assert cfg.num_experts_per_tok == 8 and cfg.n_shared_experts == 0
    assert cfg.intermediate_size == 2048 and cfg.intermediate_size_mlp == 16384
    assert cfg.moe_routed_scaling_factor == 1.0 and cfg.vocab_size == 19072


def test_config_native_round_trip_and_errors():
    cfg = program_cfg(small_model())
    d = {**dataclasses.asdict(cfg), "fls_native": True}
    assert LlamaConfig.from_hf_config(json.loads(json.dumps(d))) == cfg
    bad = weights.hf_config(small_model())
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        LlamaConfig.from_hf_config({**bad, "hybrid_layer_pattern": [0, 1]})
    with pytest.raises(ValueError, match="ep_size"):
        LlamaConfig.from_hf_config({**bad, "ep_size": 3})
    for path in ("KV-cache decoding", "tensor parallelism"):
        with pytest.raises(NotImplementedError, match=path):
            cfg.require_one_attention_shape(path)
    LlamaConfig().require_one_attention_shape("anything")  # one shape: fine


# --- HF names -------------------------------------------------------------

def _hf_layer(model: dict, i: int, rng) -> tuple[dict, dict]:
    """(HF-keyed state dict of layer i with EVERY routed expert, the native
    flat dict the held share converts to)."""
    name = f"model.layers.{i}"
    native = {k: rng.standard_normal(shape).astype(np.float32)
              for k, shape, _ in weights.tensor_specs(model, name)}
    hf_of = {"input_layernorm.scale": "input_layernorm.weight",
             "post_attention_layernorm.scale": "post_attention_layernorm.weight",
             "attn.wq": "self_attn.q_proj.weight", "attn.wk": "self_attn.k_proj.weight",
             "attn.wv": "self_attn.v_proj.weight", "attn.wo": "self_attn.o_proj.weight",
             "attn.sink": "self_attn.attention_sink_bias",
             "mlp.router": "mlp.gate.weight",
             "mlp.correction_bias": "mlp.gate.e_score_correction_bias"}
    sd = {}
    moe = weights.is_moe_layer(model, i)
    held = weights.held_experts(model)
    for k, a in native.items():
        if moe and k in ("mlp.gate", "mlp.up", "mlp.down"):
            proj = {"mlp.gate": "gate_proj", "mlp.up": "up_proj", "mlp.down": "down_proj"}[k]
            for e in range(weights.router_width(model)):
                w = a[e - held.start] if e in held else rng.standard_normal(a.shape[1:])
                sd[f"{name}.mlp.experts.{e}.{proj}.weight"] = np.asarray(w.T, np.float32)
        elif k in hf_of:
            sd[f"{name}.{hf_of[k]}"] = a.T if a.ndim == 2 else a
        else:
            sd[f"{name}.mlp.{k.split('.')[1]}_proj.weight"] = a.T
    return sd, native


@pytest.mark.parametrize("layer", [0, 1, 3])
def test_hf_names_convert_to_native_with_held_experts(layer):
    model = small_model(ep_rank=2)
    sd, native = _hf_layer(model, layer, np.random.default_rng(layer))
    got = ckpt.hf_layer_to_native(
        f"model.layers.{layer}", sd, held_experts=program_cfg(model).held_experts
    )
    assert sorted(got) == sorted(native)
    for k in native:
        np.testing.assert_array_equal(got[k], native[k])
    with pytest.raises(ValueError, match="no native-layout slot"):
        ckpt.hf_layer_to_native(
            f"model.layers.{layer}", {**sd, f"model.layers.{layer}.self_attn.extra": sd[
                f"model.layers.{layer}.input_layernorm.weight"]})


# --- the whole model through run_prompts -----------------------------------

@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    model = small_model()
    d = str(tmp_path_factory.mktemp("mimo") / "model")
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
    over the same weights: what is left is the order of float32 sums (the
    flash kernels' online softmax, the experts' stacked einsum), so 2e-4 in
    log-probability holds with room (measured 2e-5); the controls below move
    it by 0.1 and more. Four layers a shard puts a window and a full layer
    in one shard: the builder has to break the run on shape."""
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
        np.testing.assert_allclose(np.log(np.asarray(g)[:, 0, :]), w, atol=2e-4)
    rec = executor.process_sweep_log()[-1]
    assert (rec["window_layers"], rec["full_layers"]) == (2, 2)
    assert (rec["experts_held"], rec["router_width"]) == (4, 16)
    # 3 expert layers x top-2 x every row computed: the prefixes' 64-token
    # buckets and the suffixes' (padding included, so more than the tokens)
    rows, rest = divmod(rec["routed_assignments"], 3 * 2)
    assert rest == 0 and rows >= 64 + 128 + 192 + 3 * 2 * 64
    assert 0 < rec["held_expert_hits"] < rec["routed_assignments"]


@pytest.mark.parametrize("part", ["sink", "window", "value_scale"])
def test_reference_controls_differ(model_dir, part):
    """Each part of the mathematics moves the answers by far more than the
    tolerance above: leaving one out of the program could not pass."""
    model, _ = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)
    full = _reference_logp(model, prompts, tok)
    cut = _reference_logp(model, prompts, tok, leave_out=(part,))
    assert max(float(jnp.abs(a - b).max()) for a, b in zip(full, cut)) > 0.05


def test_shard_builder_breaks_a_run_on_shape():
    a = {"attn": {"wk": np.zeros((8, 4))}, "mlp": {"gate": np.zeros((2, 8, 4))}}
    b = {"attn": {"wk": np.zeros((8, 8))}, "mlp": {"gate": np.zeros((2, 8, 4))}}
    c = {"attn": {"wk": np.zeros((8, 4)), "sink": np.zeros(4)}, "mlp": {"gate": np.zeros((2, 8, 4))}}
    assert executor._stackable(a, jax.tree.map(np.ones_like, a))
    assert not executor._stackable(a, b)  # same structure, other shape
    assert not executor._stackable(a, c)  # other structure
    assert not executor._stackable(a, jax.tree.map(lambda x: x.astype(np.float16), a))


# --- forward_full, decode through the cache ---------------------------------

@pytest.fixture(scope="module")
def params_cfg():
    cfg = program_cfg(small_model())
    return llama.init_mixed_params(jax.random.PRNGKey(5), cfg), cfg


def test_decode_through_cache_matches_full_forward(params_cfg):
    """Prefill (prefix_suffix_layer, its KV kept) and then three decode steps
    through the cache, layer by layer with each layer's own kind, against
    forward_full over the whole sequence. float32 throughout: 1e-4 covers the
    summation order (the cache path sums three key regions jointly)."""
    params, cfg = params_cfg
    rng = np.random.default_rng(0)
    lp, ls, steps = 40, 6, 3
    ids = jnp.asarray(rng.integers(3, cfg.vocab_size, lp + ls + steps))
    x_full = llama.forward_full(params, cfg, ids[None])[0]  # [L, V] logits
    pattern = llama.layer_sliding_pattern(cfg)

    ph = llama.embed(params["embed"], ids[:lp], jnp.float32, cfg)
    sh = llama.embed(params["embed"], ids[lp:lp + ls], jnp.float32, cfg)[None]
    ph = jnp.pad(ph, ((0, 64 - lp), (0, 0)))
    sh = jnp.pad(sh, ((0, 0), (0, 8 - ls), (0, 0)))
    kvs = []
    for lyr, sl in zip(params["layers"], pattern):
        ph, sh, kv = llama.prefix_suffix_layer(
            lyr, cfg, ph, sh, jnp.int32(lp), return_kv=True, sliding=sl)
        nkv = cfg.attn_shape(sl)[1]
        assert kv["kp"].shape == (64, nkv, 96) and kv["vp"].shape == (64, nkv, 64)
        kv["kg"] = jnp.zeros((1, steps, nkv, 96))
        kv["vg"] = jnp.zeros((1, steps, nkv, 64))
        kvs.append(kv)
    head = lambda h: llama._mm(
        llama.rms_norm(h, params["norm"]["scale"], cfg.rms_norm_eps), params["lm_head"]["kernel"])
    np.testing.assert_allclose(head(sh[0, ls - 1]), x_full[lp + ls - 1], atol=1e-4)
    eos = jnp.asarray([ls - 1])
    for t in range(steps):
        x = llama.embed(params["embed"], ids[lp + ls + t][None, None], jnp.float32, cfg)
        for i, (lyr, sl) in enumerate(zip(params["layers"], pattern)):
            x, kvs[i] = llama.decode_step_layer(
                lyr, cfg, x, kvs[i], jnp.int32(lp), eos, jnp.int32(t), sliding=sl)
        np.testing.assert_allclose(head(x[0, 0]), x_full[lp + ls + t], atol=1e-4)


# --- an expert layer that holds a share --------------------------------------

def _uncut(cfg):
    return dataclasses.replace(cfg, moe_ep_size=1, moe_ep_rank=0)


def test_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the four ranks' partial results add up to what
    the layer gives with all 16 experts (no part is computed by every rank:
    the family has no shared expert), in the program and in the reference."""
    model = small_model()
    cfg = program_cfg(model)
    whole = llama.init_layer_params(jax.random.PRNGKey(1), _uncut(cfg))["mlp"]
    whole["correction_bias"] = jax.random.normal(jax.random.PRNGKey(2), (16,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.hidden_size))
    want = llama._deepseek_moe_mlp(whole, _uncut(cfg), x)
    parts, ref_parts, stats = [], [], []
    for rank in range(4):
        c = dataclasses.replace(cfg, moe_ep_rank=rank)
        mine = {k: (v[c.held_experts.start:c.held_experts.stop] if v.ndim == 3 else v)
                for k, v in whole.items()}
        parts.append(llama._deepseek_moe_mlp(mine, c, x, stats))
        ref_parts.append(reference.moe({**model, "ep_rank": rank}, mine, x[0]))
    np.testing.assert_allclose(sum(parts), want, atol=1e-5)
    ref_whole = reference.moe({**model, "n_routed_experts": 16, "ep_size": 1}, whole, x[0])
    np.testing.assert_allclose(sum(ref_parts), ref_whole, atol=1e-5)
    np.testing.assert_allclose(ref_whole, want[0], atol=1e-5)
    hits = np.sum([np.asarray(s) for s in stats], axis=0)
    assert hits.tolist() == [2 * 24 * 2, 4 * 2 * 24 * 2]  # every assignment lands once


def _deepseek_moe_before(mlp, cfg, x):
    """``_deepseek_moe_mlp`` as it was before an expert layer could hold a
    share (PR 26), kept here word for word as the yardstick of 'unchanged'."""
    _mm, _ACT, _PRECISION = llama._mm, llama._ACT, llama._PRECISION
    e, k = cfg.num_local_experts, cfg.num_experts_per_tok
    g = cfg.moe_n_group
    logits = jnp.einsum("...ld,de->...le", x.astype(jnp.float32),
                        mlp["router"].astype(jnp.float32), precision=_PRECISION)
    scores = jax.nn.sigmoid(logits)
    choice = scores + mlp["correction_bias"].astype(jnp.float32)
    if g > 1:
        grouped = choice.reshape(*choice.shape[:-1], g, e // g)
        top2, _ = jax.lax.top_k(grouped, 2)
        group_scores = top2.sum(axis=-1)
        _, gidx = jax.lax.top_k(group_scores, cfg.moe_topk_group)
        gmask = jnp.sum(jax.nn.one_hot(gidx, g, dtype=choice.dtype), axis=-2)
        choice = jnp.where(jnp.repeat(gmask, e // g, axis=-1) > 0, choice, 0.0)
    _, top_idx = jax.lax.top_k(choice, k)
    top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
    if cfg.moe_norm_topk_prob:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg.moe_routed_scaling_factor
    combine = jnp.sum(jax.nn.one_hot(top_idx, e, dtype=jnp.float32) * top_w[..., None],
                      axis=-2).astype(x.dtype)
    act = _ACT[cfg.hidden_act]
    h = act(jnp.einsum("...ld,edf->...lef", x, mlp["gate"].astype(x.dtype), precision=_PRECISION)
            ) * jnp.einsum("...ld,edf->...lef", x, mlp["up"].astype(x.dtype), precision=_PRECISION)
    c = combine[..., None]
    h = jnp.where(c != 0, h * c, jnp.zeros_like(h))
    routed = jnp.einsum("...lef,efd->...ld", h, mlp["down"].astype(x.dtype), precision=_PRECISION)
    shared = _mm(act(_mm(x, mlp["shared_gate"])) * _mm(x, mlp["shared_up"]), mlp["shared_down"])
    return routed + shared


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("groups", [1, 4])
def test_deepseek_v3_with_all_experts_held_is_unchanged_bit_for_bit(dtype, groups):
    cfg = LlamaConfig(
        model_type="deepseek_v3", hidden_size=64, intermediate_size=32,
        intermediate_size_mlp=96, num_hidden_layers=2, num_attention_heads=4,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_local_experts=8, num_experts_per_tok=2, moe_n_group=groups,
        moe_topk_group=2, moe_routed_scaling_factor=2.5, n_shared_experts=2,
        moe_layer_pattern=(False, True),
    )
    mlp = llama.init_mixed_params(jax.random.PRNGKey(0), cfg, dtype)["layers"][1]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 64)).astype(dtype)
    now = jax.jit(lambda m, x: llama._deepseek_moe_mlp(m, cfg, x))(mlp, x)
    before = jax.jit(lambda m, x: _deepseek_moe_before(m, cfg, x))(mlp, x)
    assert now.dtype == before.dtype
    np.testing.assert_array_equal(np.asarray(now, np.float32), np.asarray(before, np.float32))


# --- the kernels with a sink -------------------------------------------------

def _plain_attention(q, k, v, mask, sink, scale):
    """float64 numpy: softmax with the sink as one more denominator term.
    q [Lq, nq, hd], k [Lk, nkv, hd], v [Lk, nkv, vd], mask [Lq, Lk]."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    g = q.shape[1] // k.shape[1]
    out = np.zeros((q.shape[0], q.shape[1], v.shape[-1]))
    for h in range(q.shape[1]):
        s = q[:, h] @ k[:, h // g].T * scale
        s = np.where(mask, s, -np.inf)
        m = np.maximum(s.max(-1, keepdims=True), sink[h])
        p = np.exp(s - m)
        out[:, h] = (p / (p.sum(-1, keepdims=True) + np.exp(sink[h] - m))) @ v[:, h // g]
    return out


KERNEL_SHAPES = [(16, 4), (16, 8)]  # (query heads, KV heads): both kinds' group sizes


@pytest.mark.parametrize("n_q,n_kv", KERNEL_SHAPES)
@pytest.mark.parametrize("window", [None, 128])
def test_causal_kernel_with_sink(n_q, n_kv, window):
    """qk 192 / v 128, float32 in interpret mode against the XLA op and a
    float64 softmax: 2e-5 is float32's rounding over 256 keys."""
    rng = jax.random.split(jax.random.PRNGKey(n_kv), 4)
    lq, plen = 256, 200
    q = jax.random.normal(rng[0], (lq, n_q, 192))
    k = jax.random.normal(rng[1], (lq, n_kv, 192))
    v = jax.random.normal(rng[2], (lq, n_kv, 128))
    sink = jax.random.normal(rng[3], (n_q,)) * 2 + 2
    got = pa.flash_causal_attention(q, k, v, jnp.int32(plen), window=window, sink=sink,
                                    interpret=True)
    mask = xla_attn.causal_mask(lq, lq, window=window) & (jnp.arange(lq)[None] < plen)
    xla = xla_attn.attention(q, k, v, mask, sink=sink)
    want = _plain_attention(q, k, v, np.asarray(mask), np.asarray(sink), 192 ** -0.5)
    np.testing.assert_allclose(got[:plen], want[:plen], atol=2e-5)
    np.testing.assert_allclose(xla[:plen], want[:plen], atol=2e-5)
    plain = pa.flash_causal_attention(q, k, v, jnp.int32(plen), window=window, interpret=True)
    assert float(jnp.abs(plain[:plen] - got[:plen]).max()) > 1e-2  # the sink took mass


@pytest.mark.parametrize("n_q,n_kv", KERNEL_SHAPES)
@pytest.mark.parametrize("window", [None, 128])
def test_prefix_shared_kernel_with_sink(n_q, n_kv, window):
    rng = jax.random.split(jax.random.PRNGKey(10 + n_kv), 6)
    lp, plen, s, ls = 256, 230, 2, 64
    q = jax.random.normal(rng[0], (s, ls, n_q, 192))
    kp = jax.random.normal(rng[1], (lp, n_kv, 192))
    vp = jax.random.normal(rng[2], (lp, n_kv, 128))
    ks = jax.random.normal(rng[3], (s, ls, n_kv, 192))
    vs = jax.random.normal(rng[4], (s, ls, n_kv, 128))
    sink = jax.random.normal(rng[5], (n_q,)) * 2 + 2
    got = pa.flash_prefix_shared_attention(
        q, kp, vp, ks, vs, jnp.int32(plen), window=window, sink=sink, interpret=True)
    xla = xla_attn.prefix_shared_attention(
        q, kp, vp, ks, vs, jnp.int32(plen), window=window, sink=sink)
    for si in range(s):  # one causal sequence: the real prefix, then the suffix
        k = jnp.concatenate([kp[:plen], ks[si]])
        v = jnp.concatenate([vp[:plen], vs[si]])
        mask = np.asarray(xla_attn.causal_mask(ls, plen + ls, offset=plen, window=window))
        want = _plain_attention(q[si], k, v, mask, np.asarray(sink), 192 ** -0.5)
        np.testing.assert_allclose(got[si], want, atol=2e-5)
        np.testing.assert_allclose(xla[si], want, atol=2e-5)


@pytest.mark.parametrize("n_q,n_kv", KERNEL_SHAPES)
@pytest.mark.parametrize("window", [None, 128])
def test_decode_kernel_with_sink(n_q, n_kv, window):
    rng = jax.random.split(jax.random.PRNGKey(20 + n_kv), 8)
    lp, plen, s, ls, tmax, t = 256, 250, 2, 64, 8, 5
    eos = jnp.asarray([40, 63])
    q = jax.random.normal(rng[0], (s, 1, n_q, 192))
    kp = jax.random.normal(rng[1], (lp, n_kv, 192))
    vp = jax.random.normal(rng[2], (lp, n_kv, 128))
    ks = jax.random.normal(rng[3], (s, ls, n_kv, 192))
    vs = jax.random.normal(rng[4], (s, ls, n_kv, 128))
    kg = jax.random.normal(rng[5], (s, tmax, n_kv, 192))
    vg = jax.random.normal(rng[6], (s, tmax, n_kv, 128))
    sink = jax.random.normal(rng[7], (n_q,)) * 2 + 2
    args = (q, kp, vp, ks, vs, kg, vg, jnp.int32(plen), eos, jnp.int32(t))
    got = pa.flash_decode_attention(*args, window=window, sink=sink, interpret=True)
    xla = xla_attn.decode_attention(*args, window=window, sink=sink)
    assert got.shape == (s, 1, n_q, 128)
    for si in range(s):  # the keys the new token sees, as one sequence
        n_s = int(eos[si]) + 1
        k = jnp.concatenate([kp[:plen], ks[si, :n_s], kg[si, :t + 1]])
        v = jnp.concatenate([vp[:plen], vs[si, :n_s], vg[si, :t + 1]])
        n = k.shape[0]
        mask = np.ones((1, n), bool)
        if window is not None:
            mask = (n - 1 - np.arange(n) < window)[None]
        want = _plain_attention(q[si], k, v, mask, np.asarray(sink), 192 ** -0.5)
        np.testing.assert_allclose(got[si], want, atol=2e-5)
        np.testing.assert_allclose(xla[si], want, atol=2e-5)
