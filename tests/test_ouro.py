"""Ouro (``ouro``, a looped language model) against its plain float32
reference (``benchmark/families/ouro/reference.py``, which shares no code with
the package), on seeded random weights at a small size: one stack of layers
that every batch visits ``total_ut_steps`` times, four norms a layer, the
final norm and an exit gate at every step's end; the plan of visits, what a
looped sweep reads and streams, the sweep's record, what the model is
refused; and that a model visited once takes the path it took."""

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
from benchmark.families.ouro import reference, weights
from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.parallel import planner
from flexible_llm_sharding_tpu.runtime import executor, hostcache, residency
from flexible_llm_sharding_tpu.runtime.orchestration import run_prompts
from flexible_llm_sharding_tpu.utils import checkpoint as ckpt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 11


def published() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b.json")) as f:
        m = json.load(f)
    m.pop("rehearsal")
    return m


def small_model(**over) -> dict:
    """The benchmark's configuration at its rehearsal widths: 3 layers of 2
    heads of 64, visited 4 times."""
    with open(os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b.json")) as f:
        m = json.load(f)
    m.update(m.pop("rehearsal"))
    m.update(over)
    return m


def program_cfg(model: dict) -> LlamaConfig:
    return LlamaConfig.from_hf_config(weights.hf_config(model))


@pytest.fixture(autouse=True)
def fresh_process_state():
    """The residency tier and the host cache are the process's: a test's
    pins must not serve the next test's model."""
    residency.reset_process_tier()
    hostcache.reset_process_cache()
    yield
    residency.reset_process_tier()
    hostcache.reset_process_cache()


# --- config ---------------------------------------------------------------

def test_config_parse_published():
    cfg = program_cfg(published())
    assert cfg.total_ut_steps == 4 and cfg.early_exit_threshold == 1.0
    assert cfg.attn_shape() == (16, 16, 128, 128) and cfg.num_hidden_layers == 48
    assert cfg.hidden_size == 2048 and cfg.intermediate_size == 5632
    assert cfg.vocab_size == 49152 and not cfg.tie_word_embeddings
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-6 and cfg.rope_scaling_kind is None
    # the sandwich residual with a plain x * scale norm, no window, no biases
    assert cfg.ffw_sandwich_norms and not cfg.norm_unit_offset and not cfg.embed_scale
    assert cfg.sliding_window is None and cfg.layer_sliding is None
    assert not (cfg.attention_in_bias or cfg.attention_out_bias or cfg.mlp_bias or cfg.qk_norm)
    assert cfg.num_local_experts == 0 and cfg.layer_linear is None


STRAY = {"qk_norm": True, "norm_unit_offset": True, "attention_chunk_size": 64,
         "layer_sliding": [True] * 48, "final_logit_softcap": 30.0, "num_local_experts": 8,
         "logit_divisor": 16.0, "sliding_window": 128}


@pytest.mark.parametrize("key", sorted(STRAY))
def test_config_ignores_a_stray_key_as_the_other_families_do(key):
    """A foreign config.json contributes only what means the same for its
    ``model_type``: a numerics-changing native field name in an export is
    ignored, not honoured."""
    hf = weights.hf_config(published())
    assert LlamaConfig.from_hf_config({**hf, key: STRAY[key]}) == program_cfg(published())


def test_config_loop_keys_round_trip_and_stay_the_family_s():
    hf = weights.hf_config(small_model())
    cfg = LlamaConfig.from_hf_config({**hf, "total_ut_steps": 2, "early_exit_threshold": 0.5})
    assert (cfg.total_ut_steps, cfg.early_exit_threshold) == (2, 0.5)
    d = {**dataclasses.asdict(cfg), "fls_native": True}
    assert LlamaConfig.from_hf_config(json.loads(json.dumps(d))) == cfg
    # another family's export with the loop's keys in it is still visited once
    llama_like = {**hf, "model_type": "llama", "total_ut_steps": 4, "early_exit_threshold": 0.5}
    stray = LlamaConfig.from_hf_config(llama_like)
    assert stray.total_ut_steps == 1 and stray.early_exit_threshold == 1.0
    with pytest.raises(NotImplementedError, match="use_sliding_window"):
        LlamaConfig.from_hf_config({**hf, "use_sliding_window": True})
    with pytest.raises(ValueError, match="total_ut_steps"):
        LlamaConfig.from_hf_config({**hf, "total_ut_steps": 0})


# --- the published tensor names ---------------------------------------------

def test_hf_names_convert_to_native():
    """Four norms a layer land in the sandwich slots (the family's
    ``post_attention_layernorm`` is the MLP's INPUT norm), the exit gate in
    the final norm's file."""
    model = small_model()
    name = "model.layers.1"
    rng = np.random.default_rng(1)
    native = {k: rng.standard_normal(shape).astype(np.float32)
              for k, shape, _ in weights.tensor_specs(model, name)}
    hf_of = {"input_layernorm.scale": "input_layernorm.weight",
             "post_attention_layernorm.scale": "input_layernorm_2.weight",
             "pre_feedforward_layernorm.scale": "post_attention_layernorm.weight",
             "post_feedforward_layernorm.scale": "post_attention_layernorm_2.weight",
             "attn.wq": "self_attn.q_proj.weight", "attn.wk": "self_attn.k_proj.weight",
             "attn.wv": "self_attn.v_proj.weight", "attn.wo": "self_attn.o_proj.weight",
             "mlp.gate": "mlp.gate_proj.weight", "mlp.up": "mlp.up_proj.weight",
             "mlp.down": "mlp.down_proj.weight"}
    sd = {f"{name}.{hf_of[k]}": (a.T if a.ndim == 2 else a) for k, a in native.items()}
    got = ckpt.hf_layer_to_native(name, sd)
    assert sorted(got) == sorted(native)
    for k in native:
        np.testing.assert_array_equal(got[k], native[k])
    with pytest.raises(ValueError, match="no native-layout slot"):
        ckpt.hf_layer_to_native(name, {**sd, f"{name}.self_attn.extra": native["attn.wq"]})
    assert ckpt.key_to_layer("model.early_exit_gate.weight") == "model.norm"
    assert ckpt.key_to_layer("model.early_exit_gate.bias") == "model.norm"
    w, b = rng.standard_normal((1, 128)).astype(np.float32), np.float32([0.25])
    norm = ckpt.hf_layer_to_native("model.norm", {
        "model.norm.weight": native["input_layernorm.scale"],
        "model.early_exit_gate.weight": w, "model.early_exit_gate.bias": b})
    assert sorted(norm) == ["gate.bias", "gate.kernel", "scale"]
    np.testing.assert_array_equal(norm["gate.kernel"], w.T)
    assert sorted(ckpt.hf_layer_to_native("model.norm", {"model.norm.weight": w[0]})) == ["scale"]


# --- the plan of visits -----------------------------------------------------

@pytest.mark.parametrize("n,k", [(5, 1), (35, 1), (35, 4), (51, 7), (19, 32)])
def test_a_model_visited_once_keeps_its_plan(n, k):
    """``loop_steps`` 1 is the rule the planner had: ``np.array_split`` over
    ``range(n)``, and the roles by index that ``process_block`` read."""
    plan = planner.plan_shards_dp(n, k)
    want = [tuple(int(i) for i in a) for a in np.array_split(np.arange(n), -(-n // k))]
    assert list(plan.shards) == want and plan.loop_steps == 1
    for shard, v in zip(plan.shards, plan.visits()):
        first, last = shard[0], shard[-1]
        assert v.embeds == (first == 0)
        assert v.needs_prefix == (first <= n - 3)
        assert v.stores == (last != n - 1)
        assert v == planner.shard_visit(shard, n)._replace(step=v.step)


@pytest.mark.parametrize("steps,k", [(2, 1), (4, 1), (4, 3), (3, 5)])
def test_a_looped_plan_lists_visits(steps, k):
    n = 6  # embedding, 3 layers, norm, head
    plan = planner.plan_shards_dp(n, k, loop_steps=steps)
    order = [i for s in plan.shards for i in s]
    assert order == [0] + [1, 2, 3, 4] * steps + [5] == planner.visit_order(n, steps)
    visits = plan.visits()
    assert [v.embeds for v in visits] == [True] + [False] * (len(visits) - 1)
    assert [v.stores for v in visits] == [True] * (len(visits) - 1) + [False]
    norms_before = 0
    for at, (shard, v) in enumerate(zip(plan.shards, visits)):
        assert v.step == norms_before  # a shard's step: the final norms before it
        norms_before += shard.count(4)
        # the prefix dies only after the LAST step's last decoder visit
        starts_after = sum(len(s) for s in plan.shards[:at]) > len(order) - 3
        assert v.needs_prefix == (not starts_after)
    if k == 1:  # every step's shards are the same tuples: same programs, same cache keys
        body = plan.shards[1:-1]
        assert body == body[:4] * steps


# --- the whole model through run_prompts ------------------------------------

@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    model = small_model()
    d = str(tmp_path_factory.mktemp("ouro") / "model")
    weights.write_model(model, SEED, d)
    return model, d


def _variant(model_dir, tmp_path, **over):
    """The same layer files under another config.json (T, the threshold)."""
    model, d = model_dir
    if not over:
        return model, d
    model = {**model, **over}
    out = str(tmp_path / "model")
    os.makedirs(out)
    for fn in os.listdir(d):
        if fn != "config.json":
            os.link(os.path.join(d, fn), os.path.join(out, fn))
    with open(os.path.join(out, "config.json"), "w") as f:
        json.dump(weights.hf_config(model), f)
    return model, out


def _prompts(model, seed=3):
    t = {"prompts": 3, "suffixes": 2,
         "prefix_tokens": {"dist": "fixed", "values": [20, 70, 130]},
         "suffix_tokens": {"dist": "uniform", "lo": 3, "hi": 9}}
    return tr.make_batch(t, int(model["vocab_size"]), seed, 0)


def _seqs(prompts, tok):
    out = []
    for prefix, suffixes in prompts:
        pids = tok(prefix)["input_ids"]
        sids = [x[1:] for x in tok(list(suffixes))["input_ids"]]
        out.append(reference.scoring_sequence(pids, sids, 192))
    return out


def _reference_logp(model, prompts, tok, **kw):
    return [jax.nn.log_softmax(jnp.asarray(l), -1)
            for l in reference.forward_rows(model, SEED, _seqs(prompts, tok), **kw)]


def _score(d, prompts, tok, **kw):
    kw.setdefault("host_cache_gb", 0)
    cfg = FrameworkConfig(model_path=d, dtype="float32", **kw)
    return run_prompts(cfg, prompts, tokenizer=tok, devices=jax.devices()[:1])


LAYER_BYTES = 2 * (4 * 128 * 128 + 3 * 128 * 192 + 4 * 128)  # 279,552
REST_BYTES = 2 * (2 * 512 * 128 + 128 + 128 + 1)  # embedding, head, norm, gate: 262,658


@pytest.mark.parametrize("pin_gb", [1.0, 0.0005], ids=["all-pinned", "layers-streamed"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_run_prompts_matches_reference(model_dir, tmp_path, steps, pin_gb):
    """float32 compute over the bfloat16 files against the float32 reference
    over the same weights: what is left is the order of float32 sums, so
    2e-5 in log-probability holds with room (measured 3e-6); the controls
    below move it by 1.5 and more. Two sweeps: the first seats what the
    budget pins from its own stream, the second reads the seats.
    ``all-pinned``: a looped sweep reads each file ONCE (steps 2..T of the
    very sweep that seats a layer already read its seat); ``layers-streamed``
    (0.5 MB: the embedding, the head and the norm fit, no layer does): the
    layers cross the link T times a sweep and the counters say so."""
    model, d = _variant(model_dir, tmp_path, **({} if steps == 4 else {"total_ut_steps": steps}))
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)
    want = _reference_logp(model, prompts, tok)
    total = 3 * LAYER_BYTES + REST_BYTES
    assert weights.model_bytes(model) == total
    for sweep in range(2):
        before = executor.process_streamed_bytes()
        got = _score(d, prompts, tok, use_pallas=False, hbm_pin_gb=pin_gb)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.log(np.asarray(g)[:, 0, :]), w, atol=2e-5)
        streamed = executor.process_streamed_bytes() - before
        rec = executor.process_sweep_log()[-1]
        assert (rec["loop_steps"], rec["layer_visits"]) == (steps, 3 * steps)
        assert rec["upload_bytes"] == streamed
        if pin_gb == 1.0:
            assert streamed == (total if sweep == 0 else 0)
            pinned = 3 * steps if sweep else 3 * (steps - 1)
        else:
            assert streamed == steps * 3 * LAYER_BYTES + (REST_BYTES if sweep == 0 else 0)
            pinned = 0
        assert (rec["visits_pinned"], rec["visits_streamed"]) == (pinned, 3 * steps - pinned)
        assert ("exit_step_mean" in rec) == (steps > 1)


@pytest.mark.parametrize("layers_per_shard,use_pallas", [(1, True), (3, False), (5, True)])
def test_shards_that_run_over_a_step_s_end(model_dir, layers_per_shard, use_pallas):
    """Three and five visits a shard put a step's final norm between decoder
    runs of one shard; the flash kernels (interpret mode here) serve every
    step. Same answers, one record."""
    model, d = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)
    # by sweep id: the log keeps the last 256 records, and may be full
    seen = max((r["sweep_id"] for r in executor.process_sweep_log()), default=-1)
    got = _score(d, prompts, tok, use_pallas=use_pallas, layer_num_per_shard=layers_per_shard,
                 hbm_pin_gb=0)
    for g, w in zip(got, _reference_logp(model, prompts, tok)):
        np.testing.assert_allclose(np.log(np.asarray(g)[:, 0, :]), w, atol=2e-5)
    log = executor.process_sweep_log()
    assert [r["sweep_id"] > seen for r in log].count(True) == 1  # one a batch, not one a step
    assert (log[-1]["loop_steps"], log[-1]["layer_visits"]) == (4, 12)
    assert log[-1]["full_layers"] == 3 and log[-1]["window_layers"] == 0
    # The flash kernels' steps are counted a visit, not a layer: twelve equal
    # shares however the visits fall into shards; none where the XLA ops run.
    steps = log[-1]["flash_steps"]
    assert (steps > 0) == use_pallas and steps % 12 == 0


@pytest.mark.parametrize("q", [1.0, 0.5, 0.7, 0.05])
def test_the_threshold_picks_each_scored_token_s_step(model_dir, tmp_path, q):
    """At the published 1 every token reads the last step; under it a token
    reads the first step whose cumulative exit probability reaches q (0.05:
    the first step, for every row). The record's expected exit step is the
    gate's, whatever the threshold."""
    model, d = _variant(model_dir, tmp_path, **({} if q == 1.0 else {"early_exit_threshold": q}))
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)
    taps = []
    want = _reference_logp(model, prompts, tok, taps=taps)
    steps = np.concatenate([t["steps"] for t in taps])
    if q == 1.0:
        assert (steps == 4).all()
    elif q == 0.05:
        assert (steps == 1).all()
    else:
        assert len(set(steps.tolist())) > 1  # the rule separates the rows
    got = _score(d, prompts, tok, use_pallas=False, hbm_pin_gb=0)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.log(np.asarray(g)[:, 0, :]), w, atol=2e-5)
    lam = np.concatenate([t["lambdas"] for t in taps], axis=1)  # [4, rows]
    left = np.concatenate([np.ones((1, lam.shape[1])), np.cumprod(1 - lam[:-1], axis=0)])
    p = np.concatenate([lam[:-1] * left[:-1], left[-1:]])
    expected = (p * np.arange(1, 5)[:, None]).sum(0).mean()
    assert executor.process_sweep_log()[-1]["exit_step_mean"] == pytest.approx(expected, rel=1e-5)


def test_a_seated_loop_waits_for_each_shard_one_shard_later(model_dir, monkeypatch):
    """The whole model seated, prefetch depth 2 as on the chip: 18 shards
    (the embedding, 4 steps of 3 layers and the norm, the head). Every
    shard's end but the head's (which has no wait) is waited for one shard
    later, inside the next shard's dispatch; the seating sweep does so
    where steps 2-4 read the seats and the build two and three shards ahead
    uploads nothing (shards 5-12, 15 and 16). The scores and the exit
    gate's mean are bit for bit those of the same pass with every wait
    kept."""
    model, d = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)

    def sweep():
        got = _score(d, prompts, tok, use_pallas=False, hbm_pin_gb=1.0,
                     storage_location="tpu", prefetch_depth=2)
        return got, executor.process_sweep_log()[-1]

    _, seating = sweep()
    lagged, rec = sweep()
    monkeypatch.setattr(executor.ShardWeightSource, "wait_may_lag", lambda self: False)
    kept, rec_kept = sweep()
    assert seating["waits_deferred"] == 10
    assert (rec["visits_pinned"], rec["uploads"]) == (12, 0)
    assert rec["waits_deferred"] == 18 - 1 and rec_kept["waits_deferred"] == 0
    assert rec["exit_step_mean"] == rec_kept["exit_step_mean"]
    for a, b in zip(lagged, kept):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("part", reference.PARTS)
def test_reference_controls_differ(model_dir, part):
    """Each part of the loop moves the answers by far more than the
    tolerance above: leaving one out of the program could not pass."""
    model, _ = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)
    full = _reference_logp(model, prompts, tok)
    cut = _reference_logp(model, prompts, tok, leave_out=(part,))
    assert max(float(jnp.abs(a - b).max()) for a, b in zip(full, cut)) > 0.5


def test_data_parallel_runs_the_loop(model_dir):
    model, d = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)
    cfg = FrameworkConfig(model_path=d, dtype="float32", use_pallas=False, data_parallel=True,
                          hbm_pin_gb=0, host_cache_gb=0)
    got = run_prompts(cfg, prompts, tokenizer=tok, devices=jax.devices()[:2])
    for g, w in zip(got, _reference_logp(model, prompts, tok)):
        np.testing.assert_allclose(np.log(np.asarray(g)[:, 0, :]), w, atol=2e-5)


# --- two passes by hand -------------------------------------------------------

def _trees(model):
    names = weights.layer_names(model)
    trees = [jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.unflatten(weights.layer_tensors(model, SEED, n))) for n in names]
    return {"embed": trees[0], "layers": trees[1:-2], "norm": trees[-2], "lm_head": trees[-1]}


def test_two_steps_are_two_passes_with_the_final_norm_between(model_dir, tmp_path):
    """T = 2 by the layer functions alone: the stack, the final norm over
    EVERY row, the stack again, then what every model's pass ends with."""
    model, d = _variant(model_dir, tmp_path, total_ut_steps=2)
    cfg = program_cfg(model)
    params = _trees(model)
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)[:1]
    (prefix, suffixes), = prompts
    pids = np.asarray(tok(prefix)["input_ids"])
    sids = [x[1:] for x in tok(list(suffixes))["input_ids"]]
    ls = max(len(s) for s in sids)
    spad = np.zeros((len(sids), ls), np.int32)
    for i, s in enumerate(sids):
        spad[i, : len(s)] = s
    eos = jnp.asarray([len(s) - 1 for s in sids])

    def stack(ph, sh):
        for lyr in params["layers"]:
            ph, sh = llama.prefix_suffix_layer(lyr, cfg, ph, sh, jnp.int32(len(pids)))
        return ph, sh

    ph = llama.embed(params["embed"], jnp.asarray(pids), jnp.float32, cfg)
    sh = llama.embed(params["embed"], jnp.asarray(spad), jnp.float32, cfg)
    ph, sh = stack(ph, sh)
    ph, sh = (llama.final_norm(params["norm"], cfg, x) for x in (ph, sh))
    ph, sh = stack(ph, sh)
    last = llama.select_eos_and_norm(params["norm"], cfg, sh, eos)
    want = np.asarray(llama.lm_head_scores(params["lm_head"], last))
    got = _score(d, prompts, tok, use_pallas=False, hbm_pin_gb=0)[0]
    np.testing.assert_allclose(np.asarray(got)[:, 0, :], want, rtol=2e-5, atol=1e-9)
    # and one pass is NOT two: the loop is in the answer
    once = _score(_variant(model_dir, tmp_path / "t1", total_ut_steps=1)[1], prompts, tok,
                  use_pallas=False, hbm_pin_gb=0)[0]
    assert np.abs(np.log(np.asarray(once)[:, 0, :]) - np.log(want)).max() > 0.5


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_model_visited_once_scores_bit_for_bit_as_before(model_dir, tmp_path, dtype):
    """``total_ut_steps`` 1 through ``run_prompts`` against the parent's
    sequence of the same jitted steps, dispatched by hand with no plan of
    visits and no loop state: embedding, each layer's block, the norm over
    the scored rows, the head. The same programs on the same operands give
    the same bits."""
    model, d = _variant(model_dir, tmp_path, total_ut_steps=1)
    cfg = program_cfg(model)
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    prompts = _prompts(model)
    got = run_prompts(
        FrameworkConfig(model_path=d, dtype=dtype, use_pallas=False, hbm_pin_gb=0,
                        host_cache_gb=0, storage_location="tpu"),
        prompts, tokenizer=tok, devices=jax.devices()[:1])
    ex = executor.StreamingExecutor(
        FrameworkConfig(model_path=d, dtype=dtype, use_pallas=False, hbm_pin_gb=0,
                        host_cache_gb=0), tokenizer=tok)
    assert ex.plan.shards == tuple((i,) for i in range(6)) and ex.plan.loop_steps == 1
    toks = ex._tokenize(prompts)
    loader = executor._HostShardLoader(d, ex.layer_names, ex._np_dtype)
    try:
        shards = [executor._place(loader.build_host_shard((i,)), None, np_dtype=ex._np_dtype)
                  for i in range(6)]
    finally:
        loader.close()
    for i, t in enumerate(toks):  # one prompt a block, as the three buckets make them
        meta = (jnp.asarray(t.prefix_ids[None]), jnp.asarray(t.suffix_ids[None]),
                jnp.asarray(np.int32([t.prefix_len])), jnp.asarray(t.suffix_eos[None]))
        ph, sh = executor._embed_block(cfg, ex.dtype, shards[0][0][1], meta[0], meta[1])
        for (_, seg), in shards[1:4]:
            ph, sh = executor._decoder_block(cfg, seg, ph, sh, meta[2], False)
        sh = executor._norm_block(cfg, shards[4][0][1], sh, meta[3])
        want = executor._head_block(cfg, shards[5][0][1], sh)[0, : t.num_suffixes, None, :]
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


# --- what the model is refused ------------------------------------------------

REFUSED = ["KV-cache decoding", "the serve engine", "the pipeline runner",
           "the long-context scorer", "tensor parallelism", "streamed training",
           "the monolithic forward"]


@pytest.mark.parametrize("path", REFUSED)
def test_paths_that_keep_state_by_layer_refuse_the_model(model_dir, path):
    """One method, every path that keeps KV (or a stage, a shard of heads, a
    gradient) by layer; the message names ``total_ut_steps``. The entry
    points themselves raise it, at construction."""
    model, d = model_dir
    cfg = program_cfg(model)
    with pytest.raises(NotImplementedError, match=f"{path}.*total_ut_steps=4"):
        cfg.require_single_visit(path)
    dataclasses.replace(cfg, total_ut_steps=1).require_single_visit(path)
    LlamaConfig().require_single_visit(path)
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    fw = FrameworkConfig(model_path=d, dtype="float32", hbm_pin_gb=0, host_cache_gb=0)

    def enter():
        if path == "KV-cache decoding":
            from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator
            DecodeGenerator(fw, tokenizer=tok)
        elif path == "the serve engine":
            from flexible_llm_sharding_tpu.serve.engine import ServeEngine
            ServeEngine(fw, tokenizer=tok, start=False)
        elif path == "the pipeline runner":
            run_prompts(fw, _prompts(model), tokenizer=tok, devices=jax.devices()[:2])
        elif path == "the long-context scorer":
            from flexible_llm_sharding_tpu.runtime.longcontext import LongContextScorer
            LongContextScorer(fw, devices=jax.devices()[:2], tokenizer=tok)
        elif path == "tensor parallelism":
            run_prompts(dataclasses.replace(fw, tensor_parallel=2), _prompts(model),
                        tokenizer=tok, devices=jax.devices()[:2])
        elif path == "streamed training":
            from flexible_llm_sharding_tpu.training_stream import StreamedTrainer
            StreamedTrainer(cfg, _trees(model))
        else:
            llama.forward_full(_trees(model), cfg, jnp.zeros((1, 8), jnp.int32))

    with pytest.raises(NotImplementedError, match="total_ut_steps=4"):
        enter()


def test_layer_functions_of_a_kv_cache_refuse_the_model(model_dir):
    model, _ = model_dir
    cfg = program_cfg(model)
    lyr = _trees(model)["layers"][0]
    ph, sh = jnp.zeros((16, 128)), jnp.zeros((2, 4, 128))
    llama.prefix_suffix_layer(lyr, cfg, ph, sh, jnp.int32(9))
    with pytest.raises(NotImplementedError, match="return_kv.*total_ut_steps"):
        llama.prefix_suffix_layer(lyr, cfg, ph, sh, jnp.int32(9), return_kv=True)


# --- spans --------------------------------------------------------------------

def test_a_span_a_step_and_scopes_in_the_programs(model_dir):
    from flexible_llm_sharding_tpu.obs import trace as obs_trace

    model, d = model_dir
    tok = tr.WordIdTokenizer(int(model["vocab_size"]))
    tracer = obs_trace.TRACER.enable()
    tracer.clear()
    try:
        _score(d, _prompts(model), tok, use_pallas=False, hbm_pin_gb=0)
        spans = [e for e in tracer.snapshot() if e["name"] == "loop_step"]
    finally:
        obs_trace.TRACER.disable()
        tracer.clear()
    assert [e["step"] for e in spans] == [0, 1, 2, 3]
    assert len({e["sweep_id"] for e in spans}) == 1 and all(e["cat"] == "sweep" for e in spans)
    cfg = program_cfg(model)
    norm = jax.tree.map(lambda a: a.astype(jnp.float32),
                        weights.unflatten(weights.layer_tensors(model, SEED, "model.norm")))
    state = llama.exit_init((1, 2), 128, jnp.float32)
    text = executor._loop_norm_block.lower(
        cfg, norm, jnp.zeros((1, 16, 128)), jnp.zeros((1, 2, 4, 128)),
        jnp.zeros((1, 2), jnp.int32), state, np.int32(1)).as_text(debug_info=True)
    assert "loop_norm" in text and "exit_gate" in text
