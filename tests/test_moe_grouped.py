"""The sigmoid-router expert layer's routed body (``llama._routed_experts``:
a row computed only in the experts its router chose, as grouped matmuls over
the block's rows sorted by expert) against its compute-all body on the same
inputs, and ``_decoder_block`` (attention per prompt under ``vmap``, the MLP
half once over the block's rows) against the per-prompt layer it replaced."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.ops import grouped_matmul as gm
from flexible_llm_sharding_tpu.runtime import executor
from flexible_llm_sharding_tpu.runtime.orchestration import run_prompts
from flexible_llm_sharding_tpu.utils.checkpoint import save_params
from tests.fake_tokenizer import FakeTokenizer

E, K, D, F = 16, 2, 64, 32
DEAD = 5  # an expert the router never chooses (held by rank 1 of 4)
SHARES = {"all": {}, "rank1of4": dict(ep_size=4, ep_rank=1)}  # experts 4-7 of 16


def _expert_cfg(groups=1, norm=True, shared=True, ep_size=1, ep_rank=0):
    return LlamaConfig(
        model_type="deepseek_v3", hidden_size=D, intermediate_size=F,
        intermediate_size_mlp=96, num_hidden_layers=2, num_attention_heads=4,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_local_experts=E, num_experts_per_tok=K, moe_n_group=groups,
        moe_topk_group=2, moe_norm_topk_prob=norm, moe_routed_scaling_factor=2.5,
        n_shared_experts=2 if shared else 0, moe_layer_pattern=(False, True),
        moe_ep_size=ep_size, moe_ep_rank=ep_rank,
    )


def _expert_mlp(cfg, dtype, shared):
    mlp = llama.init_mixed_params(jax.random.PRNGKey(0), cfg, dtype)["layers"][1]["mlp"]
    if not shared:
        for name in ("shared_gate", "shared_up", "shared_down"):
            del mlp[name]
    # Expert DEAD's selection score sits under every other's, masked groups'
    # zeros included: it gets no row.
    mlp["correction_bias"] = mlp["correction_bias"].at[DEAD].set(-10.0)
    return mlp


def _both_bodies(mlp, cfg, x):
    def body(mlp, x, grouped):
        stats = []
        return llama._deepseek_moe_mlp(mlp, cfg, x, stats, grouped), stats

    out = []
    for grouped in (False, True):
        y, stats = jax.jit(body, static_argnums=2)(mlp, x, grouped)
        out.append((y, [np.asarray(c).tolist() for c in stats]))
    return out


# (norm_topk_prob, shared experts, x's leading shape: 128 rows and 111)
VARIANTS = [(True, True, (2, 64)), (False, True, (3, 37)),
            (True, False, (3, 37)), (False, False, (128,))]


@pytest.mark.parametrize(
    "dtype,groups,held,variant",
    list(itertools.product(
        [jnp.float32, jnp.bfloat16], [1, 4], sorted(SHARES), range(len(VARIANTS))
    )),
    ids=lambda v: getattr(v, "__name__", str(v)),
)
def test_routed_body_equals_compute_all(dtype, groups, held, variant):
    norm, shared, lead = VARIANTS[variant]
    share = SHARES[held]
    cfg = _expert_cfg(groups, norm, shared, **share)
    mlp = _expert_mlp(cfg, dtype, shared)
    assert mlp["gate"].shape[0] == (4 if share else E)
    x = jax.random.normal(jax.random.PRNGKey(1), (*lead, D)).astype(dtype)
    (dense, dense_stats), (routed, routed_stats) = _both_bodies(mlp, cfg, x)
    assert routed.dtype == dense.dtype == dtype and routed.shape == x.shape
    dense, routed = np.asarray(dense, np.float32), np.asarray(routed, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(routed, dense, atol=1e-5, rtol=0)
    else:
        # One rounding of a result to bfloat16 (8 bits) at the layer's scale.
        np.testing.assert_allclose(routed, dense, atol=np.abs(dense).max() * 2.0**-7, rtol=0)
    assert routed_stats == dense_stats
    if share:
        rows = int(np.prod(lead))
        (hits, assignments), = routed_stats
        assert assignments == rows * K and 0 < hits < assignments
        if not shared:
            # Rows none of whose choices is held get exactly nothing.
            none_held = (dense == 0).all(axis=-1)
            assert none_held.any() and not none_held.all()
            assert ((routed == 0).all(axis=-1) == none_held).all()
    else:
        assert routed_stats == []  # all held: nothing to count


@pytest.mark.parametrize("held", sorted(SHARES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_overflow_in_an_unchosen_expert_stays_out(dtype, held):
    """What the compute-all body's ``where`` guards against: an expert no row
    chose (here with an empty group) whose activations overflow to ``inf``
    leaves both bodies finite and equal. The overflow comes from huge finite
    weights: with ``inf`` in the weights themselves the CPU's emulation of
    ``ragged_dot`` (every group's weights times the rows masked to zero)
    reads 0 x inf, which the chip's grouped matmul, visiting no tile of an
    empty group, does not (checked there: CHANGES.md, PR 28)."""
    share = SHARES[held]
    cfg = _expert_cfg(4, **share)
    mlp = _expert_mlp(cfg, dtype, True)
    local = DEAD - cfg.held_experts.start if share else DEAD
    for name in ("gate", "up"):
        mlp[name] = mlp[name].at[local].set(1e30)
    x = jax.random.normal(jax.random.PRNGKey(2), (111, D)).astype(dtype)
    h = llama._ACT[cfg.hidden_act](x @ mlp["gate"][local]) * (x @ mlp["up"][local])
    assert np.isinf(np.asarray(h, np.float32)).any()
    (dense, _), (routed, _) = _both_bodies(mlp, cfg, x)
    dense, routed = np.asarray(dense, np.float32), np.asarray(routed, np.float32)
    assert np.isfinite(routed).all() and np.isfinite(dense).all()
    tol = 1e-5 if dtype == jnp.float32 else np.abs(dense).max() * 2.0**-7
    np.testing.assert_allclose(routed, dense, atol=tol, rtol=0)


# --- the Pallas kernel behind use_pallas (interpreted here) -------------------

@pytest.mark.parametrize("sizes", [(40, 0, 88), (0, 0, 200), (128, 128, 0), (1, 2, 3), (0, 0, 0)],
                         ids=lambda s: "-".join(map(str, s)))
def test_grouped_matmul_kernel_equals_ragged_dot(sizes):
    """The Pallas kernel at its 128-row tile against ``jax.lax.ragged_dot``
    on the rows that belong to a group (empty groups, groups that straddle
    a tile, rows past the last group: those are nobody's to read)."""
    m, k, n = 256, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    lhs = jax.random.normal(ks[0], (m, k)).astype(jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (len(sizes), k, n)).astype(jnp.bfloat16)
    gs = jnp.array(sizes, jnp.int32)
    assert gm.supports(k, n, lhs.dtype) and not gm.supports(k, n, jnp.float32)
    want = gm.for_groups(gs)(lhs, rhs, jnp.float32)
    got = gm.for_groups(gs, use_pallas=True)(lhs, rhs, jnp.float32)
    assert "ragged_dot" not in str(jax.make_jaxpr(
        lambda a, b, c: gm.for_groups(c, use_pallas=True)(a, b, jnp.float32)
    )(lhs, rhs, gs))
    used = sum(sizes)
    np.testing.assert_allclose(got[:used], want[:used], atol=1e-5, rtol=1e-5)
    # Shapes that are not eligible fall back without a word.
    odd = gm.for_groups(gs, use_pallas=True)(lhs[:, :64], rhs[:, :64], jnp.float32)
    assert "ragged_dot" in str(jax.make_jaxpr(
        lambda a, b, c: gm.for_groups(c, use_pallas=True)(a, b, jnp.float32)
    )(lhs[:, :64], rhs[:, :64], gs))
    assert odd.shape == (m, n)


def test_grouped_matmul_kernel_over_a_cut_contraction():
    """A weight tile over its budget cuts the contraction: the accumulator
    carries a visit's partial sums across the cuts."""
    m, k, n = 256, 2048, 1024
    assert gm._k_tile(k, n, 2) == 1024
    ks = jax.random.split(jax.random.PRNGKey(10), 2)
    lhs = jax.random.normal(ks[0], (m, k)).astype(jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (3, k, n)).astype(jnp.bfloat16)
    gs = jnp.array([100, 60, 50], jnp.int32)
    want = gm.for_groups(gs)(lhs, rhs, jnp.float32)
    got = gm.for_groups(gs, use_pallas=True)(lhs, rhs, jnp.float32)
    np.testing.assert_allclose(got[:210], want[:210], atol=1e-3, rtol=1e-4)


@pytest.mark.parametrize("widths", [(2048, 1408), (2048, 768), (4096, 2048)],
                         ids=["moonlight", "kanana", "mimo"])
def test_kernel_tiles_fit_the_chip_s_vmem(widths):
    """Both directions of the cells' expert shapes: tiles divide the widths
    and two weight tiles, two row tiles, two output tiles and the float32
    accumulator stay inside the kernel's 16 MiB."""
    d, f = widths
    for k, n in ((d, f), (f, d)):
        tm, tk, tn = gm.ROW_TILE, gm._k_tile(k, n, 2), n
        assert k % tk == 0 and tk % 128 == 0
        vmem = 2 * tk * tn * 2 + 2 * tm * tk * 2 + 2 * tm * tn * 4 + tm * tn * 4
        assert vmem < 16 << 20, (k, n, tk, vmem)


@pytest.mark.parametrize("held", sorted(SHARES))
def test_routed_body_with_the_kernel_equals_compute_all(held):
    """Eligible widths (128), bfloat16, a row count whose assignments do not
    fill whole row tiles: the routed body pads them with rows of no group."""
    share = SHARES[held]
    cfg = dataclasses.replace(_expert_cfg(4, **share), hidden_size=128, intermediate_size=128)
    mlp = llama.init_mixed_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)["layers"][1]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(9), (100, 128)).astype(jnp.bfloat16)
    dense = jax.jit(lambda m, x: llama._deepseek_moe_mlp(m, cfg, x))(mlp, x)
    routed = jax.jit(lambda m, x: llama._deepseek_moe_mlp(m, cfg, x, None, True, True))
    assert "ragged_dot" not in str(jax.make_jaxpr(routed)(mlp, x))
    dense, routed = np.asarray(dense, np.float32), np.asarray(routed(mlp, x), np.float32)
    np.testing.assert_allclose(routed, dense, atol=np.abs(dense).max() * 2.0**-7, rtol=0)


def test_routed_body_refuses_a_vmap_with_its_own_groups():
    """Why ``runtime/decode.py``'s vmapped layers keep the compute-all body."""
    cfg = _expert_cfg()
    mlp = _expert_mlp(cfg, jnp.float32, True)
    x = jnp.ones((2, 8, D))
    with pytest.raises(NotImplementedError, match="ragged_dot"):
        jax.vmap(lambda x: llama._deepseek_moe_mlp(mlp, cfg, x, None, True))(x)


# --- _decoder_block ---------------------------------------------------------

def _deepseek_model():
    cfg = dataclasses.replace(
        _expert_cfg(4), num_hidden_layers=3, moe_layer_pattern=(False, True, True)
    )
    return cfg, llama.init_mixed_params(jax.random.PRNGKey(3), cfg)["layers"][1:], None


def _mimo_model():
    """Two window layers of a MiMo-V2-type model that holds experts 4-7 of 16."""
    cfg = LlamaConfig.from_hf_config(dict(
        model_type="mimo_v2_flash", vocab_size=300, hidden_size=D,
        intermediate_size=96, moe_intermediate_size=F, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=24, v_head_dim=16,
        swa_num_attention_heads=4, swa_num_key_value_heads=4, swa_head_dim=24,
        swa_v_head_dim=16, partial_rotary_factor=1 / 3, rope_theta=5e6,
        swa_rope_theta=1e4, sliding_window=16, hybrid_layer_pattern=[0, 1, 1],
        moe_layer_freq=[0, 1, 1], n_routed_experts=E, num_experts_per_tok=K,
        n_group=1, topk_group=1, norm_topk_prob=True, routed_scaling_factor=1.0,
        attention_value_scale=0.707, add_swa_attention_sink_bias=True,
        add_full_attention_sink_bias=False, layernorm_epsilon=1e-5,
        max_position_embeddings=4096, ep_size=4, ep_rank=1,
    ))
    layers = llama.init_mixed_params(jax.random.PRNGKey(4), cfg)["layers"][1:]
    return cfg, layers, jnp.array([True, True])


def _llama_model():
    cfg = LlamaConfig(
        vocab_size=300, hidden_size=D, intermediate_size=96, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2,
    )
    return cfg, llama.init_params(jax.random.PRNGKey(5), cfg)["layers"], None


def _block(cfg, b=3, lp=32, s=2, ls=16):
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    return (
        jax.random.normal(ks[0], (b, lp, cfg.hidden_size)),
        jax.random.normal(ks[1], (b, s, ls, cfg.hidden_size)),
        jnp.array([lp, lp - 5, lp - 13][:b], jnp.int32),
    )


@pytest.mark.parametrize("model", [_deepseek_model, _mimo_model, _llama_model])
def test_decoder_block_equals_the_per_prompt_layer(model):
    """The parent's arithmetic, kept here as a loop: every prompt through the
    whole ``prefix_suffix_layer`` (MLP half per prompt, compute-all experts)."""
    cfg, layers, sliding = model()
    moe_stats = cfg.moe_ep_size > 1
    p, s, plen = _block(cfg)
    want_p, want_s, want_counts = p, s, np.zeros((2,), np.int64)
    for i, layer in enumerate(layers):
        outs = [
            llama.prefix_suffix_layer(
                layer, cfg, want_p[b], want_s[b], plen[b],
                sliding=None if sliding is None else bool(sliding[i]),
                moe_stats=moe_stats,
            )
            for b in range(p.shape[0])
        ]
        want_p = jnp.stack([o[0] for o in outs])
        want_s = jnp.stack([o[1] for o in outs])
        if moe_stats:
            want_counts += sum(np.asarray(o[2], np.int64) for o in outs)
    seg = {
        "layers": jax.tree.map(lambda *a: jnp.stack(a), *layers),
        "sliding": sliding, "rope": None,
    }
    got_p, got_s, *counts = executor._decoder_block(
        cfg, seg, jnp.array(p), jnp.array(s), plen, False, None, None, moe_stats
    )  # copies: the block's activations are donated
    np.testing.assert_allclose(got_p, want_p, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5, rtol=1e-5)
    if moe_stats:
        rows = p.shape[0] * (p.shape[1] + s.shape[1] * s.shape[2])
        assert np.asarray(counts[0]).tolist() == want_counts.tolist()
        assert want_counts[1] == rows * K * len(layers) and 0 < want_counts[0] < want_counts[1]
    rows_layers = (p.size + s.size) // cfg.hidden_size * len(layers)
    expert = cfg.num_local_experts > 0
    assert executor._expert_rows(seg, p.size + s.size, None) == (
        ("grouped", rows_layers) if expert else ("dense", 0)
    )
    jaxpr = str(executor._decoder_block.trace(
        cfg, seg, p, s, plen, False, None, None, moe_stats
    ).jaxpr)
    assert ("ragged_dot" in jaxpr) == expert


def test_decoder_block_under_a_tp_mesh_keeps_the_compute_all_body():
    cfg, layers, _ = _deepseek_model()
    seg = {"layers": jax.tree.map(lambda *a: jnp.stack(a), *layers),
           "sliding": None, "rope": None}
    p, s, plen = _block(cfg)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    rows_layers = (p.size + s.size) // cfg.hidden_size * len(layers)
    assert executor._expert_rows(seg, p.size + s.size, mesh) == ("dense", rows_layers)
    assert "ragged_dot" in str(executor._decoder_block.trace(cfg, seg, p, s, plen).jaxpr)
    assert "ragged_dot" not in str(
        executor._decoder_block.trace(cfg, seg, p, s, plen, False, mesh).jaxpr
    )
    one_chip = executor._decoder_block(cfg, seg, jnp.array(p), jnp.array(s), plen)
    under_tp = executor._decoder_block(cfg, seg, p, s, plen, False, mesh)
    for a, b in zip(one_chip, under_tp):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# --- the sweep's record -----------------------------------------------------

@pytest.mark.parametrize("tensor_parallel", [1, 2])
def test_sweep_record_says_which_body_ran(tmp_path, tensor_parallel):
    """``expert_rows_grouped`` / ``expert_rows_dense`` of a scoring sweep: a
    sigmoid-router model on one chip runs every expert layer grouped, under a
    tensor-parallel mesh compute-all."""
    cfg = dataclasses.replace(
        _expert_cfg(4), vocab_size=300, num_hidden_layers=3,
        moe_layer_pattern=(False, True, True),
    )
    params = llama.init_mixed_params(jax.random.PRNGKey(7), cfg)
    save_params(jax.tree.map(np.asarray, params), str(tmp_path), cfg)
    fw = FrameworkConfig(
        model_path=str(tmp_path), layer_num_per_shard=1, storage_location="cpu",
        dtype="float32", bucket_multiple=8, block_size=2,
        tensor_parallel=tensor_parallel,
    )
    prompts = [("the quick brown fox", (" jumps", " sleeps")),
               ("a much longer prefix than the first one", (" ends",))]
    run_prompts(fw, prompts, tokenizer=FakeTokenizer(), devices=jax.devices()[:tensor_parallel])
    rec = executor.process_sweep_log()[-1]
    ran, idle = ("grouped", "dense") if tensor_parallel == 1 else ("dense", "grouped")
    assert rec[f"expert_rows_{idle}"] == 0
    rows, rest = divmod(rec[f"expert_rows_{ran}"], 2)  # two expert layers
    assert rest == 0 and rows >= 2 * (8 + 8)  # every padded row of the block
    assert executor.stream_stats()[f"last_sweep_expert_rows_{ran}"] == 2 * rows
