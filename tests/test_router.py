"""The DeepSeek router's selections without a sort (``llama._top_k``,
``llama._top_groups``) against ``jax.lax.top_k``, which the chip compiles as
a sort of the whole axis: the same values, the same indices in the same
order (of equal values the lower index first), the same kept groups; the
expert layer of every MoE family routed by them against the same layer
routed by ``top_k``, to the bit; and no top-k left in the lowered programs."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families.glm5_next_text import weights as glm_weights
from benchmark.families.ling3_flash_text import weights as ling_weights
from flexible_llm_sharding_tpu.config import LlamaConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.runtime import executor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ["random", "ties", "flat", "masked"]


def _inputs(kind: str, shape: tuple, seed: int) -> np.ndarray:
    """Float32 rows of the router's kind: ``random`` sigmoid scores plus a
    bias; ``ties`` drawn from five values (every row holds equal entries);
    ``flat`` whole rows of one value, some of them 0; ``masked`` the group
    limit's output: the scores of half the row's groups set to 0.0."""
    rng = np.random.default_rng(seed)
    scores = 1 / (1 + np.exp(-rng.standard_normal(shape))) + 0.1 * rng.standard_normal(shape[-1])
    if kind == "ties":
        return (rng.integers(0, 5, shape) / 4).astype(np.float32)
    if kind == "flat":
        return np.repeat(rng.integers(0, 3, shape[:-1])[..., None] / 2, shape[-1], -1).astype(
            np.float32)
    if kind == "masked":
        g = 8 if shape[-1] % 8 == 0 else 2
        keep = rng.permutation(np.arange(shape[0] * g) % 2).reshape(shape[0], g) > 0
        scores = np.where(np.repeat(keep, shape[-1] // g, -1), scores, 0.0)
    return scores.astype(np.float32)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("k", [2, 4, 6, 8])
@pytest.mark.parametrize("width", [8, 64, 128, 256, 288, 512])
def test_rounds_give_top_k(width, k, kind):
    """Rows of the routers' widths (Moonlight 64, Kanana 128, MiMo 256, GLM
    288, Ling 512, Ling's groups of 64 and its 8 groups), flat and with a
    leading group axis."""
    x = jnp.asarray(_inputs(kind, (37, width), width * 10 + k))
    for arg in (x, x.reshape(37, 2, width // 2) if width // 2 >= k else x):
        want_v, want_i = jax.lax.top_k(arg, k)
        got_v, got_i = jax.jit(llama._top_k, static_argnums=1)(arg, k)
        assert got_i.dtype == want_i.dtype and got_v.dtype == want_v.dtype
        np.testing.assert_array_equal(got_i, want_i)
        np.testing.assert_array_equal(got_v, want_v)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("groups,k", [(8, 4), (8, 1), (8, 7), (4, 2), (2, 1), (16, 5)])
def test_group_mask_is_top_k_s_set(groups, k, kind):
    """The groups kept are the ones ``top_k`` of the group scores names,
    ties included (a row of equal scores keeps its first k)."""
    s = jnp.asarray(_inputs(kind, (53, groups), groups * 10 + k))
    _, idx = jax.lax.top_k(s, k)
    want = np.zeros(s.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, -1)
    got = jax.jit(llama._top_groups, static_argnums=1)(s, k)
    np.testing.assert_array_equal(got, want)
    assert (np.asarray(got).sum(-1) == k).all()


# --- the expert layer of each family, routed both ways ---------------------------

def _top_k_groups(scores, k):
    """The parent's group mask: ``top_k``'s indices, one-hot, summed."""
    _, idx = jax.lax.top_k(scores, k)
    return jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype), axis=-2) > 0


def _family_file(name: str, **over) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
        m = json.load(f)
    m.update(m.pop("rehearsal"))
    m.update(over)
    return m


def _from_weights(weights, name, seed, layer):
    """A toy cell's expert layer, with its row of the clamps where it has one."""
    model = _family_file(name)
    cfg = LlamaConfig.from_hf_config(weights.hf_config(model))
    flat = weights.layer_tensors(model, seed, f"model.layers.{layer}")
    mlp = weights.unflatten({k: jnp.asarray(a, jnp.float32) for k, a in flat.items()})["mlp"]
    table = llama.layer_swiglu_limits(cfg)
    return cfg, mlp, None if table is None else jnp.asarray(table[layer])


def _deepseek(experts, groups, top_groups, k, ep_size=1, ep_rank=0):
    cfg = LlamaConfig(
        model_type="deepseek_v3", hidden_size=64, intermediate_size=32,
        intermediate_size_mlp=96, num_hidden_layers=2, num_attention_heads=4,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_local_experts=experts, num_experts_per_tok=k, moe_n_group=groups,
        moe_topk_group=top_groups, moe_norm_topk_prob=True, moe_routed_scaling_factor=2.5,
        n_shared_experts=2, moe_layer_pattern=(False, True),
        moe_ep_size=ep_size, moe_ep_rank=ep_rank,
    )
    mlp = llama.init_mixed_params(jax.random.PRNGKey(0), cfg, jnp.float32)["layers"][1]["mlp"]
    # a bias of the trained router's size, so that it, not the token, decides some choices
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(1), mlp["correction_bias"].shape)
    return cfg, {**mlp, "correction_bias": bias}, None


def _mimo():
    from tests.test_moe_grouped import _mimo_model

    cfg, layers, _ = _mimo_model()
    return cfg, layers[0]["mlp"], None


FAMILIES = {
    # Moonlight's and Kanana's routers (deepseek_v3: 64 and 128 of 6, one group)
    "moonlight": lambda: _deepseek(64, 1, 1, 6),
    "kanana": lambda: _deepseek(128, 1, 1, 6, ep_size=4, ep_rank=2),
    "mimo": _mimo,  # 4 of 16 held, top-2
    "glm": lambda: _from_weights(glm_weights, "glm-5.3-flash.json", 11, 4),
    "ling": lambda: _from_weights(ling_weights, "ling-3.0-flash-vl.json", 13, 1),
    # Ling's router at its published width: 512 in 8 groups, top-4 groups,
    # top-8, one chip's 128
    "ling-width": lambda: _deepseek(512, 8, 4, 8, ep_size=4, ep_rank=0),
}


@pytest.mark.parametrize("grouped", [False, True], ids=["compute_all", "routed"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_layer_routes_as_top_k_did(family, grouped, monkeypatch):
    cfg, mlp, limits = FAMILIES[family]()
    held = mlp["gate"].shape[0]
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 41, cfg.hidden_size))
    if family in ("glm", "ling"):  # their MLP input is a norm's output of larger spread
        x = 3.0 * x

    def run():
        def layer(mlp, x):  # a new function a call: no trace is shared between the arms
            stats, tiles = [], []
            y = llama._deepseek_moe_mlp(
                mlp, cfg, x, stats, grouped, limits=limits, tiles=tiles if grouped else None)
            return y, stats, tiles

        return jax.jit(layer)(mlp, x), str(jax.make_jaxpr(layer)(mlp, x))

    got, jaxpr = run()
    with monkeypatch.context() as m:
        m.setattr(llama, "_top_k", jax.lax.top_k)
        m.setattr(llama, "_top_groups", _top_k_groups)
        want, want_jaxpr = run()
    assert "top_k" in want_jaxpr and "top_k" not in jaxpr
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    assert (held != mlp["router"].shape[-1]) == bool(got[1])  # a share counts its hits
    if got[1]:
        hits, routed = np.asarray(got[1][0])
        assert 0 < hits < routed == x.size // cfg.hidden_size * cfg.num_experts_per_tok


# --- the lowered programs ------------------------------------------------------------

def _expert_segment(family):
    """One expert layer as ``_decoder_block`` takes it, and its block."""
    if family == "mimo":
        from tests.test_moe_grouped import _mimo_model

        cfg, layers, _ = _mimo_model()
        layer, extra = layers[0], {"sliding": jnp.array([True])}
    else:
        model = _family_file("ling-3.0-flash-vl.json", distinct_layers=1)
        cfg = LlamaConfig.from_hf_config(ling_weights.hf_config(model))
        flat = ling_weights.layer_tensors(model, 13, "model.layers.1")
        layer = ling_weights.unflatten({k: jnp.asarray(a, jnp.float32) for k, a in flat.items()})
        extra = {"sliding": None, "index": np.asarray([1], np.int32)}
    seg = {"layers": jax.tree.map(lambda a: a[None], layer), "rope": None, **extra}
    p = jnp.zeros((1, 64, cfg.hidden_size))
    s = jnp.zeros((1, 2, 64, cfg.hidden_size))
    return cfg, seg, p, s, jnp.asarray([40], jnp.int32)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("family", ["mimo", "ling"])
def test_no_top_k_in_the_lowered_expert_layer(family, use_pallas):
    cfg, seg, p, s, plen = _expert_segment(family)
    text = executor._decoder_block.lower(
        cfg, seg, p, s, plen, use_pallas, None, None, cfg.moe_ep_size > 1
    ).as_text()
    low = text.lower()
    assert "top_k" not in low and "topk" not in low
    # what sorts is the routed body's two assignment sorts, nothing else
    assert text.count("stablehlo.sort") == 2
