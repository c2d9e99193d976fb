"""The plain reference against the program's own monolithic float32 forward
(``llama.forward_full``) at toy size, for both configurations' shapes, and
the scoring layout against plain causal sequences."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, weights

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def toy(name, **over):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        m = json.load(f)
    m.update(m.pop("rehearsal"))
    m.update(over)
    return m


CASES = {
    "moonlight": ("moonlight-16b-a3b", {}),
    # the sibling's shape: 128 experts at top-6, rope_interleave stated
    "kanana-128-experts": ("kanana-2-30b-a3b", {"n_routed_experts": 128, "num_experts_per_tok": 6}),
    "grouped-router": ("kanana-2-30b-a3b", {"n_group": 4, "topk_group": 2}),
    "rope-not-interleaved": ("moonlight-16b-a3b", {"rope_interleave": False}),
}


def program_logits(model, seed, ids):
    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.models import llama

    cfg = LlamaConfig.from_hf_config(weights.hf_config(model))
    names = weights.layer_names(model)
    trees = [weights.unflatten(weights.layer_tensors(model, seed, n)) for n in names]
    params = {"embed": trees[0], "layers": trees[1:-2], "norm": trees[-2], "lm_head": trees[-1]}
    return np.asarray(llama.forward_full(params, cfg, jnp.asarray(ids)[None], dtype=jnp.float32)[0])


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_forward_full(case):
    name, over = CASES[case]
    model = toy(name, **over)
    rng = np.random.default_rng(3)
    ids = rng.integers(3, model["vocab_size"], size=40)
    want = program_logits(model, 11, ids)
    seq = reference.causal_sequence(ids, rows=range(40), pad_to=64)
    got = reference.forward_rows(model, 11, [seq])[0]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < 2e-4 * max(1.0, np.abs(want).max())


def test_scoring_layout_equals_separate_causal_sequences():
    model = toy("moonlight-16b-a3b")
    rng = np.random.default_rng(5)
    prefix = rng.integers(3, 512, size=21).tolist()
    suffixes = [rng.integers(3, 512, size=n).tolist() for n in (5, 9, 3)]
    packed = reference.forward_rows(
        model, 2, [reference.scoring_sequence(prefix, suffixes, pad_to=64)])[0]
    for j, s in enumerate(suffixes):
        seq = reference.causal_sequence(prefix + s, rows=[len(prefix) + len(s) - 1], pad_to=64)
        alone = reference.forward_rows(model, 2, [seq])[0][0]
        assert np.abs(packed[j] - alone).max() < 1e-4


def test_lower_precision_weights_move_the_logits():
    model = toy("moonlight-16b-a3b")
    ids = np.arange(3, 35)
    seq = reference.causal_sequence(ids, rows=[31], pad_to=64)
    full = reference.forward_rows(model, 4, [seq])[0]
    for q in ("fp8", "int8"):
        low = reference.forward_rows(model, 4, [seq], quant=q)[0]
        assert 1e-3 < np.abs(low - full).max() < 2.0
