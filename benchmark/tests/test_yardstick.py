"""The yardstick's own arithmetic: peaks, needed FLOPs, token accounting,
the tokenizer and the seeded generator."""

import json
import os

import numpy as np
import pytest

from benchmark import flops, peaks, traffic as tr

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def _model(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_peaks_known_kind_and_unknown_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("name,e,k", [("moonlight-16b-a3b", 64, 6), ("kanana-2-30b-a3b", 128, 6)])
def test_needed_expert_term_counts_active_experts_only(name, e, k):
    m = _model(name)
    need = flops.expert_term_params(m)
    all_e = flops.expert_term_params(m, all_experts=True)
    # what the program computes today (all E) over what a token needs
    # (k routed + 2 shared): ~ (E + 2) / (k + 2)
    assert all_e / need == pytest.approx((e + 2) / (k + 2), rel=0.01)
    # and the needed count agrees with the program's own analytic count
    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.utils.metrics import model_flops_per_token
    from benchmark import weights

    cfg = LlamaConfig.from_hf_config(weights.hf_config(m))
    mine = flops.needed_flops(m, tokens=1, mean_context=300, head_rows=1)
    assert mine == pytest.approx(model_flops_per_token(cfg, context_len=300), rel=1e-6)


def test_needed_flops_per_token_magnitudes():
    # ISSUE.md's reckoning: ~2.5 GFLOP a token for Moonlight at 15 layers
    m = _model("moonlight-16b-a3b")
    per_tok = flops.needed_flops(m, 1, 400, 0)
    assert 1.8e9 < per_tok < 3.0e9


def test_tokenizer_covers_the_whole_vocabulary_and_round_trips():
    tok = tr.WordIdTokenizer(163840)
    ids = tok("t3 t163839 t40000")["input_ids"]
    assert ids == [tok.BOS, 3, 163839, 40000]
    assert tok(tok.decode([5, 163839]).strip())["input_ids"][1:] == [5, 163839]
    batch = tok(["t5 t6 t7", "t8"], padding=True)["input_ids"]
    assert len(batch[0]) == len(batch[1]) and batch[1][-1] == tok.pad_token_id


def test_generator_is_seeded_and_every_seed_gets_the_same_sizes():
    t = tr.load_traffic("score-b8")
    a = tr.make_batch(t, 163840, 7, 0)
    assert a == tr.make_batch(t, 163840, 7, 0)
    b = tr.make_batch(t, 163840, 8, 0)
    assert a != b
    sizes = lambda ps: (sorted(len(p.split()) for p, _ in ps),  # noqa: E731
                        sorted(len(s.split()) for _, ss in ps for s in ss))
    assert sizes(a) == sizes(b) == sizes(tr.make_batch(t, 163840, 2**31 + 11, 5))
    ids = [int(w[1:]) for p, _ in a for w in p.split()]
    assert max(ids) > 32000 and min(ids) >= tr.WordIdTokenizer.FIRST


def test_token_accounting_is_the_programs():
    from flexible_llm_sharding_tpu.runtime.tokenization import count_tokens

    t = tr.load_traffic("score-b8")
    tok = tr.WordIdTokenizer(128256)
    prompts = tr.make_batch(t, 128256, 3, 1)
    assert tr.count_tokens(tok, prompts) == count_tokens(tok, prompts)


def test_quantile_lengths_stay_inside_their_range():
    t = tr.load_traffic("score-b8")
    pre = tr.quantile_lengths(t["prefix_tokens"], 8)
    assert min(pre) >= 256 and max(pre) <= 768 and pre == sorted(pre)
    assert np.all(np.diff(np.log(pre)) > 0.1)  # log-uniform: even in the log
