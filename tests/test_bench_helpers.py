"""Tests for bench.py's measurement machinery — the artifact generators the
judge reads. Pins (1) the ratio-dispersion contract (spreads +
inconclusive flags), and (2) the reference-schedule emulation's
score parity with the streaming executor — the emulation must stay the
SAME computation under the reference's schedule, or vs_reference_schedule
stops being an apples-to-apples ratio."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
from flexible_llm_sharding_tpu.config import FrameworkConfig


def test_ratio_stats_contract():
    r = {}
    bench._ratio_stats(r, "x", [1.2, 1.1, 1.3])
    assert r["x"] == 1.2
    assert r["x_spread"] == [1.1, 1.2, 1.3]
    assert r["x_n"] == 3
    assert r["x_inconclusive"] is False

    bench._ratio_stats(r, "x", [0.9, 1.05, 1.2])
    assert r["x_inconclusive"] is True  # spread straddles 1.0

    # A single rep (budget-truncated pair loop) is ALWAYS inconclusive —
    # one noisy ratio cannot establish a win or a loss, and
    # the rep count distinguishes it in the artifact.
    bench._ratio_stats(r, "y", [0.8])
    assert r["y"] == 0.8 and r["y_n"] == 1
    assert r["y_inconclusive"] is True

    # Conclusive again: the flag must be OVERWRITTEN (not popped) so a
    # stale True can't sit next to a fresh median.
    bench._ratio_stats(r, "x", [1.1, 1.15])
    assert r["x_inconclusive"] is False


@pytest.fixture
def bench_model(tmp_path, monkeypatch):
    """The bench's own synthetic checkpoint, built under a tmp dir.
    vocab_size matches BenchTokenizer's 32000-id space — a smaller vocab
    would clamp ~every token to the last embedding row and degenerate the
    parity test's activations."""
    import jax

    monkeypatch.setattr(bench, "BENCH_DIR", str(tmp_path))
    cfg_kwargs = dict(
        vocab_size=32000,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=4,
        max_position_embeddings=4096,
    )
    return bench.make_model(jax, cfg_kwargs)


def test_resident_mfu_phase(monkeypatch):
    """The resident-MFU phase is TPU-gated in production (chip_peak_flops
    is None on CPU) and so would otherwise first EXECUTE on a chip, where
    an exception is logged-and-lost. Run its whole
    machinery here with a faked chip peak and a tiny model."""
    import jax

    from flexible_llm_sharding_tpu import config as cfg_mod
    from flexible_llm_sharding_tpu.utils import metrics

    # bench_resident_mfu binds chip_peak_flops at call time via a local
    # from-import, so patching the metrics module attribute takes effect.
    monkeypatch.setattr(metrics, "chip_peak_flops", lambda dev=None: 1e12)
    tiny = cfg_mod.LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        max_position_embeddings=512,
    )
    result = {}
    bench.bench_resident_mfu(
        jax, result, lambda: 1.0, cfg=tiny, B=2, T=64, iters=2
    )
    assert result["mfu_resident"] > 0
    assert result["resident_tokens_per_sec"] > 0
    assert result["resident_pass_s"] > 0
    assert result["resident_model_flops_per_token"] > 0


def test_reference_schedule_matches_executor(bench_model):
    """The reference-schedule emulation (per-tensor sync uploads, no scan,
    per-prompt loop, host activation round-trips) must produce the SAME
    scores as the overlapped executor on the same workload — the whole
    point of vs_reference_schedule is that only the schedule differs."""
    import jax

    from flexible_llm_sharding_tpu.runtime.executor import StreamingExecutor

    tok = bench.BenchTokenizer()
    prompts = bench.make_prompts(n=2, prefix_words=12, suffix_words=5, n_suffix=3)
    cfg = FrameworkConfig(
        model_path=bench_model,
        layer_num_per_shard=1,
        storage_location="cpu",
        dtype="float32",
        block_size=8,
        prefetch_depth=0,
    )
    ex = StreamingExecutor(cfg, tokenizer=tok)
    want = ex(prompts)
    toks = ex._tokenize(prompts)
    got, wall, load_s = bench._reference_schedule_run(jax, ex, toks)
    assert wall > 0 and load_s >= 0
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == np.asarray(w).shape
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=1e-5, atol=1e-6,
        )


def test_gb_bench_mode(bench_model, tmp_path):
    """run_gb_bench's whole machinery on the tiny bench checkpoint (the GB
    invocation differs only in the --model_path it is handed): throughput +
    stream seconds + forced-overlap + reference-schedule + int8/int4 ratio
    keys all land, with the single-rep inconclusive flags and the CPU
    quant-premise note."""
    out = str(tmp_path / "gb.json")
    result = bench.run_gb_bench(bench_model, n_prompts=1, out=out)
    assert result["gb_tokens_per_sec"] > 0
    assert result["model_gb"] > 0
    assert result["tokens_per_pass"] > 0
    assert "compute_wall_s" in result["gb_stream_seconds"]
    assert result["gb_streamed_bytes_per_pass"] > 0
    assert result["gb_overlap_efficiency_forced"] is not None
    # reference schedule ran and its scores matched (parity pinned
    # elsewhere; here the keys + dispersion flags must exist)
    assert "gb_vs_reference_schedule" in result
    assert "gb_vs_reference_schedule_n" in result
    # quant ratios: single rep -> flagged inconclusive, CPU premise noted
    assert "gb_int8_speedup" in result
    assert result["gb_int8_speedup_n"] == 1
    assert result["gb_int8_speedup_inconclusive"] is True
    assert "gb_int4_speedup" in result
    assert "cpu backend" in result["gb_quant_note"]
    import json as _json
    import os as _os

    assert _os.path.exists(out)
    with open(out) as f:
        assert _json.load(f)["metric"] == "gb_streamed_scoring"
    # The persisted raw ratio must be the value the median was computed
    # from (4-decimal raw vs 3-decimal median).
    assert len(result["gb_int8_ratios"]) == 1
    assert result["gb_int8_ratios"][0] == pytest.approx(
        result["gb_int8_speedup"], abs=1e-3
    )

    # Second invocation against the same out merges the prior run's raw
    # quant ratios: n upgrades to 2 instead of resetting to a fresh
    # flagged single rep forever.
    result2 = bench.run_gb_bench(bench_model, n_prompts=1, out=out)
    assert result2["gb_int8_speedup_n"] == 2
    assert result2["gb_int4_speedup_n"] == 2
    assert len(result2["gb_int8_ratios"]) == 2
    assert result2["merged_reps_from"]
