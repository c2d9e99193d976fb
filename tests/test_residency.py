"""Partial HBM residency suite (PR 6): the device residency tier.

The contract under test: with a nonzero pin budget the planner pins the
hottest layers (embedding, lm_head, norm first, then blocks), every
sweep's ``streamed_bytes`` drops by EXACTLY the pinned layers' bytes, and
outputs stay token-identical to the unpinned run — offline, decode, and
serving, including under chaos. Pin-time loads ride the manifest-verified
loader path: injected corruption re-read-heals into a clean pin, and
corruption that survives every re-read DEMOTES the layer back to
streaming (typed error through the normal degrade machinery) instead of
poisoning a resident copy. ``hbm_pin_gb=0`` is a strict no-op, and the
auto budget follows the host cache's explicit-cap precedence rule.
"""

import io
import json
import os
from contextlib import redirect_stdout

import numpy as np
import pytest

import jax

from flexible_llm_sharding_tpu.config import (
    FaultConfig,
    FrameworkConfig,
    ServeConfig,
)
from flexible_llm_sharding_tpu.integrity import manifest as iman
from flexible_llm_sharding_tpu.integrity.manifest import ShardCorruptError
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.runtime import hostcache, residency
from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator
from flexible_llm_sharding_tpu.runtime.executor import StreamingExecutor
from flexible_llm_sharding_tpu.serve import ServeEngine
from flexible_llm_sharding_tpu.utils.checkpoint import (
    layer_names_for,
    save_params,
)

from tests.fake_tokenizer import FakeTokenizer

CHAOS_SEED = int(os.environ.get("FLS_CHAOS_SEED", "1234"))

PROMPTS = [
    ("The capital of France", (" is Paris", " is Rome")),
    ("Two plus two equals", (" four", " five")),
    ("The sky is", (" blue", " green")),
    ("Hello world", (" again", " anew")),
]


@pytest.fixture(scope="module")
def model_dir(tiny_cfg, tmp_path_factory):
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    d = tmp_path_factory.mktemp("tiny_model_residency")
    save_params(jax.tree.map(np.asarray, params), str(d), tiny_cfg)
    return str(d)


@pytest.fixture(autouse=True)
def _fresh_process_state():
    residency.reset_process_tier()
    hostcache.reset_process_cache()
    iman.reset_verdicts()
    yield
    residency.reset_process_tier()
    hostcache.reset_process_cache()


def _fw(model_dir, **kw) -> FrameworkConfig:
    base = dict(
        model_path=model_dir,
        layer_num_per_shard=1,
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
        host_cache_gb=0.0,  # isolate the pin tier from the host cache
        io_retry_attempts=8,
        io_retry_base_s=0.001,
    )
    base.update(kw)
    return FrameworkConfig(**base)


@pytest.fixture(scope="module")
def clean_scores(model_dir):
    """Unpinned, fault-free oracle shared by the parity tests."""
    return StreamingExecutor(
        _fw(model_dir), tokenizer=FakeTokenizer()
    )(list(PROMPTS))


def _sizes(model_dir):
    return residency.layer_stream_bytes(model_dir, layer_names_for(4), False)


def _partial_budget_gb(model_dir) -> float:
    """A budget that pins embed + norm + lm_head + one block and no more."""
    s = _sizes(model_dir)
    return (s[0] + s[5] + s[6] + s[1] + 16) / 1e9


# ---------------------------------------------------------------------------
# Planner units
# ---------------------------------------------------------------------------

def test_planner_priority_and_budget(model_dir):
    names = layer_names_for(4)
    sizes = _sizes(model_dir)
    # Non-decoder layers (embed=0, norm=5, lm_head=6) take priority.
    plan = residency.plan_residency(
        model_dir, names, sizes[0] + sizes[5] + sizes[6]
    )
    assert plan.pinned == (0, 5, 6)
    assert plan.pinned_bytes_est <= plan.budget_bytes
    # A bigger budget adds decoder blocks in order (uniform sizes).
    plan2 = residency.plan_residency(
        model_dir, names, sizes[0] + sizes[5] + sizes[6] + sizes[1]
    )
    assert plan2.pinned == (0, 1, 5, 6)
    # Huge budget pins everything; zero pins nothing.
    assert residency.plan_residency(model_dir, names, 1 << 40).pinned == tuple(
        range(7)
    )
    empty = residency.plan_residency(model_dir, names, 0)
    assert empty.pinned == () and empty.pinned_fraction == 0.0
    # Greedy knapsack: a budget below the biggest tier-0 layer still pins
    # what fits (norm is tiny) instead of stopping at the first miss.
    small = residency.plan_residency(model_dir, names, sizes[5] + 1)
    assert 5 in small.pinned and 0 not in small.pinned


def test_config_validation_and_budget_resolution(model_dir):
    with pytest.raises(ValueError, match="hbm_pin_gb"):
        _fw(model_dir, hbm_pin_gb=-1.0)
    assert _fw(model_dir, hbm_pin_gb=0.0).effective_hbm_pin_bytes() == 0
    assert _fw(model_dir, hbm_pin_gb=2.0).effective_hbm_pin_bytes() == int(2e9)
    chaos = FaultConfig(enabled=True, seed=1)
    # Auto resolves OFF under chaos; an explicit budget still wins.
    assert _fw(model_dir, hbm_pin_gb=None, faults=chaos).effective_hbm_pin_bytes() == 0
    assert (
        _fw(model_dir, hbm_pin_gb=1.0, faults=chaos).effective_hbm_pin_bytes()
        == int(1e9)
    )
    # Auto on the CPU backend (unknown HBM) resolves to off.
    assert _fw(model_dir, hbm_pin_gb=None).effective_hbm_pin_bytes() == 0


def test_explicit_budget_pins_tier_against_auto_growth(model_dir):
    # Mirror of the host cache's precedence rule: an explicit cap pins the
    # tier's budget; a later auto config in the same process cannot grow it.
    names = layer_names_for(4)
    capped = residency.tier_for(
        _fw(model_dir, hbm_pin_gb=1.0), names, False, None
    )
    assert capped is not None and capped.plan.budget_bytes == int(1e9)
    auto = residency.tier_for(_fw(model_dir, hbm_pin_gb=None), names, False, None)
    # Auto resolves to 0 on CPU -> no tier handed out, and the pinned cap
    # is untouched.
    assert auto is None
    assert capped.plan.budget_bytes == int(1e9)
    again = residency.tier_for(
        _fw(model_dir, hbm_pin_gb=0.5), names, False, None
    )
    assert again is capped and again.plan.budget_bytes == int(5e8)


def test_tier_for_install_race_applies_losers_explicit_cap(model_dir, monkeypatch):
    # An explicit-cap caller that loses the install race to a concurrent
    # auto-budget caller must still pin the process budget (and resize the
    # winner's tier to its cap) — otherwise a later auto call could grow
    # past the explicitly pinned cap.
    names = layer_names_for(4)
    real_plan = residency.plan_residency
    raced = []
    loser_plans = []

    def racing_plan(path, layer_names, budget_bytes, tied_embeddings=False, **kw):
        if budget_bytes == int(5e8):
            loser_plans.append(budget_bytes)
        plan = real_plan(path, layer_names, budget_bytes, tied_embeddings, **kw)
        if not raced:
            raced.append(True)
            # While the explicit caller plans off the lock, an auto caller
            # wins the install with a bigger budget.
            key = (
                os.path.abspath(model_dir), "float32", False,
                tuple(layer_names), bool(tied_embeddings),
            )
            with residency._PROCESS_LOCK:
                residency._PROCESS_TIER = residency.DeviceResidencyTier(
                    model_dir, layer_names,
                    real_plan(path, layer_names, int(2e9), tied_embeddings),
                )
                residency._PROCESS_TIER_KEY = key
                residency._PROCESS_BUDGET_EXPLICIT = False
        return plan

    monkeypatch.setattr(residency, "plan_residency", racing_plan)
    tier = residency.tier_for(
        _fw(model_dir, hbm_pin_gb=0.5), names, False, None
    )
    assert tier is residency.process_tier()  # reused the winner's tier
    assert tier.plan.budget_bytes == int(5e8)  # loser's explicit cap applied
    assert residency._PROCESS_BUDGET_EXPLICIT is True
    # The loser's pre-lock plan was reused for the resize — no second
    # disk-stat sweep at its budget.
    assert loser_plans == [int(5e8)]


def test_auto_grow_apply_revalidates_against_explicit_cap(model_dir, monkeypatch):
    # An auto grower that decided to resize BEFORE an explicit cap landed
    # must re-validate at install time and skip — planning runs off every
    # lock, so its late last-swap-wins install would otherwise silently
    # override the pinned cap.
    names = layer_names_for(4)
    real_plan = residency.plan_residency
    auto_budget = [int(1e9)]
    monkeypatch.setattr(
        FrameworkConfig,
        "effective_hbm_pin_bytes",
        lambda self, device=None, in_flight_bytes=0: (
            auto_budget[0]
            if self.hbm_pin_gb is None
            else int(self.hbm_pin_gb * 1e9)
        ),
    )
    seeded = residency.tier_for(_fw(model_dir, hbm_pin_gb=None), names, False, None)
    assert seeded is not None and not residency._PROCESS_BUDGET_EXPLICIT
    auto_budget[0] = int(2e9)
    raced = []

    def racing_plan(path, layer_names, budget_bytes, tied_embeddings=False, **kw):
        if budget_bytes == int(2e9) and not raced:
            raced.append(True)
            # The explicit cap lands while the auto grower is planning.
            residency.tier_for(
                _fw(model_dir, hbm_pin_gb=0.5), names, False, None
            )
        return real_plan(path, layer_names, budget_bytes, tied_embeddings, **kw)

    monkeypatch.setattr(residency, "plan_residency", racing_plan)
    grown = residency.tier_for(_fw(model_dir, hbm_pin_gb=None), names, False, None)
    assert grown is seeded
    assert grown.plan.budget_bytes == int(5e8)  # the explicit cap held
    assert residency._PROCESS_BUDGET_EXPLICIT is True


def test_auto_grow_apply_revalidates_against_bigger_auto(model_dir, monkeypatch):
    # Two auto growers race: the one with the SMALLER budget can finish
    # planning last, and its install must skip — auto only ever grows the
    # budget, a property the pre-off-lock code enforced atomically.
    names = layer_names_for(4)
    real_plan = residency.plan_residency
    auto_budget = [int(1e9)]
    monkeypatch.setattr(
        FrameworkConfig,
        "effective_hbm_pin_bytes",
        lambda self, device=None, in_flight_bytes=0: (
            auto_budget[0]
            if self.hbm_pin_gb is None
            else int(self.hbm_pin_gb * 1e9)
        ),
    )
    seeded = residency.tier_for(_fw(model_dir, hbm_pin_gb=None), names, False, None)
    assert seeded is not None and seeded.plan.budget_bytes == int(1e9)
    auto_budget[0] = int(15e8)
    raced = []

    def racing_plan(path, layer_names, budget_bytes, tied_embeddings=False, **kw):
        if budget_bytes == int(15e8) and not raced:
            raced.append(True)
            # A bigger auto grower lands while this one is planning.
            auto_budget[0] = int(2e9)
            residency.tier_for(
                _fw(model_dir, hbm_pin_gb=None), names, False, None
            )
        return real_plan(path, layer_names, budget_bytes, tied_embeddings, **kw)

    monkeypatch.setattr(residency, "plan_residency", racing_plan)
    grown = residency.tier_for(_fw(model_dir, hbm_pin_gb=None), names, False, None)
    assert grown is seeded
    assert grown.plan.budget_bytes == int(2e9)  # the bigger grower won
    assert residency._PROCESS_BUDGET_EXPLICIT is False


def test_failed_explicit_resize_does_not_latch_explicit(model_dir, monkeypatch):
    # The explicit mark must land WITH the install: if the off-lock
    # re-plan fails (transient disk error stat'ing layer files), the cap
    # was never applied and the process must not be marked explicit —
    # that would permanently block auto growth at the stale budget.
    names = layer_names_for(4)
    real_plan = residency.plan_residency
    auto_budget = [int(1e9)]
    monkeypatch.setattr(
        FrameworkConfig,
        "effective_hbm_pin_bytes",
        lambda self, device=None, in_flight_bytes=0: (
            auto_budget[0]
            if self.hbm_pin_gb is None
            else int(self.hbm_pin_gb * 1e9)
        ),
    )
    seeded = residency.tier_for(_fw(model_dir, hbm_pin_gb=None), names, False, None)
    assert seeded is not None and seeded.plan.budget_bytes == int(1e9)

    def failing_plan(path, layer_names, budget_bytes, tied_embeddings=False, **kw):
        if budget_bytes == int(5e8):
            raise OSError("transient stat failure")
        return real_plan(path, layer_names, budget_bytes, tied_embeddings, **kw)

    monkeypatch.setattr(residency, "plan_residency", failing_plan)
    with pytest.raises(OSError):
        residency.tier_for(_fw(model_dir, hbm_pin_gb=0.5), names, False, None)
    assert residency._PROCESS_BUDGET_EXPLICIT is False
    assert seeded.plan.budget_bytes == int(1e9)  # untouched
    auto_budget[0] = int(2e9)
    grown = residency.tier_for(_fw(model_dir, hbm_pin_gb=None), names, False, None)
    assert grown is seeded
    assert grown.plan.budget_bytes == int(2e9)  # auto growth still alive


# ---------------------------------------------------------------------------
# Offline parity + exact byte accounting
# ---------------------------------------------------------------------------

def test_hbm_pin_zero_is_a_noop(model_dir, clean_scores):
    ex = StreamingExecutor(
        _fw(model_dir, hbm_pin_gb=0.0), tokenizer=FakeTokenizer()
    )
    got = ex(list(PROMPTS))
    assert ex._residency is None
    assert residency.process_tier() is None
    for k in ("pinned_bytes", "stream_bytes_saved", "pin_hits"):
        assert k not in ex.stats
    for g, w in zip(got, clean_scores):
        np.testing.assert_array_equal(g, w)


def test_full_pin_parity_and_zero_stream(model_dir, clean_scores):
    off = StreamingExecutor(_fw(model_dir), tokenizer=FakeTokenizer())
    off(list(PROMPTS))
    full_stream = off.stats["streamed_bytes"]
    ex = StreamingExecutor(
        _fw(model_dir, hbm_pin_gb=1.0), tokenizer=FakeTokenizer()
    )
    first = ex(list(PROMPTS))
    warm = ex(list(PROMPTS))
    s2 = dict(ex.stats)
    for g, w in zip(first, clean_scores):
        np.testing.assert_array_equal(g, w)
    for g, w in zip(warm, clean_scores):
        np.testing.assert_array_equal(g, w)
    # Warm sweep: zero streamed bytes; the saved bytes are EXACTLY what
    # the unpinned run streams, and the stats witness all of it.
    assert s2["streamed_bytes"] == 0.0
    assert s2["stream_bytes_saved"] == full_stream
    assert s2["pin_hits"] == 7.0
    assert s2["pinned_bytes"] > 0
    # HBM honesty: the reported peak can never sit below the pin tier —
    # on the stat-less CPU backend the tier's bytes ARE the floor figure.
    assert s2["peak_hbm_gb"] >= s2["pinned_bytes"] / 1e9


def test_partial_pin_streams_drop_by_exactly_pinned_bytes(
    model_dir, clean_scores
):
    off = StreamingExecutor(_fw(model_dir), tokenizer=FakeTokenizer())
    off(list(PROMPTS))
    full_stream = off.stats["streamed_bytes"]
    ex = StreamingExecutor(
        _fw(model_dir, hbm_pin_gb=_partial_budget_gb(model_dir)),
        tokenizer=FakeTokenizer(),
    )
    ex(list(PROMPTS))
    warm = ex(list(PROMPTS))
    s2 = dict(ex.stats)
    for g, w in zip(warm, clean_scores):
        np.testing.assert_array_equal(g, w)
    tier = residency.process_tier()
    assert tier.plan.pinned == (0, 1, 5, 6)
    assert s2["streamed_bytes"] > 0  # the unpinned blocks still stream
    assert s2["streamed_bytes"] + s2["stream_bytes_saved"] == full_stream
    assert s2["pin_hits"] == 4.0


def test_mid_shard_pin_splits_stacked_run_token_identical(model_dir):
    # layer_num_per_shard=2 stacks two decoders per scan; pinning norm
    # (idx 5) splits the (4, 5) shard into stream(4) + pin(5) — the merged
    # segment list must score token-identically to the unsplit run.
    want = StreamingExecutor(
        _fw(model_dir, layer_num_per_shard=2), tokenizer=FakeTokenizer()
    )(list(PROMPTS))
    ex = StreamingExecutor(
        _fw(
            model_dir,
            layer_num_per_shard=2,
            hbm_pin_gb=_partial_budget_gb(model_dir),
        ),
        tokenizer=FakeTokenizer(),
    )
    got = ex(list(PROMPTS))
    for g, w in zip(got, want):
        assert (g[:, 0].argmax(-1) == w[:, 0].argmax(-1)).all()
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-6)


def test_decode_parity_with_pins(model_dir):
    kw = dict(num_gen_token=3, decode_resident="off", decode_fused="off")
    sc_off, up_off = DecodeGenerator(
        _fw(model_dir, **kw), tokenizer=FakeTokenizer()
    )(list(PROMPTS))
    residency.reset_process_tier()
    gen = DecodeGenerator(
        _fw(model_dir, hbm_pin_gb=_partial_budget_gb(model_dir), **kw),
        tokenizer=FakeTokenizer(),
    )
    sc_on, up_on = gen(list(PROMPTS))
    for a, b in zip(sc_off, sc_on):
        np.testing.assert_array_equal(a, b)
    assert up_off == up_on
    # Multi-sweep decode is the tier's sweet spot: the prefill sweep seats
    # the four pins from its own stream, each step after it skips them.
    st = residency.process_tier().stats()
    assert st["pin_loads"] == 4 and st["pin_hits"] == 4 * 2


# ---------------------------------------------------------------------------
# Serving: parity, stats line, pins survive engine restarts
# ---------------------------------------------------------------------------

def test_serve_parity_stats_and_pin_survival(model_dir, clean_scores):
    cfg = _fw(model_dir, hbm_pin_gb=1.0, prefetch_depth=1)
    engine = ServeEngine(
        cfg,
        ServeConfig(max_wave_requests=2, default_max_new_tokens=1),
        tokenizer=FakeTokenizer(),
    )
    try:
        for _ in range(2):  # sweep 2+ is the warm regime
            reqs = [engine.submit(p, s) for p, s in PROMPTS]
            results = [r.future.result(timeout=300) for r in reqs]
            assert engine.error is None
            for res, want in zip(results, clean_scores):
                assert (
                    res.scores[:, 0].argmax(-1) == want[:, 0].argmax(-1)
                ).all()
    finally:
        engine.shutdown(drain=True)
    stats = engine.stats()
    # The warm serve stats line must show the tier working (acceptance
    # criterion: nonzero pinned_bytes AND stream_bytes_saved, top level).
    assert stats["pinned_bytes"] > 0, stats
    assert stats["stream_bytes_saved"] > 0, stats
    assert stats["residency"]["pin_hits"] > 0
    loads = residency.process_tier().stats()["pin_loads"]
    assert loads == 7
    # A second engine (source restart / process-internal redeploy) finds
    # the pins already resident: zero new pin loads.
    engine2 = ServeEngine(
        cfg,
        ServeConfig(max_wave_requests=2, default_max_new_tokens=1),
        tokenizer=FakeTokenizer(),
    )
    try:
        reqs = [engine2.submit(p, s) for p, s in PROMPTS]
        results = [r.future.result(timeout=300) for r in reqs]
        assert engine2.error is None
        for res, want in zip(results, clean_scores):
            assert (
                res.scores[:, 0].argmax(-1) == want[:, 0].argmax(-1)
            ).all()
    finally:
        engine2.shutdown(drain=True)
    assert residency.process_tier().stats()["pin_loads"] == loads


def test_serve_chaos_parity_with_pins(model_dir, clean_scores):
    # Explicit pin budget + explicit cache budget override chaos auto-off;
    # injected corruption on the (pin-time and streamed) loads must heal
    # without ever changing a token.
    cfg = _fw(
        model_dir,
        hbm_pin_gb=_partial_budget_gb(model_dir),
        prefetch_depth=1,
        faults=FaultConfig(
            enabled=True, seed=CHAOS_SEED, error_rate=0.2,
            sites=("corrupt_shard",),
        ),
    )
    engine = ServeEngine(
        cfg,
        ServeConfig(max_wave_requests=2, default_max_new_tokens=1),
        tokenizer=FakeTokenizer(),
    )
    try:
        for _ in range(4):
            reqs = [engine.submit(p, s) for p, s in PROMPTS]
            results = [r.future.result(timeout=300) for r in reqs]
            assert engine.error is None
            for res, want in zip(results, clean_scores):
                assert (
                    res.scores[:, 0].argmax(-1) == want[:, 0].argmax(-1)
                ).all()
            if engine.metrics.integrity.total("integrity_failures"):
                break
    finally:
        engine.shutdown(drain=True)
    tier = residency.process_tier()
    assert tier is not None and tier.stats()["pin_failures"] == 0


# ---------------------------------------------------------------------------
# Chaos at pin time: heal into a clean pin, or demote — never poison
# ---------------------------------------------------------------------------

def test_pin_time_corruption_rereads_and_heals(model_dir, clean_scores):
    # One injected bit-flip, guaranteed to land on a pin-time load (rate
    # 1.0, budget 1): the loader's retry re-reads clean bytes, the pin is
    # verified-clean, and every output matches the oracle.
    cfg = _fw(
        model_dir,
        hbm_pin_gb=1.0,
        faults=FaultConfig(
            enabled=True, seed=CHAOS_SEED, error_rate=1.0,
            sites=("corrupt_shard",), max_faults=1,
        ),
    )
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    got = ex(list(PROMPTS))
    for g, w in zip(got, clean_scores):
        np.testing.assert_array_equal(g, w)
    assert ex._integrity.total("reread_heals") >= 1
    tier = residency.process_tier()
    st = tier.stats()
    assert st["pin_failures"] == 0 and st["pinned_layers"] == 7


def test_persistent_pin_corruption_demotes_never_pins(model_dir):
    # Unlimited injected corruption: every re-read is dirty, so NOTHING
    # may be pinned (a poisoned resident layer would serve wrong bytes for
    # the process lifetime) and the run surfaces the typed quarantine
    # error through the normal stream path.
    cfg = _fw(
        model_dir,
        hbm_pin_gb=1.0,
        io_retry_attempts=2,
        faults=FaultConfig(
            enabled=True, seed=CHAOS_SEED, error_rate=1.0,
            sites=("corrupt_shard",),
        ),
    )
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    with pytest.raises(ShardCorruptError):
        ex(list(PROMPTS))
    st = residency.process_tier().stats()
    assert st["pinned_layers"] == 0
    assert st["pin_failures"] >= 1


# ---------------------------------------------------------------------------
# verify CLI: dry-run planner audit
# ---------------------------------------------------------------------------

def test_verify_cli_residency_dry_run(model_dir):
    from flexible_llm_sharding_tpu.cli import verify_main

    buf = io.StringIO()
    with redirect_stdout(buf):
        verify_main(["--model_path", model_dir, "--hbm_pin_gb", "1"])
    out = buf.getvalue()
    assert "residency plan @ 1.0 GB" in out
    assert "model.embed_tokens" in out and "lm_head" in out
    assert "per sweep" in out
    # JSON mode carries the structured plan.
    buf = io.StringIO()
    with redirect_stdout(buf):
        verify_main(
            ["--model_path", model_dir, "--hbm_pin_gb", "0.0001", "--json"]
        )
    rep = json.loads(buf.getvalue())["residency_plan"]
    assert rep["total_layers"] == 7
    assert rep["pinned_bytes"] <= int(0.0001 * 1e9)
    assert rep["stream_bytes_saved_per_sweep"] == rep["pinned_bytes"]
    # Nothing was loaded or pinned by the audit.
    assert residency.process_tier() is None
    with pytest.raises(SystemExit, match="requires --model_path"):
        verify_main(["--spill_dir", model_dir, "--hbm_pin_gb", "1"])


def test_half_budget_pins_the_planned_fraction_and_the_sweep_saves_it(
    model_dir,
):
    """A budget of half the model's bytes: the planner's ``pinned_fraction``
    is its pinned bytes over the model's, and a warm sweep's own counters
    show the link spared exactly that share (zero saved bytes beside a
    nonzero plan would be a tier that never engaged)."""
    names = layer_names_for(4)
    total = sum(_sizes(model_dir).values())
    plan = residency.plan_residency(model_dir, names, total // 2)
    assert plan.total_bytes_est == total
    assert 0 < plan.pinned_bytes_est <= total // 2
    assert plan.pinned_fraction == plan.pinned_bytes_est / total
    assert 0.25 < plan.pinned_fraction <= 0.5

    off = StreamingExecutor(_fw(model_dir), tokenizer=FakeTokenizer())
    off(list(PROMPTS))
    full_stream = off.stats["streamed_bytes"]
    ex = StreamingExecutor(
        _fw(model_dir, hbm_pin_gb=(total // 2) / 1e9),
        tokenizer=FakeTokenizer(),
    )
    ex(list(PROMPTS))  # seats the pins
    ex(list(PROMPTS))
    s2 = dict(ex.stats)
    assert residency.process_tier().plan.pinned == plan.pinned
    assert s2["pinned_bytes"] > 0
    assert s2["streamed_bytes"] + s2["stream_bytes_saved"] == full_stream
    # The plan counts file bytes, the sweep tensor bytes: headers apart.
    assert s2["stream_bytes_saved"] / full_stream == pytest.approx(
        plan.pinned_fraction, rel=0.01
    )


def test_first_seat_wins_and_is_counted_once(model_dir):
    """Two sources in their seating sweep can place the same layer (each
    from its own stream). The earlier seat must win: one pin_load, device
    bytes counted exactly once, the seated copy handed to both — and the
    resident draft's load-on-first-request path (``segments``) finds it."""
    from flexible_llm_sharding_tpu.runtime.executor import (
        _HostShardLoader,
        _place,
    )
    from flexible_llm_sharding_tpu.runtime.residency import (
        DeviceResidencyTier,
        _placed_device_nbytes,
        placement_key,
        plan_residency,
    )

    names = layer_names_for(4)
    plan = plan_residency(model_dir, names, 10**12, False)
    tier = DeviceResidencyTier(model_dir, names, plan)
    dev = jax.devices()[0]
    loader = _HostShardLoader(model_dir, names, np.float32)
    host = loader.build_host_shard((0,))
    first = _place(host, dev, np_dtype=np.float32)
    dup = _place(host, dev, np_dtype=np.float32)
    assert tier.seat_state(0, (dev,)) == "unseated"
    assert tier.seat(0, dev, host, first) is first
    assert tier.seat(0, dev, host, dup) is first  # the duplicate is dropped
    assert tier.seat_state(0, (dev,)) == "seated"
    assert tier.segments(0, dev, loader) is first  # no second load
    key = placement_key(dev)
    with tier._lock:
        dev_bytes = tier._dev_bytes[key]
    assert tier.pin_loads == 1
    assert dev_bytes == _placed_device_nbytes(first)
    # A demoted layer is never seated, whoever brings it.
    tier.demote(1)
    placed = _place(loader.build_host_shard((1,)), dev, np_dtype=np.float32)
    assert tier.seat(1, dev, host, placed) is placed
    assert tier.seated(1, dev) is None
    assert tier.seat_state(1, (dev,)) == "failed"
    assert tier.stats()["pin_failures"] == 1


# ---------------------------------------------------------------------------
# Seated from the first sweep's own stream (PR 26)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefetch", [0, 2])
@pytest.mark.parametrize("lnps", [1, 2])
def test_seating_sweep_streams_once_then_only_the_remainder(
    model_dir, lnps, prefetch
):
    """The first source of a process streams every layer as an untiered
    run does, and keeps what it placed of the planned layers: no second
    read, check or upload of a pin. The next source streams the remainder
    only. Scores are the untiered run's bit for bit in both sweeps, also
    where a pin splits a stacked run (``layer_num_per_shard`` 2)."""
    kw = dict(layer_num_per_shard=lnps, prefetch_depth=prefetch)
    off = StreamingExecutor(
        _fw(model_dir, hbm_pin_gb=0.0, **kw), tokenizer=FakeTokenizer()
    )
    want = off(list(PROMPTS))
    full = off.stats["streamed_bytes"]
    ex = StreamingExecutor(
        _fw(model_dir, hbm_pin_gb=_partial_budget_gb(model_dir), **kw),
        tokenizer=FakeTokenizer(),
    )
    seating = ex(list(PROMPTS))
    s1 = dict(ex.stats)
    tier = residency.process_tier()
    assert tier.plan.pinned == (0, 1, 5, 6)
    # The seating sweep: every byte crossed once, nothing was skipped,
    # and the four planned layers are resident at its end.
    assert s1["streamed_bytes"] == full
    assert "pin_hits" not in s1 and "stream_bytes_saved" not in s1
    assert tier.stats()["pin_loads"] == 4 and tier.stats()["pinned_layers"] == 4
    seated = ex(list(PROMPTS))
    s2 = dict(ex.stats)
    assert s2["pin_hits"] == 4.0
    assert s2["streamed_bytes"] + s2["stream_bytes_saved"] == full
    assert 0 < s2["streamed_bytes"] < full
    assert tier.stats()["pin_loads"] == 4  # nothing loaded a second time
    for got in (seating, seated):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    # The sweep's own account carries the engagement.
    from flexible_llm_sharding_tpu.runtime.executor import process_sweep_log

    first, second = process_sweep_log()[-2:]
    assert first["pin_hits"] == 0 and second["pin_hits"] == 4
    assert first["pinned_bytes"] == second["pinned_bytes"] == s2["pinned_bytes"]
    assert second["upload_bytes"] == s2["streamed_bytes"]


def _host_nbytes(model_dir, *idxs) -> int:
    """What the loader builds (and the link carries) for these layers."""
    from flexible_llm_sharding_tpu.runtime.executor import _HostShardLoader

    loader = _HostShardLoader(model_dir, layer_names_for(4), np.float32)
    return sum(residency._tree_nbytes(loader.build_host_shard((i,))) for i in idxs)


def _placement_fault(monkeypatch):
    """``_place`` fails once, on the embedding (no room on the chip)."""
    from flexible_llm_sharding_tpu.runtime import executor

    real, fired = executor._place, []

    def place(segments, device, np_dtype=None):
        if not fired and segments and segments[0][0] == "embed":
            fired.append(True)
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return real(segments, device, np_dtype=np_dtype)

    monkeypatch.setattr(executor, "_place", place)
    return {}, RuntimeError


def _read_fault(monkeypatch):
    """The first layer read (the embedding's) fails past its retries."""
    faults = FaultConfig(
        enabled=True, seed=CHAOS_SEED, error_rate=1.0,
        sites=("shard_read",), max_faults=1,
    )
    from flexible_llm_sharding_tpu.faults.retry import ShardLoadError

    return dict(faults=faults, io_retry_attempts=1), ShardLoadError


@pytest.mark.parametrize("fault", [_read_fault, _placement_fault])
def test_failed_seat_demotes_to_streaming(model_dir, clean_scores, monkeypatch, fault):
    """A planned layer whose load or placement fails in the seating sweep
    is demoted: the sweep raises the stream path's own error, and every
    later source streams that layer and pins the rest."""
    kw, error = fault(monkeypatch)
    ex = StreamingExecutor(
        _fw(model_dir, hbm_pin_gb=1.0, **kw), tokenizer=FakeTokenizer()
    )
    with pytest.raises(error):
        ex(list(PROMPTS))
    tier = residency.process_tier()
    assert tier.stats()["pin_failures"] == 1
    assert tier.seat_state(0, (None,)) == "failed"
    ex(list(PROMPTS))  # seats what the aborted sweep had not reached
    got = ex(list(PROMPTS))
    for g, w in zip(got, clean_scores):
        np.testing.assert_array_equal(g, w)
    assert ex.stats["streamed_bytes"] == _host_nbytes(model_dir, 0)  # it alone
    assert ex.stats["pin_hits"] == 6.0
    assert tier.stats()["pinned_layers"] == 6


def _sized_dir(tmp_path, n_blocks, block=1000):
    """A model dir of empty files with the planner's sizes only."""
    names = layer_names_for(n_blocks)
    for name in names:
        size = block if name.startswith("model.layers.") else 10
        with open(tmp_path / f"{name}.safetensors", "wb") as f:
            f.write(b"\0" * size)
    return str(tmp_path), names


@pytest.mark.parametrize(
    "n_blocks,n_pinned", [(14, 8), (13, 7), (4, 1), (5, 4), (3, 3), (3, 0)]
)
def test_plan_takes_the_first_of_equal_sized_blocks(tmp_path, n_blocks, n_pinned):
    """Of equal-sized blocks the budget covers in part, the plan takes the
    first N, and a grown budget only ever adds to them. (PR 26 measured a
    plan spread over the depth on the chip: weight uploads and compute
    alternate there, the resident head is where compute runs unhindered,
    and the spread read 5-7% slower; PERF.md section 6.)"""
    path, names = _sized_dir(tmp_path, n_blocks)
    plan = residency.plan_residency(path, names, 30 + 1000 * n_pinned + 999)
    assert plan.pinned == (
        0, *range(1, n_pinned + 1), n_blocks + 1, n_blocks + 2
    )
    assert plan.pinned_bytes_est == 30 + 1000 * n_pinned
    assert plan.skipped == tuple(range(n_pinned + 1, n_blocks + 1))
    grown = residency.plan_residency(path, names, 30 + 1000 * n_blocks)
    assert set(plan.pinned) <= set(grown.pinned)


def test_source_walks_resident_shards_and_uploads_only_the_rest(model_dir):
    """A source over a tier that holds layers 0, 1, 3, 5, 6 uploads layers 2
    and 4 and nothing else, whatever the consumer's pace; the shards it
    hands over are the full segment lists in order."""
    from flexible_llm_sharding_tpu.runtime.executor import ShardWeightSource

    names = layer_names_for(4)
    sizes = _sizes(model_dir)
    plan = residency.ResidencyPlan(
        budget_bytes=1 << 40,
        pinned=(0, 1, 3, 5, 6),
        layer_bytes=tuple(sizes.items()),
        skipped=(2, 4),
    )
    tier = residency.DeviceResidencyTier(model_dir, names, plan)
    shards = [(i,) for i in range(7)]

    def source():
        return ShardWeightSource(
            model_dir, names, shards, np.float32, prefetch_depth=1,
            residency=tier,
        )

    seating = source()
    try:
        first = [(idxs, [k for k, _ in segs]) for idxs, segs in seating]
    finally:
        seating.close()
    assert tier.stats()["pin_loads"] == 5 and seating.pin_hits == 0
    src = source()
    try:
        second = [(idxs, [k for k, _ in segs]) for idxs, segs in src]
    finally:
        src.close()
    assert first == second and [idxs for idxs, _ in second] == shards
    assert src.pin_hits == 5
    assert src.upload_bytes == src.bytes_loaded == _host_nbytes(model_dir, 2, 4)


# ---------------------------------------------------------------------------
# The default (PR 26): auto, which the CPU backend and chaos resolve to off
# ---------------------------------------------------------------------------

def _batch_cfg(argv):
    from flexible_llm_sharding_tpu import cli

    args = cli.build_parser().parse_args(
        [*argv, "--prompt_pickle", "-", "--output_file", "-"]
    )
    return args, cli.config_from_args(args)


def _serve_cfg(argv):
    from flexible_llm_sharding_tpu import cli

    args = cli.build_serve_parser().parse_args(argv)
    return args, FrameworkConfig(
        model_path=args.model_path,
        hbm_pin_gb=args.hbm_pin_gb,
        faults=cli._fault_config_from_args(args),
    )


@pytest.mark.parametrize("chaos", [False, True])
@pytest.mark.parametrize("build", [_batch_cfg, _serve_cfg, None])
def test_default_budget_is_auto_and_off_where_it_must_be(model_dir, build, chaos):
    """The dataclass's and both parsers' default is auto; the CPU backend
    (no HBM to read) and a chaos run resolve it to 0, so no tier exists."""
    if build is None:  # the dataclass itself
        cfg = FrameworkConfig(
            model_path=model_dir, faults=FaultConfig(enabled=chaos, seed=1)
        )
    else:
        args, cfg = build(
            ["--model_path", model_dir, *(["--chaos"] if chaos else [])]
        )
        assert args.hbm_pin_gb is None
    assert cfg.hbm_pin_gb is None and cfg.faults.enabled is chaos
    assert cfg.effective_hbm_pin_bytes() == 0
    assert residency.tier_for(cfg, layer_names_for(4), False, None) is None
    assert residency.process_tier() is None


def test_auto_budget_reads_the_chip_behind_a_placement(monkeypatch):
    """Auto asks a device for its memory, so a target that is a placement
    resolves to a chip first; and what the tier itself holds there is the
    budget's to spend, not in use by someone else."""
    from flexible_llm_sharding_tpu.utils import metrics

    class _Chip:
        platform, device_kind, id = "tpu", "TPU v5 lite", 0

        def memory_stats(self):
            return {"bytes_limit": 16_000, "bytes_in_use": self.in_use}

    chip = _Chip()
    chip.in_use = 1_000
    mesh = type("M", (), {"devices": np.array([chip], dtype=object)})()
    placement = type("P", (), {"mesh": mesh, "segment_target": None})()
    want = 16_000 - 1_000 - int(residency.ACTIVATION_HEADROOM_FRACTION * 16_000)
    for target in (chip, mesh, placement):
        assert residency.auto_pin_budget_bytes(target) == want

    class _Tier:
        def pinned_device_bytes(self, device=None):
            return 9_000

    chip.in_use = 10_000  # of which the tier's own pins are 9,000
    monkeypatch.setattr(residency, "process_tier", lambda: _Tier())
    assert residency.auto_pin_budget_bytes(placement) == want


@pytest.mark.parametrize(
    "in_flight,headroom",
    [(0, 5_600), (4_000, 5_600), (4_800, 5_600), (6_000, 6_800), (20_000, 16_000)],
)
def test_auto_headroom_covers_what_the_source_holds_in_flight(in_flight, headroom):
    """The headroom is 35% of the chip, or the source's own in-flight
    shards plus 5% where that is more; never a negative budget."""

    class _Chip:
        platform, device_kind, id = "tpu", "TPU v5 lite", 0

        def memory_stats(self):
            return {"bytes_limit": 16_000, "bytes_in_use": 0}

    assert residency.auto_pin_budget_bytes(_Chip(), in_flight) == 16_000 - headroom


@pytest.mark.parametrize("lnps,depth", [(1, 0), (1, 2), (2, 1), (7, 3)])
def test_in_flight_bytes_is_depth_plus_two_largest_shards(model_dir, lnps, depth):
    from flexible_llm_sharding_tpu.parallel.planner import plan_shards_dp

    sizes = _sizes(model_dir)
    cfg = _fw(model_dir, layer_num_per_shard=lnps, prefetch_depth=depth)
    largest = max(
        sum(sizes[i] for i in s) for s in plan_shards_dp(7, lnps).shards
    )
    assert residency.in_flight_bytes(cfg, layer_names_for(4), False) == (
        max(1, depth) + 2
    ) * largest


def test_first_seat_holds_under_contention(model_dir):
    """More threads than cores race to seat one layer and to demote
    another: one seat wins and is counted once, one failure is counted."""
    import sys
    import threading

    names = layer_names_for(4)
    plan = residency.plan_residency(model_dir, names, 10**12, False)
    tier = residency.DeviceResidencyTier(model_dir, names, plan)
    winners, n = [], 4 * (os.cpu_count() or 4)
    start = threading.Barrier(n)

    def race(k):
        start.wait(timeout=30)
        for _ in range(50):
            winners.append(tier.seat(2, None, [], [("decoders", k)]))
            tier.demote(3)
            assert tier.seat_state(2, (None,)) == "seated"

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=race, args=(k,)) for k in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len(winners) == 50 * n and len({id(w) for w in winners}) == 1
    st = tier.stats()
    assert st["pin_loads"] == 1 and st["pin_failures"] == 1


# ---------------------------------------------------------------------------
# pinned_host copies and the upload-counting bound beside the tier (PR 30)
# ---------------------------------------------------------------------------

def test_resident_layers_are_never_copied_to_pinned_host(model_dir, clean_scores):
    """The host cache's pinned_host copies are for the layers a chip streams
    every sweep: a layer of the tier's plan is uploaded once and never
    copied, seated or not; the others all are, and the scores do not move."""
    from flexible_llm_sharding_tpu.runtime import executor as executor_mod

    dev = jax.devices()[0]
    cfg = _fw(
        model_dir, host_cache_gb=1.0, prefetch_depth=2,
        hbm_pin_gb=_partial_budget_gb(model_dir),
    )
    for sweep in range(3):
        out = StreamingExecutor(cfg, device=dev, tokenizer=FakeTokenizer())(list(PROMPTS))
        assert hostcache.process_cache().pin_wait()
        for a, b in zip(clean_scores, out):
            np.testing.assert_array_equal(a, b)
        rec = executor_mod.process_sweep_log()[-1]
        # The seating sweep built the streamed layers first, so their copies
        # were under way before its own uploads of them (made or not yet).
        assert sweep == 0 or rec["upload_pinned_bytes"] == rec["upload_bytes"]
    planned = set(residency.process_tier().plan.pinned)
    assert planned == {0, 1, 5, 6}
    cache = hostcache.process_cache()
    with cache._lock:
        held = {key[-1]: entry[0] for key, entry in cache._entries.items()}
    # The budget has room for everything: the seats are cached too (NumPy,
    # for a restart or a re-seat), only the streamed layers are copied.
    assert set(held) == {(i,) for i in range(7)}
    for idxs, tree in held.items():
        assert (executor_mod._on_pinned_host(tree) is None) == (idxs[0] in planned)
    sizes = _sizes(model_dir)
    streamed = sum(sizes[i] for i in range(7) if i not in planned)
    s = cache.stats()
    assert s["pinned_host_copies"] == 3
    assert rec["upload_bytes"] == s["pinned_host_bytes"]
    assert rec["pin_hits"] == 4 and 0 < rec["upload_bytes"] <= streamed


@pytest.mark.parametrize("room", ["for_everything", "for_the_streamed_layers"])
def test_re_seat_after_a_release_reads_what_the_cache_kept(model_dir, clean_scores, room):
    """A seat's tree is cached where the budget has room and pushes nothing
    out where it has not. With room for everything a re-seat (the tier was
    dropped, the process lives on) reads no file again: 7 hits, no miss, no
    verify. With room for the streamed layers and one seat, those stay (the
    streamed ones pinned) and the other three seats are read again."""
    from flexible_llm_sharding_tpu.runtime import executor as executor_mod

    dev = jax.devices()[0]
    planned = {0, 1, 5, 6}
    kw = dict(prefetch_depth=2, hbm_pin_gb=_partial_budget_gb(model_dir))
    StreamingExecutor(
        _fw(model_dir, host_cache_gb=1.0, **kw), device=dev, tokenizer=FakeTokenizer()
    )(list(PROMPTS))
    cache = hostcache.process_cache()
    assert cache.pin_wait()
    with cache._lock:
        nbytes = {key[-1][0]: entry[1] for key, entry in cache._entries.items()}
    streamed = sum(n for i, n in nbytes.items() if i not in planned)
    if room == "for_the_streamed_layers":
        # A fresh process with a smaller budget: the smallest seat fits beside
        # the streamed layers, no other.
        residency.reset_process_tier()
        hostcache.reset_process_cache()
        iman.reset_verdicts()
        gb = (streamed + min(nbytes[i] for i in planned) + 1) / 1e9
        cfg = _fw(model_dir, host_cache_gb=gb, **kw)
        StreamingExecutor(cfg, device=dev, tokenizer=FakeTokenizer())(list(PROMPTS))
        cache = hostcache.process_cache()
        assert cache.pin_wait()
        s = cache.stats()
        assert s["evictions"] == 0 and s["pinned_host_bytes"] == streamed
        assert s["entries"] == 4
    else:
        cfg = _fw(model_dir, host_cache_gb=1.0, **kw)
    residency.reset_process_tier()
    s0, v0 = cache.stats(), iman.verdict_stats()["full_verifies"]
    out = StreamingExecutor(cfg, device=dev, tokenizer=FakeTokenizer())(list(PROMPTS))
    for a, b in zip(clean_scores, out):
        np.testing.assert_array_equal(a, b)
    s = cache.stats()
    hits, misses = s["hits"] - s0["hits"], s["misses"] - s0["misses"]
    verifies = iman.verdict_stats()["full_verifies"] - v0
    # The pass ahead and the sweep's own builds both hit the three streamed layers.
    if room == "for_everything":
        assert (hits, misses, verifies) == (3 + 7, 0, 0)
    else:
        assert (hits, misses, verifies) == (3 + 3 + 1, 3, 0)  # the verdict cache spares the crc
        assert s["evictions"] == 0 and s["pinned_host_bytes"] == streamed
    rec = executor_mod.process_sweep_log()[-1]
    assert rec["upload_pinned_bytes"] == streamed < rec["upload_bytes"]
    assert residency.process_tier().stats()["pinned_layers"] == 4


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_in_flight_bytes_is_what_the_source_can_hold(model_dir, depth):
    """The queue's places, the shard in the producer's hand and the one at
    the consumer: the count of streamed shards the tier's budget leaves
    room for (tests/test_executor.py holds the count itself)."""
    from flexible_llm_sharding_tpu.runtime.executor import ShardWeightSource

    names = layer_names_for(4)
    cfg = _fw(model_dir, prefetch_depth=depth)
    source = ShardWeightSource(
        model_dir, names, [(i,) for i in range(7)], np.float32,
        prefetch_depth=cfg.effective_prefetch_depth(),
    )
    try:
        can_hold = source._q.maxsize + 1 + 1
    finally:
        source.close()
    assert can_hold == depth + 2
    assert residency.in_flight_bytes(cfg, names, False) == can_hold * max(
        _sizes(model_dir).values()
    )
