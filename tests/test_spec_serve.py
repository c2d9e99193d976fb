"""Speculative decoding on the SERVING path (docs/speculative.md).

The contract under test: ``ServeConfig.speculative_k > 0`` changes only
how many weight sweeps serving takes, never what it serves — every
scenario pins the spec-on output token-identical (strings, token ids,
and per-step distributions) to the spec-off / offline oracle, across
plain waves, mixed budgets with staggered finishes, prefix-coalesced
waves, preempt-then-resume, and fleet re-dispatch. The draft economy
must be observable (fls_spec_* counter family, spec_draft/spec_verify
trace instants), and the degenerate zero-acceptance case must cost no
extra sweeps over the plain path.
"""

import os
import re
import time

import numpy as np
import pytest

import jax

from flexible_llm_sharding_tpu.config import (
    FaultConfig,
    FrameworkConfig,
    SchedConfig,
    ServeConfig,
)
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.runtime import decode as decode_mod
from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator
from flexible_llm_sharding_tpu.serve import ReplicaFleet, ServeEngine
from flexible_llm_sharding_tpu.serve.request import RequestStatus
from flexible_llm_sharding_tpu.utils.checkpoint import save_params

from tests.fake_tokenizer import FakeTokenizer

CHAOS_SEED = int(os.environ.get("FLS_CHAOS_SEED", "1234"))

# Uniform 2-suffix prompts (one jit shape family per block); the first
# two are repetition-heavy — prompt-lookup's home turf — so spec runs
# show real acceptance, while the rest exercise the hostile regime.
PROMPTS = [
    (
        "the cat sat on the mat the cat sat on the mat",
        (" the cat sat", " on the mat"),
    ),
    ("alpha beta gamma alpha beta gamma alpha", (" beta gamma alpha", " delta")),
    ("The capital of France", (" is Paris", " is Rome")),
    ("Two plus two equals", (" four", " five")),
]

N_GEN = 4
SPEC_K = 4


@pytest.fixture(scope="module")
def model_dir(tiny_cfg, tmp_path_factory):
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    d = tmp_path_factory.mktemp("tiny_model_spec_serve")
    save_params(jax.tree.map(np.asarray, params), str(d), tiny_cfg)
    return str(d)


def _fw(model_dir, **kw) -> FrameworkConfig:
    base = dict(
        model_path=model_dir,
        layer_num_per_shard=1,
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
        num_gen_token=N_GEN,
    )
    base.update(kw)
    return FrameworkConfig(**base)


@pytest.fixture(scope="module")
def offline_oracle(model_dir):
    """Fault-free offline batch outputs for PROMPTS[:2] at N_GEN (the
    parity target serve already pins against; spec-on must match it too).
    Two prompts keep the module inside the tier-1 wall budget — the
    full-set parity rides test_serve/test_sched's existing pins."""
    return DecodeGenerator(
        _fw(model_dir), tokenizer=FakeTokenizer()
    )(list(PROMPTS[:2]))


@pytest.fixture
def process_tracer():
    from flexible_llm_sharding_tpu.obs import trace as obs_trace

    t = obs_trace.TRACER
    was = t.enabled
    t.clear()
    t.enable()
    yield t
    t.disable()
    t.clear()
    if was:
        t.enable()


def _serve(model_dir, spec_k, **serve_kw):
    base = dict(default_max_new_tokens=N_GEN, speculative_k=spec_k)
    base.update(serve_kw)
    return ServeEngine(
        _fw(model_dir), ServeConfig(**base), tokenizer=FakeTokenizer()
    )


def _context_base_len(prompt) -> int:
    """Length of a one-suffix prompt's draft context before any token is
    generated (prefix + the suffix's real tokens): what a scripted draft
    source subtracts to know how many tokens are done."""
    from flexible_llm_sharding_tpu.runtime.tokenization import PromptTokenizer

    tp = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)(*prompt)
    return tp.prefix_len + int(tp.suffix_eos[0]) + 1


def _assert_same_result(res, want_scores, want_updated):
    assert res.updated == want_updated
    assert (res.tokens == want_scores.argmax(-1)).all()
    np.testing.assert_allclose(res.scores, want_scores, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Single wave + counters
# ---------------------------------------------------------------------------

def test_spec_serve_single_wave_token_identical(model_dir, process_tracer):
    """One wave under --speculative_k: token-identical to the spec-off
    serve path (itself pinned to the offline oracle in test_serve.py),
    FEWER sweeps than plain needed (acceptance really amortized weight
    streams), the fls_spec_* family scrapeable with nonzero acceptance,
    and the draft/verify instants on the timeline."""
    n_gen = 6  # enough budget for the generated cycles to latch
    # The repetition-heavy pair only: a wave advances at its SLOWEST
    # suffix, so the sweep-saving assertion needs every member to accept
    # at least once (the hostile prompts ride the other tests' waves).
    prompts = PROMPTS[:2]

    def run(spec_k):
        # start=False: all requests admit at ONE boundary, so the sweep
        # counts of the two runs are deterministic and comparable.
        engine = ServeEngine(
            _fw(model_dir),
            ServeConfig(
                max_wave_requests=len(prompts),
                default_max_new_tokens=n_gen,
                speculative_k=spec_k,
            ),
            tokenizer=FakeTokenizer(),
            start=False,
        )
        try:
            reqs = [engine.submit(p, s) for p, s in prompts]
            engine.start()
            out = [r.future.result(timeout=300) for r in reqs]
            text = engine.metrics.registry.prometheus_text()
        finally:
            engine.shutdown(drain=True)
        assert engine.error is None
        return out, engine.stats(), text

    plain, plain_stats, _ = run(0)
    results, stats, text = run(SPEC_K)
    for res, p in zip(results, plain):
        _assert_same_result(res, p.scores, p.updated)
    # The repetitive workload accepts: strictly fewer weight sweeps than
    # plain serving's prefill + (n_gen - 1) one-token sweeps.
    assert plain_stats["sweeps"] == n_gen
    assert stats["sweeps"] < plain_stats["sweeps"]
    assert stats["tokens_emitted"] == len(prompts) * n_gen
    spec = stats["spec"]
    assert spec["accepted_tokens"] > 0
    assert spec["drafted_tokens"] >= spec["accepted_tokens"]
    assert (
        spec["rejected_tokens"]
        == spec["drafted_tokens"] - spec["accepted_tokens"]
    )
    assert spec["acceptance_rate"] > 0
    assert spec["extra_tokens_per_sweep"] > 0
    assert re.search(r"^fls_spec_accepted_tokens [1-9]", text, re.M)
    assert re.search(r"^fls_spec_drafted_tokens [1-9]", text, re.M)
    assert re.search(r"^fls_spec_rejected_tokens \d", text, re.M)
    spans = process_tracer.snapshot()
    drafts = [s for s in spans if s["name"] == "spec_draft"]
    verifies = [s for s in spans if s["name"] == "spec_verify"]
    assert drafts and drafts[0]["cat"] == "spec" and "wave_id" in drafts[0]
    assert verifies and verifies[0]["cat"] == "spec"
    assert sum(s["accepted"] for s in verifies) == spec["accepted_tokens"]


def test_spec_serve_counters_preseeded_when_off(model_dir):
    """speculative_k=0 keeps the plain path but the fls_spec_* family is
    still scrapeable at zero — "no drafts" vs "not exported"."""
    engine = _serve(model_dir, 0)
    try:
        engine.submit(*PROMPTS[2]).future.result(timeout=300)
    finally:
        engine.shutdown(drain=True)
    assert engine.error is None
    text = engine.metrics.registry.prometheus_text()
    assert re.search(r"^fls_spec_accepted_tokens 0$", text, re.M)
    assert re.search(r"^fls_spec_drafted_tokens 0$", text, re.M)


# ---------------------------------------------------------------------------
# Multi-wave, staggered finishes, mixed budgets
# ---------------------------------------------------------------------------

def test_spec_serve_multi_wave_staggered_finishes(model_dir):
    """Mixed budgets in one spec wave plus a late wave joining mid-run:
    the short request resolves early (its suffixes stop at their own
    budget — an accepted run crossing max_new_tokens discards nothing),
    and every stream matches the spec-off serve path exactly."""
    def run(spec_k):
        engine = _serve(model_dir, spec_k, max_wave_requests=2)
        try:
            short = engine.submit(*PROMPTS[0], max_new_tokens=2)
            long = engine.submit(*PROMPTS[1], max_new_tokens=6)
            deadline = time.monotonic() + 120
            while engine.metrics.counter("prefills") < 1:
                assert time.monotonic() < deadline, "first wave stuck"
                time.sleep(0.005)
            late = engine.submit(*PROMPTS[2], max_new_tokens=4)
            out = [
                r.future.result(timeout=300) for r in (short, long, late)
            ]
        finally:
            engine.shutdown(drain=True)
        assert engine.error is None
        return out, engine.stats()

    plain, plain_stats = run(0)
    spec, spec_stats = run(SPEC_K)
    for p, s in zip(plain, spec):
        _assert_same_result(s, p.scores, p.updated)
    # The short request really finished early in the spec run too.
    assert spec[0].tokens.shape[1] == 2 and spec[1].tokens.shape[1] == 6
    # Acceptance can only remove sweeps, never add them.
    assert spec_stats["sweeps"] <= plain_stats["sweeps"]


# ---------------------------------------------------------------------------
# Zero-acceptance degenerate case
# ---------------------------------------------------------------------------

def test_spec_serve_zero_acceptance_costs_no_extra_sweeps(
    model_dir, monkeypatch
):
    """An adversarial draft source that always proposes the WRONG next
    token (built from the oracle chain) forces acceptance to zero: the
    spec run must degrade to exactly the plain path's sweep count — a
    verify pass always emits its position-0 token, so rejected drafts
    cost nothing but the wasted draft slots — and stay token-identical."""
    prompt = (PROMPTS[0][0], (PROMPTS[0][1][0],))  # one suffix: no
    # context ambiguity for the anti-oracle below
    plain_engine = _serve(model_dir, 0)
    try:
        plain = plain_engine.submit(*prompt).future.result(timeout=300)
    finally:
        plain_engine.shutdown(drain=True)
    plain_sweeps = plain_engine.metrics.counter("sweeps")
    chain = [int(t) for t in plain.tokens[0]]

    base_len = _context_base_len(prompt)

    def anti_draft(context_ids, k, ngram=2, corpus=None):
        # done tokens so far (incl. prefill's); the next picks are
        # chain[done:], so chain[done + j] + 1 can never be accepted.
        done = len(context_ids) - base_len
        return np.asarray(
            [
                (chain[min(done + j, len(chain) - 1)] + 1) % 256
                for j in range(k)
            ],
            np.int64,
        )

    monkeypatch.setattr(decode_mod, "propose_draft", anti_draft)
    engine = _serve(model_dir, SPEC_K)
    try:
        res = engine.submit(*prompt).future.result(timeout=300)
    finally:
        engine.shutdown(drain=True)
    assert engine.error is None
    _assert_same_result(res, plain.scores, plain.updated)
    assert engine.metrics.counter("sweeps") == plain_sweeps
    spec = engine.stats()["spec"]
    assert spec["accepted_tokens"] == 0
    assert spec["drafted_tokens"] > 0
    assert spec["rejected_tokens"] == spec["drafted_tokens"]


def test_spec_serve_perfect_draft_emits_k_plus_one_tokens_a_sweep(
    model_dir, monkeypatch
):
    """The mirror case: a draft source that replays the plain run's own
    greedy chain is accepted in full, so two identical requests of 8 new
    tokens at k = 7 take the prefill sweep and ONE verify sweep where plain
    serving takes 8: 16 tokens over 2 sweeps. A verify pass that stopped
    engaging would read 8 sweeps again, whatever the machine."""
    n_gen, k = 8, 7
    prompt = (PROMPTS[0][0], (PROMPTS[0][1][0],))

    def run(spec_k):
        engine = ServeEngine(
            _fw(model_dir, num_gen_token=n_gen),
            ServeConfig(
                max_wave_requests=2, default_max_new_tokens=n_gen,
                speculative_k=spec_k,
            ),
            tokenizer=FakeTokenizer(),
            start=False,  # both requests admit at one boundary
        )
        try:
            reqs = [engine.submit(*prompt) for _ in range(2)]
            engine.start()
            out = [r.future.result(timeout=300) for r in reqs]
        finally:
            engine.shutdown(drain=True)
        assert engine.error is None
        return out, engine.stats()

    plain, plain_stats = run(0)
    chain = [int(t) for t in plain[0].tokens[0]]

    base_len = _context_base_len(prompt)

    def replay_draft(context_ids, k, ngram=2, corpus=None):
        done = len(context_ids) - base_len  # tokens generated so far
        d = chain[done : done + k]
        return np.asarray(d + [chain[-1]] * (k - len(d)), np.int64)

    monkeypatch.setattr(decode_mod, "propose_draft", replay_draft)
    spec, spec_stats = run(k)
    for res, p in zip(spec, plain):
        _assert_same_result(res, p.scores, p.updated)
    assert plain_stats["sweeps"] == n_gen
    assert spec_stats["sweeps"] == 2
    assert spec_stats["tokens_emitted"] == 2 * n_gen
    assert spec_stats["spec"]["acceptance_rate"] == 1.0


# ---------------------------------------------------------------------------
# Scheduler interactions: coalesced wave, preempt-then-resume
# ---------------------------------------------------------------------------

def test_spec_serve_coalesced_wave_token_identical(model_dir):
    """Prefix-coalesced admission + speculation: three same-prefix
    requests share ONE prefill, then draft per-suffix — outputs match
    the per-request offline oracle exactly."""
    prefix = "repeat repeat repeat repeat repeat"
    suffix_sets = [
        (" repeat repeat", " again again"),
        (" red blue", " blue red"),
        (" one two", " two one"),
    ]
    oracle_scores, oracle_updated = DecodeGenerator(
        _fw(model_dir), tokenizer=FakeTokenizer()
    )([(prefix, s) for s in suffix_sets])
    engine = ServeEngine(
        _fw(model_dir),
        ServeConfig(
            max_wave_requests=4,
            default_max_new_tokens=N_GEN,
            speculative_k=SPEC_K,
            sched=SchedConfig(enabled=True),
        ),
        tokenizer=FakeTokenizer(),
        start=False,  # queue all three so ONE boundary admits them together
    )
    try:
        reqs = [engine.submit(prefix, s) for s in suffix_sets]
        engine.start()
        results = [r.future.result(timeout=300) for r in reqs]
    finally:
        engine.shutdown(drain=True)
    assert engine.error is None
    for res, w_s, w_u in zip(results, oracle_scores, oracle_updated):
        _assert_same_result(res, w_s, w_u)
    # One shared prefill carried every request through spec decode.
    assert engine.metrics.counter("prefills") == 1
    assert engine._sched.stats()["coalesced_requests"] == len(suffix_sets)


def test_spec_serve_preempt_then_resume_token_identical(model_dir):
    """A best-effort spec wave preempted mid-run by an interactive
    arrival captures its draft/accept state up to the request's slowest
    suffix, resumes with the generated tokens folded into the draft
    context (never re-drafted stale), and the full stream equals the
    uninterrupted oracle."""
    n_long = 6
    oracle_scores, oracle_updated = DecodeGenerator(
        _fw(model_dir, num_gen_token=n_long), tokenizer=FakeTokenizer()
    )([PROMPTS[0]])
    engine = ServeEngine(
        _fw(model_dir),
        ServeConfig(
            max_wave_requests=1,
            max_active_requests=1,
            default_max_new_tokens=N_GEN,
            speculative_k=SPEC_K,
            sched=SchedConfig(enabled=True),
        ),
        tokenizer=FakeTokenizer(),
    )
    try:
        victim = engine.submit(
            *PROMPTS[0], max_new_tokens=n_long, slo_class="best_effort",
            tenant_id="batch",
        )
        deadline = time.monotonic() + 120
        while engine.metrics.counter("prefills") < 1:
            assert time.monotonic() < deadline, "victim never prefilled"
            time.sleep(0.005)
        urgent = engine.submit(
            *PROMPTS[2], max_new_tokens=1, slo_class="interactive",
            tenant_id="live",
        )
        urgent_res = urgent.future.result(timeout=300)
        victim_res = victim.future.result(timeout=300)
    finally:
        engine.shutdown(drain=True)
    assert engine.error is None
    assert urgent.finished_at < victim.finished_at
    assert urgent_res.tokens.shape[1] == 1
    _assert_same_result(victim_res, oracle_scores[0], oracle_updated[0])
    assert engine._sched.stats()["preemptions"] >= 1


# ---------------------------------------------------------------------------
# Fleet: kill/re-dispatch stays token-identical with spec on
# ---------------------------------------------------------------------------

def test_spec_serve_fleet_replica_kill_token_identical(
    model_dir, offline_oracle
):
    """3 speculative replicas under a seeded replica_kill: the dead
    replica's requests re-dispatch exactly once and every completion is
    token-identical to the no-chaos oracle — speculation is invisible to
    the failover contract (a re-dispatched request restarts generation,
    and greedy-exact verification reproduces the same stream)."""
    off_scores, off_updated = offline_oracle
    fleet = ReplicaFleet(
        _fw(
            model_dir,
            io_retry_attempts=8,
            io_retry_base_s=0.001,
            faults=FaultConfig(
                enabled=True, seed=CHAOS_SEED, error_rate=1.0,
                sites=("replica_kill",), max_faults=1,
            ),
        ),
        ServeConfig(
            replicas=3,
            max_wave_requests=2,
            default_max_new_tokens=N_GEN,
            speculative_k=SPEC_K,
            router_health_poll_s=0.05,
        ),
        tokenizer=FakeTokenizer(),
    )
    try:
        reqs = [fleet.submit(p, s) for p, s in PROMPTS[:2]]
        results = [r.future.result(timeout=300) for r in reqs]
    finally:
        fleet.shutdown(drain=True)
    assert fleet.error is None
    assert all(r.status is RequestStatus.DONE for r in reqs)
    for res, w_s, w_u in zip(results, off_scores, off_updated):
        _assert_same_result(res, w_s, w_u)
    snap = fleet.metrics.snapshot()
    assert snap["replicas_dead"] == 1
    assert snap["redispatches"] >= 1


# ---------------------------------------------------------------------------
# Config/CLI surface
# ---------------------------------------------------------------------------

def test_spec_serve_config_validation_and_cli_flag():
    """ServeConfig.speculative_k validates its range; the serve parser
    carries --speculative_k and threads it into ServeConfig."""
    with pytest.raises(ValueError, match="speculative_k"):
        ServeConfig(speculative_k=-1)
    with pytest.raises(ValueError, match="speculative_k"):
        ServeConfig(speculative_k=65)
    from flexible_llm_sharding_tpu.cli import build_serve_parser

    args = build_serve_parser().parse_args(
        ["--model_path", "/x", "--speculative_k", "3"]
    )
    assert args.speculative_k == 3


def test_spec_serve_offline_knob_still_rejected(model_dir):
    """FrameworkConfig.speculative_k stays the OFFLINE scorer's knob:
    handing it to the engine raises loudly, pointing at the serve knob."""
    with pytest.raises(ValueError, match="ServeConfig.speculative_k"):
        ServeEngine(
            _fw(model_dir, speculative_k=2),
            ServeConfig(),
            tokenizer=FakeTokenizer(),
            start=False,
        )
