"""Replica fleet: shard-phase-aware routing, health-driven draining, and
chaos-proven failover (serve/fleet.py + serve/router.py).

The acceptance bar is the PR 3/4 standard lifted one level: under
replica-level chaos (a whole engine killed or wedged mid-sweep), every
submitted request completes with output token-identical to a single
healthy engine — with exactly-once re-dispatch (no request resolves
twice, no request is dropped) and the deadline contract preserved (an
orphan whose deadline lapsed resolves EXPIRED, never re-served late).

The injector seed is pinned (overridable via FLS_CHAOS_SEED, like the
rest of the chaos suite) so a failure replays exactly.
"""

import os
import time

import numpy as np
import pytest

import jax

from flexible_llm_sharding_tpu.config import (
    AutoscaleConfig,
    FaultConfig,
    FrameworkConfig,
    ServeConfig,
)
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator
from flexible_llm_sharding_tpu.serve import (
    ReplicaFleet,
    Router,
    ServeEngine,
    WaveAborted,
)
from flexible_llm_sharding_tpu.serve.request import (
    DeadlineExceeded,
    Request,
    RequestStatus,
    ServeFuture,
)
from flexible_llm_sharding_tpu.utils.checkpoint import save_params

from tests.fake_tokenizer import FakeTokenizer

CHAOS_SEED = int(os.environ.get("FLS_CHAOS_SEED", "1234"))

N_GEN = 2

# Uniform 2-suffix prompts: one (B, S, L) shape family = one jit compile
# set for the whole module (XLA:CPU compile wall dominates otherwise).
PROMPTS = [
    ("The capital of France", (" is Paris", " is Rome")),
    ("Two plus two equals", (" four", " five")),
    ("The sky is", (" blue", " green")),
    ("Hello world", (" again", " anew")),
    ("Water boils at", (" one hundred", " zero")),
    ("A stitch in time", (" saves nine", " is lost")),
]


@pytest.fixture(scope="module")
def model_dir(tiny_cfg, tmp_path_factory):
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    d = tmp_path_factory.mktemp("tiny_model_fleet")
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
        io_retry_attempts=8,
        io_retry_base_s=0.001,
    )
    base.update(kw)
    return FrameworkConfig(**base)


def _chaos(**kw) -> FaultConfig:
    base = dict(enabled=True, seed=CHAOS_SEED)
    base.update(kw)
    return FaultConfig(**base)


def _serve_cfg(**kw) -> ServeConfig:
    base = dict(
        replicas=3,
        max_wave_requests=2,
        default_max_new_tokens=N_GEN,
        router_health_poll_s=0.05,
    )
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture(scope="module")
def offline_oracle(model_dir):
    """Fault-free single-engine-equivalent outputs for PROMPTS (the
    DecodeGenerator batch path — test_serve.py pins serve == this). Also
    pre-pays the module's jit compiles, so fleet liveness thresholds
    below never race a cold compile."""
    cfg = _fw(model_dir)
    return DecodeGenerator(cfg, tokenizer=FakeTokenizer())(list(PROMPTS))


# ---------------------------------------------------------------------------
# Units: future claim, router scoring, reclaim
# ---------------------------------------------------------------------------

def test_future_first_wins_and_callback_exactly_once():
    """Terminal transitions are first-wins: a racing second resolution is
    a silent no-op, and the callback fires exactly once — the
    never-double-served half of the fleet's re-dispatch contract."""
    fired = []
    r = Request(
        prefix="p", suffixes=("s",), max_new_tokens=1,
        callback=lambda req: fired.append(req.status),
    )
    r.fail(WaveAborted("first"), RequestStatus.FAILED)
    # Late winner-less attempts: resolve() and fail() both lose the claim.
    r.resolve(np.zeros((1, 1, 4)), ("p", ("s",)), np.zeros((1, 1), np.int64))
    r.fail(RuntimeError("second"), RequestStatus.CANCELLED)
    assert r.status is RequestStatus.FAILED
    assert fired == [RequestStatus.FAILED]
    with pytest.raises(WaveAborted, match="first"):
        r.future.result(timeout=1)

    f = ServeFuture()
    assert f.claim() and not f.claim()  # exactly one claimer, ever
    assert f.set_error(RuntimeError("x")) is False  # claim consumed


class _FakeReplica:
    def __init__(self, idx, frac, depth, active, serving=True, max_active=8):
        self.idx = idx
        self.serving = serving
        self._snap = {
            "boundary_frac": frac,
            "queue_depth": depth,
            "active": active,
            "max_active": max_active,
        }

    def snapshot(self):
        return self._snap


def test_router_scoring_phase_and_depth():
    """Lowest score wins: an idle replica AT its boundary beats one
    mid-sweep; depth breaks phase ties; draining/dead replicas are never
    candidates; the excluded (just-failed) replica is skipped whenever an
    alternative survives, but used when it is the only one serving."""
    router = Router(phase_weight=1.0, depth_weight=1.0)
    idle = _FakeReplica(0, frac=0.0, depth=0, active=0)
    mid = _FakeReplica(1, frac=0.75, depth=0, active=0)
    deep = _FakeReplica(2, frac=0.0, depth=4, active=4)
    dead = _FakeReplica(3, frac=0.0, depth=0, active=0, serving=False)
    assert router.pick([mid, deep, idle, dead]) is idle
    # Phase proximity dominates an equal-depth choice...
    assert router.pick([mid, _FakeReplica(4, 0.25, 0, 0)]).idx == 4
    # ...and a deeply queued boundary replica loses to a shallow mid-sweep
    # one once depth outweighs phase.
    assert router.pick([deep, mid]) is mid
    # Exclusion: the failed replica is skipped while others serve…
    assert router.pick([idle, mid], exclude=idle) is mid
    # …but a lone survivor is still used (serving beats failing).
    assert router.pick([idle], exclude=idle) is idle
    assert router.pick([dead]) is None
    with pytest.raises(ValueError):
        Router(phase_weight=-1)


def test_router_never_picks_engine_with_fatal_error():
    """A replica whose engine already set a fatal error is not a
    candidate even while the fleet still lists it as serving (the
    monitor hasn't polled yet): its queue is closed, so dispatching
    there burns one of the request's two attempts on a certain failure.
    On a one-replica fleet the old 'lone survivor' fallback resent every
    orphan straight back to the corpse and terminally failed it."""

    class _Eng:
        def __init__(self, error=None):
            self.error = error

    router = Router()
    corpse = _FakeReplica(0, frac=0.0, depth=0, active=0)
    corpse.engine = _Eng(error=RuntimeError("killed"))
    live = _FakeReplica(1, frac=0.9, depth=4, active=4)
    live.engine = _Eng()
    # The worse-scoring live replica still wins over the dead one…
    assert router.pick([corpse, live]) is live
    # …and a fleet of only corpses parks (None) instead of dispatching,
    # even when the corpse is the lone non-excluded "survivor".
    assert router.pick([corpse]) is None
    assert router.pick([corpse], exclude=live) is None


def test_reclaim_inflight_returns_orphans(model_dir):
    """A stopped engine's queued requests reclaim as orphans: original
    prompts + dispatch ids returned, futures resolve WaveAborted for any
    direct waiter, and the fleet-owned callback is deliberately NOT fired
    (the caller owns the onward re-dispatch, not an error surface)."""
    fired = []
    engine = ServeEngine(
        _fw(model_dir), _serve_cfg(replicas=1),
        tokenizer=FakeTokenizer(), start=False,
    )
    reqs = []
    for i, (p, s) in enumerate(PROMPTS[:2]):
        r = Request(
            prefix=p, suffixes=s, max_new_tokens=1,
            callback=lambda req: fired.append(req), dispatch_id=100 + i,
        )
        engine.submit_request(r)
        reqs.append(r)
    orphans = engine.reclaim_inflight()
    assert orphans == reqs
    assert [o.dispatch_id for o in orphans] == [100, 101]
    assert [o.prompt for o in orphans] == list(PROMPTS[:2])
    for o in orphans:
        assert o.status is RequestStatus.FAILED
        with pytest.raises(WaveAborted):
            o.future.result(timeout=1)
    assert fired == []  # callbacks suppressed: the caller re-dispatches
    assert engine.reclaim_inflight() == []  # idempotent: all terminal now
    engine.shutdown(drain=False)


def test_orphan_with_expired_deadline_resolves_expired(model_dir):
    """The deadline contract survives orphaning: a request whose deadline
    lapsed while orphaned resolves EXPIRED (DeadlineExceeded) — it is
    NEVER re-dispatched (its TTFT contract is already lost)."""
    fleet = ReplicaFleet(
        _fw(model_dir), _serve_cfg(replicas=1),
        tokenizer=FakeTokenizer(), start=False,  # engines idle: stays queued
    )
    try:
        req = fleet.submit(*PROMPTS[0], deadline_s=0.01)
        disp = fleet._dispatches[req.request_id]
        time.sleep(0.03)  # deadline passes while "in flight" on replica 0

        # Path 1: the dead replica's reclaim sweep finds it already
        # expired — the queue eviction resolves it EXPIRED on the spot.
        rep = fleet._replicas[0]
        orphans = rep.engine.reclaim_inflight()
        assert orphans == []  # evicted as EXPIRED, not handed back
        assert req.status is RequestStatus.EXPIRED
        with pytest.raises(DeadlineExceeded):
            req.future.result(timeout=1)

        # Path 2: an orphan that reclaims non-terminal but expires before
        # the re-dispatch lands: _dispatch's expiry gate resolves EXPIRED
        # and counts it — never re-dispatched.
        req2 = Request(
            prefix="p", suffixes=("s",), max_new_tokens=1,
            deadline=time.monotonic() - 0.01,
        )
        req2.dispatch_id = req2.request_id
        from flexible_llm_sharding_tpu.serve.fleet import _Dispatch

        disp2 = _Dispatch(outer=req2, attempts=1)
        fleet._dispatches[req2.request_id] = disp2
        fleet._dispatch(disp2, redispatch=True)
        assert req2.status is RequestStatus.EXPIRED
        assert fleet.metrics.counter("expired_orphans") == 1
        assert fleet.metrics.counter("redispatches") == 0
    finally:
        fleet.shutdown(drain=False)


def test_poll_health_auto_drains_flaky_replica(model_dir):
    """A replica whose engine_recoveries counter reaches
    router_drain_recoveries is gracefully drained (state transition on
    the next health poll), not hard-failed — flaky-but-alive engines get
    to finish their in-flight work before recycling."""
    fleet = ReplicaFleet(
        _fw(model_dir),
        _serve_cfg(replicas=2, router_drain_recoveries=2),
        tokenizer=FakeTokenizer(), start=False,
    )
    try:
        flaky = fleet._replicas[0]
        flaky.engine.metrics.count("engine_recoveries", 2)
        fleet._poll_health()
        assert flaky.state == "draining"
        assert fleet._replicas[1].state == "serving"
    finally:
        fleet.shutdown(drain=False)


# ---------------------------------------------------------------------------
# Fleet end-to-end
# ---------------------------------------------------------------------------

def test_fleet_parity_multi_replica(model_dir, offline_oracle):
    """3 replicas, no chaos: every request completes token-identical to
    the single-engine path; the router spread the load (all dispatches
    first attempts, zero re-dispatches)."""
    off_scores, off_updated = offline_oracle
    fleet = ReplicaFleet(
        _fw(model_dir), _serve_cfg(), tokenizer=FakeTokenizer()
    )
    try:
        reqs = [fleet.submit(p, s) for p, s in PROMPTS]
        results = [r.future.result(timeout=300) for r in reqs]
    finally:
        fleet.shutdown(drain=True)
    assert fleet.error is None
    for res, want, upd in zip(results, off_scores, off_updated):
        assert (res.scores.argmax(-1) == want.argmax(-1)).all()
        np.testing.assert_allclose(res.scores, want, rtol=1e-5, atol=1e-6)
        assert res.updated == upd
    snap = fleet.metrics.snapshot()
    assert snap["dispatches"] == len(PROMPTS)
    assert snap["redispatches"] == 0
    stats = fleet.stats()
    assert stats["event"] == "fleet_stats"
    completed = sum(
        rep.get("completed", 0) for rep in stats["replicas"].values()
    )
    assert completed == len(PROMPTS)


def test_fleet_chaos_replica_kill_exactly_once(model_dir, offline_oracle):
    """THE acceptance bar: 3 replicas, a seeded replica_kill takes one
    whole engine down mid-sweep. Asserts (1) no request resolves twice
    (per-request callback count == 1), (2) no request is dropped (every
    future resolves DONE), (3) completions are token-identical to the
    no-chaos single-engine run, and the re-dispatch/recycle counters
    witness the failover actually happened."""
    off_scores, off_updated = offline_oracle
    fleet = ReplicaFleet(
        _fw(
            model_dir,
            faults=_chaos(
                error_rate=1.0, sites=("replica_kill",), max_faults=1
            ),
        ),
        _serve_cfg(),
        tokenizer=FakeTokenizer(),
    )
    counts: dict[int, int] = {}
    try:
        reqs = [
            fleet.submit(
                p, s,
                callback=lambda req: counts.__setitem__(
                    req.request_id, counts.get(req.request_id, 0) + 1
                ),
            )
            for p, s in PROMPTS
        ]
        results = [r.future.result(timeout=300) for r in reqs]
    finally:
        fleet.shutdown(drain=True)
    assert fleet.error is None
    # (1) exactly-once resolution: one terminal callback per request.
    assert sorted(counts) == sorted(r.request_id for r in reqs)
    assert set(counts.values()) == {1}
    # (2) nothing dropped: every request reached DONE.
    assert all(r.status is RequestStatus.DONE for r in reqs)
    # (3) token-identical to the healthy single-engine run.
    for res, want, upd in zip(results, off_scores, off_updated):
        assert (res.scores.argmax(-1) == want.argmax(-1)).all()
        np.testing.assert_allclose(res.scores, want, rtol=1e-5, atol=1e-6)
        assert res.updated == upd
    snap = fleet.metrics.snapshot()
    assert snap["replicas_dead"] == 1  # the kill really landed mid-sweep
    assert snap["redispatches"] >= 1  # orphans moved to a survivor
    assert snap["replicas_recycled"] == 1  # the slot came back
    assert snap["expired_orphans"] == 0


def test_fleet_chaos_replica_stall_liveness_failover(model_dir, offline_oracle):
    """A WEDGED engine (replica_stall: the thread blocks mid-sweep, so no
    exception ever surfaces and no in-engine watchdog can help) is
    detected by the fleet's sweep-watermark liveness check, hard-failed,
    and its requests reclaimed + re-dispatched — completions stay
    token-identical and nothing hangs."""
    off_scores, off_updated = offline_oracle
    fleet = ReplicaFleet(
        _fw(
            model_dir,
            faults=_chaos(
                error_rate=1.0, sites=("replica_stall",), max_faults=1
            ),
        ),
        # 5 s, not 2: on a loaded machine the SURVIVOR compiles the
        # re-dispatched wave's shapes for over 2 s without a heartbeat,
        # is declared dead too, and the second reclaim surfaces
        # WaveAborted (seen under xdist, at this commit and its parent).
        _serve_cfg(replicas=2, watchdog_abort_s=5.0),
        tokenizer=FakeTokenizer(),
    )
    try:
        reqs = [fleet.submit(p, s) for p, s in PROMPTS[:4]]
        results = [r.future.result(timeout=300) for r in reqs]
    finally:
        assert fleet.shutdown(drain=True)  # the wedged thread must not leak
    for res, want, upd in zip(results, off_scores, off_updated):
        assert (res.scores.argmax(-1) == want.argmax(-1)).all()
        assert res.updated == upd
    snap = fleet.metrics.snapshot()
    assert snap["replicas_dead"] >= 1
    assert snap["redispatches"] >= 1
    assert snap["replicas_recycled"] >= 1
    # Double-count regression: the wedged engine thread, released during
    # hard-fail/shutdown, may finish its sweep and try to resolve the
    # requests the fleet already reclaimed — those lose the first-wins
    # claim and must NOT be counted, so per-replica 'completed' sums to
    # exactly the number of requests served.
    completed = sum(
        rep.get("completed", 0)
        for rep in fleet.stats()["replicas"].values()
    )
    assert completed == len(reqs)


def test_fleet_elastic_add_remove(model_dir, offline_oracle):
    """Elastic join/leave: add_replica brings a new engine into rotation;
    remove_replica(drain=True) serves out its work through the graceful-
    drain path; removing the last serving replica is refused."""
    off_scores, _ = offline_oracle
    fleet = ReplicaFleet(
        _fw(model_dir), _serve_cfg(replicas=1), tokenizer=FakeTokenizer()
    )
    try:
        assert len(fleet.replicas) == 1
        new_idx = fleet.add_replica()
        assert len(fleet.replicas) == 2
        reqs = [fleet.submit(p, s) for p, s in PROMPTS[:4]]
        results = [r.future.result(timeout=300) for r in reqs]
        for res, want in zip(results, off_scores):
            assert (res.scores.argmax(-1) == want.argmax(-1)).all()
        assert fleet.remove_replica(new_idx, drain=True, timeout=60)
        assert len(fleet.replicas) == 1
        assert fleet.metrics.counter("replicas_added") == 1
        assert fleet.metrics.counter("replicas_removed") == 1
        assert fleet.metrics.counter("replicas_drained") == 1
        with pytest.raises(ValueError, match="last serving replica"):
            fleet.remove_replica(drain=True)
        # The survivor still serves after the topology change.
        res = fleet.submit(*PROMPTS[0]).future.result(timeout=300)
        assert (res.scores.argmax(-1) == off_scores[0].argmax(-1)).all()
    finally:
        fleet.shutdown(drain=True)
    assert fleet.error is None


def test_fleet_hard_remove_redispatches(model_dir, offline_oracle):
    """remove_replica(drain=False) is the hard-fail path: the removed
    replica's queued work re-dispatches to the survivor and completes."""
    off_scores, _ = offline_oracle
    # One request per wave + single active slot: work stacks up queued on
    # the busy replica, so the hard remove provably strands some.
    fleet = ReplicaFleet(
        _fw(model_dir),
        _serve_cfg(
            replicas=2, max_wave_requests=1, max_active_requests=1,
        ),
        tokenizer=FakeTokenizer(),
    )
    try:
        reqs = [fleet.submit(p, s) for p, s in PROMPTS[:4]]
        victim = fleet.replicas[0]
        assert fleet.remove_replica(victim, drain=False)
        assert len(fleet.replicas) == 1
        results = [r.future.result(timeout=300) for r in reqs]
        for res, want in zip(results, off_scores):
            assert (res.scores.argmax(-1) == want.argmax(-1)).all()
        assert fleet.metrics.counter("replicas_removed") == 1
    finally:
        fleet.shutdown(drain=True)


# ---------------------------------------------------------------------------
# Autoscale wiring (serve/autoscale.py): router term, restore target,
# staggered live fleet
# ---------------------------------------------------------------------------

def test_router_score_folds_pending_stagger_hold():
    """A pending stagger hold is admission distance: with equal raw
    phase and load, the replica about to park at its boundary loses."""
    router = Router(phase_weight=1.0, depth_weight=1.0)
    held = _FakeReplica(0, frac=0.1, depth=0, active=0)
    held._snap["hold_frac"] = 0.5
    free = _FakeReplica(1, frac=0.1, depth=0, active=0)
    assert router.pick([held, free]) is free
    # Snapshots without the key (single engines, old fixtures) are
    # unaffected.
    assert router.score(free.snapshot()) == pytest.approx(0.1)
    assert router.score(held.snapshot()) == pytest.approx(0.6)


def test_pressure_restore_targets_autoscaler_population(model_dir):
    """Satellite regression (drain -> scale -> restore): after the
    autoscaler resized the fleet, pressure_restore repopulates to the
    CONTROLLER's current target, not the stale boot-time replica
    count."""
    auto = AutoscaleConfig(enabled=True, min=1, max=4, stagger=False)
    fleet = ReplicaFleet(
        _fw(model_dir),
        _serve_cfg(replicas=2, autoscale=auto),
        tokenizer=FakeTokenizer(), start=False,
    )
    try:
        assert fleet.population() == 2
        assert fleet.population_target() == 2
        # The controller scaled up (what a confirmed burn breach does).
        fleet.add_replica()
        with fleet._autoscaler._lock:
            fleet._autoscaler.target = 3
        # Brownout sheds down to one replica...
        assert fleet.pressure_drain(keep=1) == 2
        for rep in list(fleet._replicas):
            if rep.state == "removing":
                fleet._complete_drain(rep)
        assert fleet.population() == 1
        # ...and the restore honors the autoscaler's target, not the
        # boot-time replicas=2.
        assert fleet.pressure_restore() == 2
        assert fleet.population() == 3
    finally:
        fleet.shutdown(drain=False)


def test_pressure_restore_without_autoscaler_uses_config(model_dir):
    """Static fleets keep the pre-autoscale behavior: restore returns
    to serve_cfg.replicas."""
    fleet = ReplicaFleet(
        _fw(model_dir), _serve_cfg(replicas=2),
        tokenizer=FakeTokenizer(), start=False,
    )
    try:
        assert fleet.population_target() == 2
        assert fleet.pressure_drain(keep=1) == 1
        for rep in list(fleet._replicas):
            if rep.state == "removing":
                fleet._complete_drain(rep)
        assert fleet.pressure_restore() == 1
        assert fleet.population() == 2
    finally:
        fleet.shutdown(drain=False)


def test_fleet_autoscale_helpers_and_stats_surface(model_dir):
    """The controller-facing fleet surface: population / queue_frac /
    drains_in_flight read consistently, replay gate forwards, and
    stats() carries the autoscale + stagger sections."""
    auto = AutoscaleConfig(enabled=True, min=1, max=4)
    fleet = ReplicaFleet(
        _fw(model_dir),
        _serve_cfg(replicas=2, autoscale=auto),
        tokenizer=FakeTokenizer(), start=False,
    )
    try:
        assert fleet.population() == 2
        assert fleet.drains_in_flight() == 0
        assert fleet.queue_frac() == 0.0
        assert len(fleet.serving_engines()) == 2
        fleet.mark_replay_complete()  # no WAL: already open, idempotent
        assert fleet._autoscaler.stats()["replay_pending"] == 0
        stats = fleet.stats()
        assert stats["autoscale"]["target_replicas"] == 2
        assert "stagger_error" in stats["stagger"]
        # Replica snapshots carry the router's hold_frac term.
        for rep in fleet._replicas:
            assert rep.snapshot()["hold_frac"] == 0.0
    finally:
        fleet.shutdown(drain=False)
    assert fleet.error is None


def test_fleet_staggered_parity_live(model_dir, offline_oracle):
    """A live autoscale+stagger fleet serves token-identically: boundary
    holds shift phases but never change tokens, and the stagger stats
    export through fleet.stats()."""
    off_scores, off_updated = offline_oracle
    auto = AutoscaleConfig(
        enabled=True, min=1, max=4, poll_s=0.05, confirm_polls=1000,
    )
    fleet = ReplicaFleet(
        _fw(model_dir),
        _serve_cfg(replicas=2, autoscale=auto),
        tokenizer=FakeTokenizer(),
    )
    try:
        reqs = [fleet.submit(p, s) for p, s in PROMPTS]
        results = [r.future.result(timeout=300) for r in reqs]
    finally:
        fleet.shutdown(drain=True)
    assert fleet.error is None
    for res, want, upd in zip(results, off_scores, off_updated):
        assert (res.scores.argmax(-1) == want.argmax(-1)).all()
        np.testing.assert_allclose(res.scores, want, rtol=1e-5, atol=1e-6)
        assert res.updated == upd
    stats = fleet.stats()
    assert stats["autoscale"]["polls"] >= 0  # daemon ran and closed clean
    assert 0.0 <= stats["stagger"]["stagger_error"] <= 1.0
