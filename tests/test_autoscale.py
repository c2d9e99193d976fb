"""Closed-loop fleet elasticity (serve/autoscale.py) — PR 19.

Unit layer: the stagger math and both controllers against fakes (an
injected clock and samplers make every anti-flap path deterministic).
Integration layer: a real ``ReplicaFleet`` proving the pressure ladder
restores to the AUTOSCALER's target after a runtime resize (the
satellite regression) and that stagger wiring survives a live fleet.
"""

import time

import pytest

from flexible_llm_sharding_tpu.config import AutoscaleConfig, ServeConfig
from flexible_llm_sharding_tpu.serve.autoscale import (
    FleetAutoscaler,
    StaggerController,
    stagger_error,
    stagger_targets,
)


# ---------------------------------------------------------------------------
# stagger math
# ---------------------------------------------------------------------------

def test_stagger_targets_even_spread():
    assert stagger_targets(4) == (0.0, 0.25, 0.5, 0.75)
    assert stagger_targets(1) == (0.0,)
    assert stagger_targets(0) == ()


def test_stagger_error_bounds_and_invariance():
    # Perfect i/N spread: zero error regardless of N.
    for n in (2, 3, 4, 7):
        assert stagger_error(stagger_targets(n)) == pytest.approx(0.0)
    # All replicas in phase: the worst case, exactly 1.0.
    assert stagger_error([0.3, 0.3, 0.3]) == pytest.approx(1.0)
    assert stagger_error([0.0, 1.0, 2.0]) == pytest.approx(1.0)  # mod 1
    # Rotation invariance: the error depends on gaps, not absolute phase.
    base = [0.0, 0.25, 0.5, 0.75]
    rotated = [(p + 0.13) % 1.0 for p in base]
    assert stagger_error(rotated) == pytest.approx(stagger_error(base))
    # Fewer than two phases are trivially staggered.
    assert stagger_error([]) == 0.0
    assert stagger_error([0.7]) == 0.0
    # Intermediate spreads land strictly inside (0, 1).
    mid = stagger_error([0.0, 0.1, 0.5, 0.6])
    assert 0.0 < mid < 1.0


# ---------------------------------------------------------------------------
# StaggerController
# ---------------------------------------------------------------------------

def _stagger(**kw):
    defaults = dict(enabled=True, stagger_tolerance=0.15,
                    stagger_hold_max_frac=0.5)
    defaults.update(kw)
    return StaggerController(AutoscaleConfig(**defaults))


def _warm_walls(ctl, idxs, wall=1.0):
    """Two boundaries per replica seed the sweep-wall EMA."""
    for i in idxs:
        ctl.on_boundary(i, 10.0)
        ctl.on_boundary(i, 10.0 + wall)


def test_stagger_converged_assigns_no_holds():
    ctl = _stagger()
    _warm_walls(ctl, (0, 1, 2, 3))
    err = ctl.observe({0: 0.0, 1: 0.25, 2: 0.5, 3: 0.75})
    assert err == pytest.approx(0.0)
    s = ctl.stats()
    assert s["stagger_converged"] == 1 and s["holds_pending"] == 0


def test_stagger_assigns_bounded_holds_anchor_exempt():
    ctl = _stagger(stagger_hold_max_frac=0.5)
    _warm_walls(ctl, (0, 1, 2), wall=2.0)
    # All in phase: worst case. Anchor (highest phase, ties break by
    # sort order) gets no hold; the others get bounded ones.
    err = ctl.observe({0: 0.4, 1: 0.4, 2: 0.4})
    assert err == pytest.approx(1.0)
    holds = {i: ctl.hold_frac(i) for i in (0, 1, 2)}
    assert sum(1 for h in holds.values() if h == 0.0) == 1  # the anchor
    for h in holds.values():
        # Bounded: at most hold_max_frac of the replica's sweep wall.
        assert 0.0 <= h <= 0.5 + 1e-9
    assert ctl.stats()["holds_pending"] == 2


def test_stagger_one_round_at_a_time():
    ctl = _stagger()
    _warm_walls(ctl, (0, 1))
    ctl.observe({0: 0.2, 1: 0.2})
    pending = ctl.stats()["holds_pending"]
    assert pending == 1
    # Second observe with holds still unconsumed: no new assignment.
    ctl.observe({0: 0.3, 1: 0.3})
    assert ctl.stats()["holds_pending"] == pending
    # Consume the hold at the boundary; the next observe re-corrects.
    for i in (0, 1):
        ctl.on_boundary(i, 20.0)
    assert ctl.stats()["holds_pending"] == 0
    assert ctl.stats()["holds_applied"] == 1
    ctl.observe({0: 0.3, 1: 0.3})
    assert ctl.stats()["holds_pending"] == 1


def test_stagger_membership_change_drops_holds():
    ctl = _stagger()
    _warm_walls(ctl, (0, 1))
    ctl.observe({0: 0.2, 1: 0.2})
    assert ctl.stats()["holds_pending"] == 1
    ctl.note_membership_change()
    s = ctl.stats()
    assert s["holds_pending"] == 0 and s["restaggers"] == 1
    ctl.forget(1)
    assert ctl.hold_frac(1) == 0.0


def test_stagger_closed_loop_converges_and_reconverges():
    """The controller in a closed loop on synthetic sweep clocks (no wall
    clock anywhere): two replicas start dead in phase (error 1.0), its
    boundary holds feed back into their schedules, and the error must fall
    under the tolerance; after a recycle (membership change plus a quarter
    sweep's phase jump) it must fall there again. Holds must have been
    applied in both rounds: convergence without actuation would be the
    simulation's accident, not the controller's work."""
    ctl = _stagger(stagger_tolerance=0.05)
    wall = 1.0
    nxt = {0: 0.0, 1: 0.0}  # next shard-0 boundary
    start = {0: 0.0, 1: 0.0}  # current sweep's start, after any hold
    t, err_by_round, holds_by_round = 0.0, [1.0, 1.0], [0, 0]
    for step in range(800):
        rnd = 0 if step < 400 else 1
        t = round(t + 0.1, 6)
        if step == 400:
            ctl.note_membership_change()
            nxt[1] = round(nxt[1] + 0.25 * wall, 6)
            start[1] = nxt[1] - wall
        for idx in (0, 1):
            while t >= nxt[idx]:
                hold = ctl.on_boundary(idx, nxt[idx])
                holds_by_round[rnd] += hold > 0.0
                start[idx] = nxt[idx] + hold
                nxt[idx] = round(start[idx] + wall, 6)
        err_by_round[rnd] = ctl.observe(
            {i: min(max((t - start[i]) / wall, 0.0), 0.999) for i in (0, 1)}
        )
    assert min(holds_by_round) >= 1, holds_by_round
    assert max(err_by_round) <= 0.05, err_by_round
    s = ctl.stats()
    assert s["restaggers"] == 1 and s["stagger_converged"] == 1


def test_stagger_no_wall_no_hold():
    ctl = _stagger()
    # No boundary history: walls unknown, so no hold can be sized.
    ctl.observe({0: 0.2, 1: 0.2})
    assert ctl.stats()["holds_pending"] == 0


def test_stagger_wall_ema_updates():
    ctl = _stagger()
    ctl.on_boundary(0, 0.0)
    ctl.on_boundary(0, 1.0)   # wall = 1.0
    ctl.on_boundary(0, 4.0)   # wall = 3.0 -> EMA 0.5*1 + 0.5*3 = 2.0
    ctl.on_boundary(1, 0.0)
    ctl.on_boundary(1, 1.0)
    ctl.observe({0: 0.5, 1: 0.5})
    # Replica 0's hold is sized off its 2.0 s EMA wall: hold_frac is
    # hold / wall, still bounded by hold_max_frac.
    assert 0.0 < max(ctl.hold_frac(0), ctl.hold_frac(1)) <= 0.5 + 1e-9


# ---------------------------------------------------------------------------
# FleetAutoscaler vs a fake fleet
# ---------------------------------------------------------------------------

class _FakeFleet:
    """The exact surface FleetAutoscaler touches, with countable calls."""

    def __init__(self, population=2):
        self._population = population
        self.adds = 0
        self.removes = 0
        self.drains = 0

    def population(self):
        return self._population

    def add_replica(self):
        self.adds += 1
        self._population += 1
        return self._population - 1

    def remove_replica(self, idx=None, drain=True, timeout=None):
        self.removes += 1
        self._population -= 1
        return True

    def drains_in_flight(self):
        return self.drains

    def queue_frac(self):
        return 0.0

    def serving_engines(self):
        return []


class _Harness:
    """Autoscaler + fake fleet with a hand-cranked clock and samplers."""

    def __init__(self, population=2, replay_pending=False, **cfg_kw):
        defaults = dict(enabled=True, min=1, max=4, confirm_polls=2,
                        grow_cooldown_s=5.0, shrink_cooldown_s=10.0)
        defaults.update(cfg_kw)
        self.cfg = AutoscaleConfig(**defaults)
        self.fleet = _FakeFleet(population)
        self.now = 100.0
        self.burn = (0.5, False)
        self.queue = 0.0
        self.shed = False
        self.auto = FleetAutoscaler(
            self.fleet,
            self.cfg,
            clock=lambda: self.now,
            burn_sampler=lambda: self.burn,
            queue_sampler=lambda: self.queue,
            pressure_sampler=lambda: self.shed,
            replay_pending=replay_pending,
        )


def test_grow_requires_consecutive_confirmation():
    h = _Harness(confirm_polls=3)
    h.burn = (2.0, False)
    assert h.auto.poll_once()["action"] == "hold"
    assert h.auto.poll_once()["action"] == "hold"
    # Streak broken: signal clears for one poll.
    h.burn = (0.0, False)
    assert h.auto.poll_once()["action"] == "hold"
    h.burn = (2.0, False)
    assert h.auto.poll_once()["action"] == "hold"
    assert h.auto.poll_once()["action"] == "hold"
    assert h.auto.poll_once()["action"] == "grow"
    assert h.fleet.adds == 1
    assert h.auto.stats()["target_replicas"] == 3


def test_falling_trend_vetoes_burn_grow_but_not_queue_grow():
    h = _Harness(confirm_polls=1)
    h.burn = (2.0, True)  # burning, but already draining
    assert h.auto.poll_once()["action"] == "hold"
    assert h.fleet.adds == 0
    # Queue saturation grows regardless of the burn trend.
    h.queue = 0.9
    assert h.auto.poll_once()["action"] == "grow"
    assert h.fleet.adds == 1


def test_grow_cooldown_blocks_then_releases():
    h = _Harness(confirm_polls=1, grow_cooldown_s=5.0)
    h.burn = (2.0, False)
    assert h.auto.poll_once()["action"] == "grow"
    # Confirmed again inside the cooldown: blocked, not acted.
    r = h.auto.poll_once()
    assert r["action"] == "blocked:grow_cooldown"
    assert h.fleet.adds == 1
    h.now += 6.0
    assert h.auto.poll_once()["action"] == "grow"
    assert h.fleet.adds == 2


def test_pressure_shed_interlock_and_latch():
    h = _Harness(confirm_polls=1)
    h.burn = (2.0, False)
    h.shed = True
    assert h.auto.poll_once()["action"] == "blocked:pressure_shed"
    assert h.fleet.adds == 0
    # Latched: the standing interlock counts (and journals) once.
    h.auto.poll_once()
    h.auto.poll_once()
    assert h.auto.stats()["blocked"] == 1
    # Pressure lifts: the latch re-arms after an unblocked poll.
    h.shed = False
    assert h.auto.poll_once()["action"] == "grow"
    h.now += 100.0
    h.shed = True
    h.auto.poll_once()
    assert h.auto.stats()["blocked"] == 2


def test_at_max_is_blocked_not_silent():
    h = _Harness(population=4, confirm_polls=1)
    h.burn = (2.0, False)
    assert h.auto.poll_once()["action"] == "blocked:at_max"
    assert h.fleet.adds == 0


def test_shrink_confirms_and_acts():
    h = _Harness(population=3, confirm_polls=2)
    h.burn = (0.0, False)
    h.queue = 0.0
    assert h.auto.poll_once()["action"] == "hold"
    assert h.auto.poll_once()["action"] == "shrink"
    assert h.fleet.removes == 1
    assert h.auto.stats()["target_replicas"] == 2


def test_shrink_at_min_is_silent_resting_state():
    h = _Harness(population=1, confirm_polls=1)
    h.burn = (0.0, False)
    for _ in range(3):
        assert h.auto.poll_once()["action"] == "hold"
    assert h.auto.stats()["blocked"] == 0
    assert h.fleet.removes == 0


def test_drain_in_flight_blocks_shrink():
    h = _Harness(population=3, confirm_polls=1)
    h.burn = (0.0, False)
    h.fleet.drains = 1
    assert h.auto.poll_once()["action"] == "blocked:drain_in_flight"
    assert h.fleet.removes == 0
    h.fleet.drains = 0
    assert h.auto.poll_once()["action"] == "shrink"


def test_replay_gate_blocks_both_directions_until_opened():
    h = _Harness(population=2, confirm_polls=1, replay_pending=True)
    h.burn = (2.0, False)
    assert h.auto.poll_once()["action"] == "blocked:replay_pending"
    h.burn = (0.0, False)
    assert h.auto.poll_once()["action"] == "blocked:replay_pending"
    assert h.fleet.adds == 0 and h.fleet.removes == 0
    h.auto.mark_replay_complete()
    h.burn = (2.0, False)
    assert h.auto.poll_once()["action"] == "grow"


def test_dry_run_journals_without_acting():
    h = _Harness(confirm_polls=1, dry_run=True, grow_cooldown_s=5.0)
    h.burn = (2.0, False)
    assert h.auto.poll_once()["action"] == "grow"
    assert h.fleet.adds == 0  # decision journaled, fleet untouched
    s = h.auto.stats()
    assert s["dry_run_decisions"] == 1 and s["grows"] == 0
    # Cooldowns simulate too — shadow mode rehearses the real cadence.
    assert h.auto.poll_once()["action"] == "blocked:grow_cooldown"
    assert s["target_replicas"] == 2  # target never moves in dry run


def test_scale_race_loss_holds_until_next_poll():
    h = _Harness(population=3, confirm_polls=1)

    def boom():
        raise ValueError("cannot remove the last serving replica")

    h.fleet.remove_replica = lambda **kw: boom()
    h.burn = (0.0, False)
    assert h.auto.poll_once()["action"] == "hold"
    assert h.auto.stats()["shrinks"] == 0


def test_stats_exports_every_counter():
    h = _Harness()
    h.auto.poll_once()
    s = h.auto.stats()
    for key in ("enabled", "dry_run", "polls", "grows", "shrinks",
                "blocked", "dry_run_decisions", "target_replicas",
                "min_replicas", "max_replicas", "grow_streak",
                "shrink_streak", "replay_pending", "last_burn_rate",
                "last_queue_frac"):
        assert key in s
    assert s["polls"] == 1


def test_daemon_poll_loop_runs_and_closes():
    h = _Harness(confirm_polls=1, poll_s=0.01)
    h.burn = (2.0, False)
    h.auto.start()
    deadline = time.monotonic() + 5.0
    while h.fleet.adds == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    h.auto.close()
    assert h.fleet.adds >= 1
    assert h.auto._thread is None


def test_daemon_survives_sampler_exception():
    calls = []

    def flaky():
        calls.append(1)
        raise RuntimeError("sampler broke")

    h = _Harness(poll_s=0.01)
    h.auto._burn_sampler = flaky
    h.auto.start()
    deadline = time.monotonic() + 5.0
    while len(calls) < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    h.auto.close()
    assert len(calls) >= 3  # the loop kept polling through the error


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_autoscale_config_validation():
    with pytest.raises(ValueError, match="min"):
        AutoscaleConfig(min=0)
    with pytest.raises(ValueError, match="max"):
        AutoscaleConfig(min=3, max=2)
    with pytest.raises(ValueError, match="poll_s"):
        AutoscaleConfig(poll_s=0.0)
    with pytest.raises(ValueError, match="shrink_burn_rate"):
        AutoscaleConfig(grow_burn_rate=0.5, shrink_burn_rate=0.6)
    with pytest.raises(ValueError, match="shrink_queue_frac"):
        AutoscaleConfig(grow_queue_frac=0.5, shrink_queue_frac=0.6)
    with pytest.raises(ValueError, match="confirm_polls"):
        AutoscaleConfig(confirm_polls=0)
    with pytest.raises(ValueError, match="stagger_tolerance"):
        AutoscaleConfig(stagger_tolerance=0.0)
    with pytest.raises(ValueError, match="stagger_hold_max_frac"):
        AutoscaleConfig(stagger_hold_max_frac=1.5)


def test_serve_config_replicas_must_sit_inside_autoscale_band():
    with pytest.raises(ValueError, match="autoscale"):
        ServeConfig(
            replicas=5,
            autoscale=AutoscaleConfig(enabled=True, min=1, max=4),
        )
    # Disabled band is not enforced.
    ServeConfig(replicas=5, autoscale=AutoscaleConfig(min=1, max=4))


def test_cli_serve_wants_fleet_whenever_elasticity_is_on():
    # --autoscale --replicas 1 must still build a ReplicaFleet: the
    # autoscaler lives in the fleet, and starting at one replica to grow
    # under load is the canonical elastic config. Found by an end-to-end
    # drive where the single-engine path silently dropped elasticity.
    from flexible_llm_sharding_tpu.cli import _serve_wants_fleet

    assert not _serve_wants_fleet(ServeConfig(replicas=1))
    assert _serve_wants_fleet(ServeConfig(replicas=2))
    assert _serve_wants_fleet(
        ServeConfig(replicas=1, autoscale=AutoscaleConfig(enabled=True))
    )
    # A disabled AutoscaleConfig (the parser default) must NOT force the
    # fleet onto plain single-replica serves.
    assert not _serve_wants_fleet(
        ServeConfig(replicas=1, autoscale=AutoscaleConfig())
    )
