"""Replica fleet: N serving engines behind a shard-phase-aware router.

PRs 3-4 made one ``ServeEngine`` survive I/O faults and silent corruption,
but the process still had exactly one engine: a wedged or killed engine
took every queued and in-flight request with it. This module runs N
engines (thread-per-engine in one process, all sharing the process host
shard cache so a recycled replica re-warms instantly) behind a ``Router``
(``serve/router.py``) and lifts the PR 3/4 acceptance bar one level:
under replica-level chaos (a whole engine killed or wedged mid-sweep),
every submitted request completes with output token-identical to a single
healthy engine.

The contracts, each loud:

- **Dispatch** goes to the healthiest serving replica by shard-phase
  proximity (time to its next shard-0 admission point, read from the
  engine's sweep watermark) and normalized queue depth.
- **Exactly-once re-dispatch**: every fleet request carries a stable
  ``dispatch_id``. A request orphaned by a dying replica (``WaveAborted``,
  a ``ServeClosed`` cancellation, an engine-fatal error, or a reclaim
  from a wedged engine) is re-dispatched to a surviving replica exactly
  once — never dropped, and never double-served: the caller-facing future
  is first-wins, and outcomes from an attempt the fleet already abandoned
  are discarded (``stale_results``). A re-dispatched request re-prefills
  from its prompt on the new replica (in-flight requests hold their own
  KV, which died with the replica) and — greedy decode over the same
  weights — produces token-identical output. An orphan whose deadline
  already lapsed resolves EXPIRED instead: its time-to-first-token
  contract is lost, and serving it late would steal sweeps from live
  requests.
- **Health**: the monitor polls each replica's metrics registry (the
  PR 8 ``engine_recoveries``/watchdog counters) plus a liveness
  heartbeat — the engine's sweep-progress watermark. Engine-fatal error
  or a busy watermark stalled past ``watchdog_abort_s`` ⇒ **hard-fail**
  (reclaim orphans, re-dispatch, recycle the engine). Recoveries past
  ``router_drain_recoveries`` ⇒ **graceful drain** (stop dispatching,
  let in-flight waves finish, then recycle).
- **Elastic join/leave**: ``add_replica()`` brings a fresh engine online;
  ``remove_replica(drain=True)`` reuses the graceful-drain path,
  ``drain=False`` the hard-fail (orphans re-dispatch) path.

Replica chaos (``faults/inject.py`` sites, registered in
``config.FAULT_SITES`` and docs/faults.md): ``replica_kill`` raises an
engine-fatal ``ReplicaKilled`` from inside the victim's sweep;
``replica_stall`` wedges the engine thread until the monitor declares the
replica dead. One FLEET-level injector draws for both sites across all
replicas — each site's schedule is deterministic in aggregate call count;
which replica eats a given draw depends on thread interleaving (the same
scope note as shared ``max_faults`` budgets in faults/inject.py), which
is exactly what the token-identical acceptance bar must be robust to.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque

from flexible_llm_sharding_tpu.config import FrameworkConfig, ServeConfig
from flexible_llm_sharding_tpu.faults.inject import FaultInjector, InjectedFault
from flexible_llm_sharding_tpu.obs import events as obs_events
from flexible_llm_sharding_tpu.obs import incident as obs_incident
from flexible_llm_sharding_tpu.obs import trace as obs_trace
from flexible_llm_sharding_tpu.obs.registry import REGISTRY, MetricsServer
from flexible_llm_sharding_tpu.serve.autoscale import (
    FleetAutoscaler,
    StaggerController,
)
from flexible_llm_sharding_tpu.serve.engine import ServeEngine
from flexible_llm_sharding_tpu.serve.request import (
    DeadlineExceeded,
    Request,
    RequestStatus,
    RestartPending,
    ServeClosed,
    WaveAborted,
)
from flexible_llm_sharding_tpu.serve.router import Router
from flexible_llm_sharding_tpu.serve.sched import classes as sched_classes
from flexible_llm_sharding_tpu.utils.metrics import RouterMetrics


class ReplicaKilled(RuntimeError):
    """Chaos ``replica_kill``: the whole engine dies mid-sweep. Engine-
    FATAL by design (a RuntimeError, outside the engine's recoverable
    ShardLoadError/SourceClosed/OSError family) — it models a crashed
    replica process, which no source restart can heal. The fleet
    hard-fails the replica and re-dispatches its requests."""


class _Replica:
    """One engine slot. ``state`` transitions (fleet lock): serving ->
    draining|removing -> dead (terminal; the slot is recycled with a fresh
    _Replica or dropped). ``release`` unwedges a chaos-stalled engine
    thread so it can observe its closed queue and exit."""

    def __init__(self, idx: int, engine: ServeEngine, stagger=None):
        self.idx = idx
        self.engine = engine
        self.state = "serving"
        self.release = threading.Event()
        self.stagger = stagger
        # The exact source object mirrored process-wide, for identity-
        # checked unregistration (a recycled slot must not yank the
        # replacement's registration).
        self.source = engine.metrics.registry.collect

    @property
    def serving(self) -> bool:
        return self.state == "serving"

    def snapshot(self) -> dict:
        """Router scoring inputs (lock-free engine reads).
        ``hold_frac`` is this replica's pending stagger hold as a
        fraction of its sweep wall — admission distance the phase term
        must see (a replica about to hold at its boundary is farther
        from admitting than its raw phase says)."""
        eng = self.engine
        pos = eng.sweep_position()
        return {
            "boundary_frac": pos["boundary_frac"],
            "hold_frac": (
                self.stagger.hold_frac(self.idx)
                if self.stagger is not None
                else 0.0
            ),
            "queue_depth": len(eng.queue),
            "active": eng.batcher.active_requests,
            "max_active": eng.serve_cfg.max_active_requests,
        }


@dataclasses.dataclass
class _Dispatch:
    """Fleet-side bookkeeping for one caller request: the caller-facing
    ``outer`` request (its future is what ``submit`` returns), the current
    engine-side ``inner`` attempt, and the attempt count that enforces
    exactly-once re-dispatch (attempts == 2 is final)."""

    outer: Request
    inner: Request | None = None
    replica: _Replica | None = None
    attempts: int = 0


class ReplicaFleet:
    """N ``ServeEngine`` replicas + router + health monitor, presenting
    the single-engine surface (``submit``/``drain``/``shutdown``/
    ``stats``/``error``/``metrics_server``) so the serve CLI drives either
    interchangeably."""

    def __init__(
        self,
        cfg: FrameworkConfig,
        serve_cfg: ServeConfig | None = None,
        tokenizer=None,
        device=None,
        start: bool = True,
    ):
        self.cfg = cfg
        self.serve_cfg = serve_cfg or ServeConfig()
        self._tokenizer = tokenizer
        self._device = device
        # Replicas never open their own endpoint: the fleet serves ONE
        # process-registry endpoint carrying the router counters plus
        # every replica's mirrored sources.
        self._engine_cfg = dataclasses.replace(
            self.serve_cfg, metrics_port=None, replicas=1
        )
        # ONE crash-safe request WAL shared by every replica (serve/wal.py;
        # None when --wal_dir is unset): replicas append to the same
        # segment sequence, recycled replicas inherit the log, and one
        # startup replay (serve/recovery.py) covers the whole fleet.
        from flexible_llm_sharding_tpu.serve.wal import wal_for

        self._wal = wal_for(self.serve_cfg)
        self.metrics = RouterMetrics()
        self.router = Router(
            self.serve_cfg.router_phase_weight,
            self.serve_cfg.router_depth_weight,
        )
        self._injector = FaultInjector.from_config(cfg.faults)
        self._lock = threading.Lock()
        self._replicas: list[_Replica] = []  # guarded by: _lock
        self._dispatches: dict[int, _Dispatch] = {}  # guarded by: _lock
        self._pending: deque[_Dispatch] = deque()  # guarded by: _lock
        self._closed = False  # guarded by: _lock
        self._next_idx = 0  # guarded by: _lock
        self._error: BaseException | None = None
        self._started = False
        obs_trace.ensure_configured(cfg)
        # Flight recorder: armed BEFORE the replicas build, so a replica
        # that dies during construction already journals through it.
        obs_events.ensure_configured(cfg)
        obs_incident.ensure_configured(cfg, self.serve_cfg)
        # Resource-pressure brownout (runtime/pressure.py): at the
        # ladder's deepest level the controller drains this fleet down to
        # one replica (pressure_drain) and restores the population when
        # pressure lifts (pressure_restore). Each replica's engine
        # attaches its own admission queue as a shed target itself.
        from flexible_llm_sharding_tpu.runtime import pressure as _pressure

        self._pressure = _pressure.controller_for(cfg)
        if self._pressure is not None:
            self._pressure.attach_fleet(self)
        # ONE scheduler shared by every replica (serve/sched): tenant
        # rate limits and DRR fairness are fleet-wide — per-replica
        # buckets would multiply every tenant's rate by the replica
        # count as the router spreads its traffic. Preemption decisions
        # stay per-engine (each at its own sweep boundaries). Registered
        # at the fleet endpoint as the process-level `sched` source.
        from flexible_llm_sharding_tpu.serve.sched import SweepScheduler

        self._sched = (
            SweepScheduler(self.serve_cfg.sched)
            if self.serve_cfg.sched.enabled
            else None
        )
        # Bound method kept for shutdown's identity-checked unregister.
        self._sched_source = (
            self._sched.stats if self._sched is not None else None
        )
        if self._sched_source is not None:
            REGISTRY.register("sched", self._sched_source)
        # Closed-loop elasticity + sweep-phase stagger (serve/autoscale
        # .py; docs/autoscale.md). The stagger controller must exist
        # BEFORE the replica build loop (each replica's fleet_hook
        # closes over it); the autoscaler is built after the loop, once
        # the starting population exists to seed its target. Both are
        # None unless autoscale.enabled — the fleet then behaves exactly
        # as before this module existed.
        auto_cfg = self.serve_cfg.autoscale
        self._stagger = (
            StaggerController(auto_cfg)
            if auto_cfg.enabled and auto_cfg.stagger
            else None
        )
        self._fleet_source = (
            self._stagger.stats if self._stagger is not None else None
        )
        if self._fleet_source is not None:
            REGISTRY.register("fleet", self._fleet_source)
        self._autoscaler: FleetAutoscaler | None = None
        self._autoscale_source = None
        # Process-registry registration: the bound method is kept so
        # shutdown's unregister_if identity check matches.
        self._router_source = self.metrics.snapshot
        REGISTRY.register("router", self._router_source)
        self.metrics_server = (
            MetricsServer(REGISTRY, port=self.serve_cfg.metrics_port)
            if self.serve_cfg.metrics_port is not None
            else None
        )
        try:
            for _ in range(self.serve_cfg.replicas):
                rep = self._mk_replica(start=start)
                with self._lock:
                    self._replicas.append(rep)
        except BaseException:
            self.shutdown(drain=False, timeout=1.0)
            raise
        if auto_cfg.enabled:
            # The WAL-replay interlock starts closed only when there is
            # a WAL to replay: the CLI (or embedding host) opens it via
            # mark_replay_complete() once the owed work is re-admitted.
            self._autoscaler = FleetAutoscaler(
                self, auto_cfg, replay_pending=self._wal is not None
            )
            self._autoscale_source = self._autoscaler.stats
            REGISTRY.register("autoscale", self._autoscale_source)
        self._stop = threading.Event()
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="fleet-monitor", daemon=True
        )
        if start:
            self._started = True
            self._monitor.start()
            if self._autoscaler is not None:
                self._autoscaler.start()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ReplicaFleet":
        if not self._started:
            self._started = True
            with self._lock:
                replicas = list(self._replicas)
            for rep in replicas:
                rep.engine.start()
            self._monitor.start()
            if self._autoscaler is not None:
                self._autoscaler.start()
        return self

    def __enter__(self) -> "ReplicaFleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=exc == (None, None, None))

    @property
    def error(self) -> BaseException | None:
        """Fleet-fatal error (monitor death). Per-replica engine faults do
        NOT surface here — surviving replicas absorb them; that is the
        point of the fleet."""
        return self._error

    @property
    def replicas(self) -> list[int]:
        """Serving replica indices (introspection/tests)."""
        with self._lock:
            return [r.idx for r in self._replicas if r.serving]

    def drain(self, timeout: float | None = None) -> bool:
        return self.shutdown(drain=True, timeout=timeout)

    def shutdown(
        self, drain: bool = True, timeout: float | None = None
    ) -> bool:
        if self._pressure is not None:
            self._pressure.detach_fleet(self)
        # Stop the autoscaler FIRST: a scale decision landing while the
        # teardown loop walks the replica list would race it.
        if self._autoscaler is not None:
            self._autoscaler.close()
        with self._lock:
            self._closed = True
            pending = list(self._pending)
            self._pending.clear()
        for disp in pending:
            self._finish_error(
                disp,
                ServeClosed("replica fleet shut down before dispatch"),
                RequestStatus.CANCELLED,
            )
        if self._started:
            self._stop.set()
            self._monitor.join(timeout=5.0)
        # Snapshot AFTER the monitor stops: a recycle racing the shutdown
        # could otherwise swap in a fresh engine this loop never tears
        # down (_recycle itself drops the slot once _closed is set).
        with self._lock:
            replicas = list(self._replicas)
        ok = True
        for rep in replicas:
            rep.release.set()  # unwedge any chaos-stalled engine thread
            ok = rep.engine.shutdown(drain=drain, timeout=timeout) and ok
            REGISTRY.unregister_if(f"replica{rep.idx}", rep.source)
        if self.metrics_server is not None:
            self.metrics_server.close()
        REGISTRY.unregister_if("router", self._router_source)
        if self._sched_source is not None:
            REGISTRY.unregister_if("sched", self._sched_source)
        if self._autoscale_source is not None:
            REGISTRY.unregister_if("autoscale", self._autoscale_source)
        if self._fleet_source is not None:
            REGISTRY.unregister_if("fleet", self._fleet_source)
        return ok

    def shutdown_for_restart(self, timeout: float | None = None) -> bool:
        """Fleet-wide graceful restart (the ``ServeEngine.
        shutdown_for_restart`` surface): every replica drains at its next
        sweep boundary into the SHARED WAL, parked/pending dispatches
        resolve ``RestartPending`` (their inner attempts' admission
        records stay open for replay), and the fleet exits clean. One
        replay at the next boot re-admits everything. Requires the WAL;
        without one this is ``shutdown(drain=False)``."""
        if self._wal is None:
            return self.shutdown(drain=False, timeout=timeout)
        if self._pressure is not None:
            self._pressure.detach_fleet(self)
        if self._autoscaler is not None:
            self._autoscaler.close()
        with self._lock:
            self._closed = True
            pending = list(self._pending)
            self._pending.clear()
        for disp in pending:
            self._finish_error(
                disp,
                RestartPending(
                    "replica fleet restarting; request parked for replay"
                ),
                RequestStatus.CANCELLED,
            )
        if self._started:
            self._stop.set()
            self._monitor.join(timeout=5.0)
        with self._lock:
            replicas = list(self._replicas)
        ok = True
        for rep in replicas:
            rep.release.set()
            ok = rep.engine.shutdown_for_restart(timeout=timeout) and ok
            REGISTRY.unregister_if(f"replica{rep.idx}", rep.source)
        if self.metrics_server is not None:
            self.metrics_server.close()
        REGISTRY.unregister_if("router", self._router_source)
        if self._sched_source is not None:
            REGISTRY.unregister_if("sched", self._sched_source)
        if self._autoscale_source is not None:
            REGISTRY.unregister_if("autoscale", self._autoscale_source)
        if self._fleet_source is not None:
            REGISTRY.unregister_if("fleet", self._fleet_source)
        return ok

    # -- replica lifecycle -------------------------------------------------

    def _mk_replica(self, start: bool = True, device=None) -> _Replica:
        """Build one engine slot (outside the fleet lock: construction
        reads config.json and builds a weight source). One replica per
        device: unless the caller pinned the whole fleet to one ``device``,
        a new replica goes to the local device that holds the fewest (the
        first N land on N distinct chips); a recycled slot passes its
        predecessor's ``device`` and stays where it was."""
        with self._lock:
            idx = self._next_idx
            self._next_idx += 1
            if device is None:
                device = self._device
            if device is None:
                import jax

                held = [r.engine.device for r in self._replicas]
                device = min(jax.local_devices(), key=held.count)
        engine = ServeEngine(
            self.cfg,
            self._engine_cfg,
            tokenizer=self._tokenizer,
            device=device,
            start=False,
            # No bare process-wide 'serve'/... mirrors: with N replicas
            # last-wins would expose one arbitrary replica as THE process
            # family; the replica<idx> registration below is the mirror.
            process_metrics_mirror=False,
            # Fleet-wide scheduling state: rate limits and fairness must
            # not multiply by the replica count.
            scheduler=self._sched,
            # The fleet-shared request WAL: a recycled replica inherits
            # the same log, so per-replica segment sequences never fork.
            wal=self._wal,
        )
        rep = _Replica(idx, engine, stagger=self._stagger)
        if self._injector is not None or self._stagger is not None:
            engine.fleet_hook = (
                lambda shard_pos, rep=rep: self._fleet_step(rep, shard_pos)
            )
        # Per-replica visibility at the fleet endpoint: the replica's own
        # engine registry (serve counters, retries, integrity, watchdog)
        # flattens to fls_replica<idx>_<source>_<key> gauges.
        REGISTRY.register(f"replica{idx}", rep.source)
        if start:
            engine.start()
        return rep

    def add_replica(self) -> int:
        """Elastic join: bring one more engine online and start routing to
        it. Returns the new replica's index."""
        rep = self._mk_replica(start=self._started)
        with self._lock:
            if self._closed:
                closed = True
            else:
                closed = False
                self._replicas.append(rep)
        if closed:
            rep.engine.shutdown(drain=False, timeout=1.0)
            REGISTRY.unregister_if(f"replica{rep.idx}", rep.source)
            raise ServeClosed("replica fleet is shut down")
        self.metrics.count("replicas_added")
        obs_trace.instant("replica_added", cat="fleet", replica=rep.idx)
        if self._stagger is not None:
            self._stagger.note_membership_change()
        self._flush_pending()
        return rep.idx

    def remove_replica(
        self,
        idx: int | None = None,
        drain: bool = True,
        timeout: float | None = 60.0,
    ) -> bool:
        """Elastic leave. ``drain=True`` reuses the graceful-drain path
        (stop dispatching, serve out queued + in-flight, then retire) and
        blocks up to ``timeout`` for completion; ``drain=False`` hard-
        fails the replica immediately (its requests re-dispatch to
        survivors). ``idx=None`` picks any serving replica. Removing the
        last serving replica is refused — a fleet with zero replicas can
        only park requests."""
        with self._lock:
            live = [r for r in self._replicas if r.serving]
            target = next(
                (r for r in live if idx is None or r.idx == idx), None
            )
            if target is None:
                raise ValueError(
                    f"no serving replica {'(any)' if idx is None else idx} "
                    f"to remove (serving: {[r.idx for r in live]})"
                )
            if len(live) <= 1:
                raise ValueError("cannot remove the last serving replica")
            # Claim the slot ATOMICALLY with the last-replica check: two
            # racing removals on a 2-replica fleet must not both pass the
            # guard and empty the fleet for good (removed slots are never
            # recycled).
            target.state = "removing"
        if not drain:
            self._hard_fail(target, "removed without drain")
            return True
        obs_trace.instant(
            "replica_drain", cat="fleet", replica=target.idx, remove=True
        )
        obs_events.emit("replica_drain", replica=target.idx, remove=True)
        deadline = (
            time.monotonic() + timeout if timeout is not None else None
        )
        while True:
            with self._lock:
                if target not in self._replicas:
                    return True
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(min(self.serve_cfg.router_health_poll_s, 0.05))

    def _start_drain(self, rep: _Replica) -> None:
        """Monitor auto-drain (flaky-but-alive replica): drain then
        recycle. Removal claims its slot directly in remove_replica."""
        with self._lock:
            if rep.state != "serving":
                return
            rep.state = "draining"
        obs_trace.instant(
            "replica_drain", cat="fleet", replica=rep.idx, remove=False
        )
        obs_events.emit("replica_drain", replica=rep.idx, remove=False)

    def _complete_drain(self, rep: _Replica) -> None:
        """Monitor path: the draining replica is idle — retire its engine
        (serves out nothing; the queue is empty) and recycle or drop."""
        with self._lock:
            removing = rep.state == "removing"
            rep.state = "dead"
        rep.engine.shutdown(drain=True, timeout=30.0)
        REGISTRY.unregister_if(f"replica{rep.idx}", rep.source)
        self.metrics.count("replicas_drained")
        obs_trace.instant("replica_drained", cat="fleet", replica=rep.idx)
        if removing:
            self._drop(rep)
        else:
            self._recycle(rep)

    def _hard_fail(self, rep: _Replica, reason: str) -> None:
        """Dead replica: reclaim every request it still holds, re-dispatch
        each to a survivor (exactly once), retire the engine, and recycle
        the slot (unless it was being removed)."""
        with self._lock:
            if rep.state == "dead":
                return
            removing = rep.state == "removing"
            rep.state = "dead"
        self.metrics.count("replicas_dead")
        obs_trace.instant(
            "replica_dead", cat="fleet", replica=rep.idx, reason=reason
        )
        obs_events.emit("replica_dead", replica=rep.idx, reason=reason)
        rep.release.set()  # unwedge a chaos-stalled thread so it can exit
        orphans = rep.engine.reclaim_inflight()
        rep.engine.shutdown(drain=False, timeout=2.0)
        REGISTRY.unregister_if(f"replica{rep.idx}", rep.source)
        for inner in orphans:
            self._handle_orphan(inner)
        if removing:
            self._drop(rep)
        else:
            self._recycle(rep)

    def _recycle(self, rep: _Replica) -> None:
        """Replace a dead/drained slot with a fresh engine (same config;
        the shared host shard cache re-warms it instantly)."""
        with self._lock:
            if self._closed:
                if rep in self._replicas:
                    self._replicas.remove(rep)
                return
        new = self._mk_replica(start=self._started, device=rep.engine.device)
        with self._lock:
            # Re-check under the lock: shutdown() may have closed the
            # fleet while the fresh engine was being built — appending it
            # now would leak a running engine (and its replica<idx>
            # registration) that no teardown loop will ever see.
            aborted = self._closed
            if not aborted:
                if rep in self._replicas:
                    self._replicas[self._replicas.index(rep)] = new
                else:
                    self._replicas.append(new)
        if aborted:
            new.engine.shutdown(drain=False, timeout=1.0)
            REGISTRY.unregister_if(f"replica{new.idx}", new.source)
            with self._lock:
                if rep in self._replicas:
                    self._replicas.remove(rep)
            return
        self.metrics.count("replicas_recycled")
        if self._stagger is not None:
            self._stagger.forget(rep.idx)
            self._stagger.note_membership_change()
        obs_trace.instant(
            "replica_recycled", cat="fleet", replica=rep.idx,
            new_replica=new.idx,
        )
        obs_events.emit(
            "replica_recycled", replica=rep.idx, new_replica=new.idx
        )
        self._flush_pending()

    def _drop(self, rep: _Replica) -> None:
        with self._lock:
            if rep in self._replicas:
                self._replicas.remove(rep)
        self.metrics.count("replicas_removed")
        if self._stagger is not None:
            self._stagger.forget(rep.idx)
            self._stagger.note_membership_change()

    # -- brownout (runtime/pressure.py) ------------------------------------

    def pressure_drain(self, keep: int = 1) -> int:
        """Brownout level 4: gracefully retire all but ``keep`` serving
        replicas — each drained slot serves out its queued and in-flight
        requests (the monitor's ``_complete_drain`` path), then is
        DROPPED rather than recycled (recycling would rebuild the engine
        the ladder just shed). Non-blocking: returns how many replicas
        were marked for removal. ``pressure_restore`` brings the
        population back to ``serve_cfg.replicas`` once pressure lifts."""
        marked: list[int] = []
        with self._lock:
            live = [r for r in self._replicas if r.serving]
            for rep in live[max(keep, 1):]:
                # The "removing" state rides the existing graceful-drain
                # machinery; the >= 1 floor mirrors remove_replica's
                # last-serving-replica refusal.
                rep.state = "removing"
                marked.append(rep.idx)
        for idx in marked:
            obs_trace.instant(
                "replica_drain", cat="fleet", replica=idx, remove=True,
                pressure=True,
            )
            obs_events.emit(
                "replica_drain", replica=idx, remove=True, pressure=True
            )
        return len(marked)

    def pressure_restore(self) -> int:
        """Reverse :meth:`pressure_drain`: add replicas back up to the
        CURRENT population target — the autoscaler's target when one is
        running, else the configured ``serve_cfg.replicas`` — so a
        brownout that fires mid-scale does not snap the fleet back to a
        stale boot-time size. Returns how many were added. Safe to call
        when nothing was drained (no-op) or after shutdown (0)."""
        restored = 0
        while True:
            target = self.population_target()
            with self._lock:
                if self._closed:
                    return restored
                deficit = target - len(
                    [r for r in self._replicas if r.serving]
                )
            if deficit <= 0:
                return restored
            try:
                self.add_replica()
            except ServeClosed:
                return restored
            restored += 1

    # -- per-shard fleet hook (stagger + chaos) ----------------------------

    def _fleet_step(self, rep: _Replica, shard_pos: int) -> None:
        """The composite ``engine.fleet_hook``: fired from inside the
        replica's engine thread at every shard step. Shard 0 is the
        sweep boundary — the only point where a stagger hold is safe
        (no wave is mid-flight), so the hold happens before any chaos
        fault site can kill the step."""
        if self._stagger is not None and shard_pos == 0:
            hold = self._stagger.on_boundary(rep.idx, time.monotonic())
            if hold > 0.0:
                self._hold_at_boundary(rep, hold)
        if self._injector is not None:
            self._chaos_step(rep, shard_pos)

    def _hold_at_boundary(self, rep: _Replica, hold: float) -> None:
        """Park a replica's engine thread at its sweep-0 boundary to
        shift its phase. The hold is capped below the liveness watchdog
        (a correction must never read as a stall) and sliced so the
        replica's release event — set on hard-fail AND by fleet
        shutdown before engine teardown — interrupts it promptly."""
        if self.serve_cfg.watchdog_abort_s > 0:
            hold = min(hold, self.serve_cfg.watchdog_abort_s / 4.0)
        deadline = time.monotonic() + hold
        while True:
            left = deadline - time.monotonic()
            if left <= 0 or rep.release.wait(min(left, 0.05)):
                break

    # -- chaos -------------------------------------------------------------

    def _chaos_step(self, rep: _Replica, shard_pos: int) -> None:
        """Replica-level fault sites, fired from INSIDE the replica's
        engine thread at every shard step of its sweep. ``replica_kill``
        raises the engine-fatal ``ReplicaKilled`` (the whole engine dies
        mid-sweep, futures fail, the fleet re-dispatches and recycles);
        ``replica_stall`` wedges THIS thread until the health monitor
        declares the replica dead and releases it — the liveness-
        watermark path, which no in-engine watchdog can recover because
        the stall is in compute, not in the weight source."""
        inj = self._injector
        if inj is None:
            return
        try:
            inj.fire("replica_kill", detail=f"replica{rep.idx} shard{shard_pos}")
        except InjectedFault as e:
            obs_trace.instant(
                "replica_kill", cat="fleet", replica=rep.idx,
                shard_idx=shard_pos,
            )
            raise ReplicaKilled(
                f"chaos replica_kill: replica {rep.idx} died at shard "
                f"{shard_pos}"
            ) from e
        try:
            inj.fire("replica_stall", detail=f"replica{rep.idx} shard{shard_pos}")
        except InjectedFault:
            obs_trace.instant(
                "replica_stall", cat="fleet", replica=rep.idx,
                shard_idx=shard_pos,
            )
            rep.release.wait()  # wedged until hard-fail (or fleet shutdown)

    # -- dispatch ----------------------------------------------------------

    def submit(
        self,
        prefix: str,
        suffixes,
        max_new_tokens: int | None = None,
        deadline_s: float | None = None,
        callback=None,
        slo_class: str | None = None,
        tenant_id: str | None = None,
        adapter_id: str | None = None,
        client_id=None,
    ) -> Request:
        """Enqueue one request (any thread) — the ``ServeEngine.submit``
        surface. The returned request's future resolves from whichever
        replica ultimately serves it; a mid-flight replica death is
        invisible to the caller beyond latency. SLO class/tenant and the
        LoRA ``adapter_id`` ride every attempt: the replica's own
        scheduler fair-queues and may preempt for them, the router
        biases interactive dispatch toward the replica nearest its
        shard-0 boundary, and every replica resolves the adapter from
        the shared process store."""
        slo = sched_classes.parse_class(slo_class)
        if deadline_s is None:
            deadline_s = sched_classes.class_deadline_s(
                self.serve_cfg.sched, slo
            )
        if deadline_s is None and self.serve_cfg.default_deadline_s > 0:
            deadline_s = self.serve_cfg.default_deadline_s
        req = Request(
            prefix=prefix,
            suffixes=tuple(suffixes),
            max_new_tokens=(
                max_new_tokens
                if max_new_tokens is not None
                else self.serve_cfg.default_max_new_tokens
            ),
            deadline=(
                time.monotonic() + deadline_s
                if deadline_s is not None and deadline_s > 0
                else None
            ),
            callback=callback,
            slo_class=slo,
            tenant_id=tenant_id if tenant_id is not None else "default",
            adapter_id=adapter_id,
            client_id=client_id,
        )
        return self.submit_request(req)

    def submit_request(self, req: Request) -> Request:
        """Enqueue a pre-built request — the same surface as
        ``ServeEngine.submit_request``, so restart replay
        (serve/recovery.py) re-admits through ONE interface whether the
        process serves a single engine or a fleet. A replayed request
        arrives with its WAL id already set; the first inner attempt
        inherits it, so the reopen admission record lands under the same
        durable identity."""
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        req.dispatch_id = req.request_id  # the stable dispatch id
        disp = _Dispatch(outer=req)
        with self._lock:
            closed = self._closed
            if not closed:
                self._dispatches[req.request_id] = disp
        if closed:
            req.fail(
                ServeClosed("replica fleet is shut down"),
                RequestStatus.CANCELLED,
            )
            return req
        self._dispatch(disp)
        return req

    def _dispatch(self, disp: _Dispatch, redispatch: bool = False) -> None:
        outer = disp.outer
        if outer.expired():
            # The deadline lapsed while orphaned/parked: EXPIRED, never
            # re-dispatched — its TTFT contract is already lost, and a
            # late re-serve would steal sweeps from live requests.
            if redispatch:
                self.metrics.count("expired_orphans")
            self._finish_error(
                disp,
                DeadlineExceeded(
                    f"request {outer.request_id} deadline passed before "
                    f"{'re-' if redispatch else ''}dispatch"
                ),
                RequestStatus.EXPIRED,
            )
            return
        failed_on = disp.replica if redispatch else None
        with self._lock:
            if self._closed:
                choice = "closed"
                replica = None
            else:
                # Class-aware dispatch (serve/sched): interactive work
                # weighs boundary proximity harder, landing on the
                # replica whose next shard-0 admission point is soonest.
                bias = (
                    self.serve_cfg.sched.interactive_phase_boost
                    if (
                        self.serve_cfg.sched.enabled
                        and outer.slo_class == sched_classes.INTERACTIVE
                    )
                    else 1.0
                )
                replica = self.router.pick(
                    self._replicas, exclude=failed_on, phase_bias=bias
                )
                if replica is None:
                    # No serving replica right now (all dead/draining):
                    # park; the monitor re-dispatches when one recovers.
                    self._pending.append(disp)
                    choice = "parked"
                else:
                    choice = "dispatched"
                    prev = disp.inner
                    inner = Request(
                        prefix=outer.prefix,
                        suffixes=outer.suffixes,
                        max_new_tokens=outer.max_new_tokens,
                        deadline=outer.deadline,
                        callback=self._inner_terminal,
                        dispatch_id=outer.request_id,
                        # A RE-dispatch is work the fleet accepted before
                        # the original replica died: it must not be shed
                        # Overloaded at the survivor's front door
                        # (brownout sheds NEW admissions, never strands
                        # already-accepted in-flight work).
                        shed_exempt=redispatch,
                        slo_class=outer.slo_class,
                        tenant_id=outer.tenant_id,
                        adapter_id=outer.adapter_id,
                        # Durable identity (serve/wal.py): every attempt
                        # for one fleet request shares one WAL id — a
                        # re-dispatch REOPENS it, a replayed request's
                        # first attempt inherits it from the outer — so
                        # replay/compaction fold all attempts into one
                        # request, exactly like dispatch_id does in RAM.
                        wal_id=(
                            prev.wal_id if prev is not None else outer.wal_id
                        ),
                        client_id=outer.client_id,
                    )
                    disp.inner = inner
                    disp.replica = replica
                    disp.attempts += 1
        if choice == "closed":
            self._finish_error(
                disp,
                ServeClosed("replica fleet is shut down"),
                RequestStatus.CANCELLED,
            )
            return
        if choice == "parked":
            return
        self.metrics.count("redispatches" if redispatch else "dispatches")
        if redispatch:
            obs_trace.instant(
                "redispatch", cat="fleet", request_id=outer.request_id,
                replica=replica.idx,
            )
            obs_events.emit(
                "redispatch", request_id=outer.request_id,
                replica=replica.idx, attempts=disp.attempts,
            )
        # Outside the fleet lock: queue.submit may resolve synchronously
        # (backpressure/chaos rejection -> _inner_terminal re-enters).
        replica.engine.submit_request(inner)

    def _flush_pending(self) -> None:
        with self._lock:
            batch = list(self._pending)
            self._pending.clear()
        for disp in batch:
            # attempts >= 1 means a previous attempt failed on a replica:
            # flushing it is the re-dispatch.
            self._dispatch(disp, redispatch=disp.attempts >= 1)

    # -- terminal outcomes -------------------------------------------------

    def _inner_terminal(self, inner: Request) -> None:
        """Per-attempt callback — the only consumer of engine-side
        outcomes. Maps the inner request's terminal state back to exactly
        one caller-facing future via the stable dispatch id, discarding
        outcomes from attempts the fleet already abandoned."""
        did = inner.dispatch_id
        with self._lock:
            disp = self._dispatches.get(did) if did is not None else None
            stale = disp is None or disp.inner is not inner
            replica = disp.replica if not stale else None
            attempts = disp.attempts if not stale else 0
        if stale:
            self.metrics.count("stale_results")
            return
        if inner.status is RequestStatus.DONE:
            self._finish_result(disp, inner)
            return
        err = inner.future.exception(timeout=0)
        if inner.status is RequestStatus.EXPIRED:
            self._finish_error(disp, err, RequestStatus.EXPIRED)
            return
        # Orphan family: a recoverable wave abort, a shutdown cancellation
        # (replica recycling under it), or anything failed by an engine
        # that has gone fatal. Everything else (backpressure rejection,
        # a malformed request failing tokenization) is the request's own
        # outcome and propagates.
        orphaned = isinstance(err, (WaveAborted, ServeClosed, ReplicaKilled)) or (
            replica is not None and replica.engine.error is not None
        )
        if orphaned and attempts == 1:
            self._dispatch(disp, redispatch=True)
        else:
            self._finish_error(disp, err, inner.status)

    def _handle_orphan(self, inner: Request) -> None:
        """Reclaimed orphan (dead replica): re-dispatch exactly once, or
        propagate if this was already the re-dispatch."""
        did = inner.dispatch_id
        with self._lock:
            disp = self._dispatches.get(did) if did is not None else None
            stale = disp is None or disp.inner is not inner
            attempts = disp.attempts if not stale else 0
        if stale:
            self.metrics.count("stale_results")
            return
        if attempts == 1:
            self._dispatch(disp, redispatch=True)
        else:
            self._finish_error(
                disp, inner.future.exception(timeout=0), RequestStatus.FAILED
            )

    def _finish_result(self, disp: _Dispatch, inner: Request) -> None:
        with self._lock:
            self._dispatches.pop(disp.outer.request_id, None)
        outer = disp.outer
        # Fleet-level timings: TTFT/latency measure from the ORIGINAL
        # submission (a re-dispatch's delay is real caller latency).
        outer.admitted_at = inner.admitted_at
        outer.first_token_at = inner.first_token_at
        res = inner.future.result(timeout=0)
        outer.resolve(res.scores, res.updated, res.tokens)

    def _finish_error(
        self, disp: _Dispatch, err: BaseException | None, status: RequestStatus
    ) -> None:
        with self._lock:
            self._dispatches.pop(disp.outer.request_id, None)
        disp.outer.fail(
            err
            if err is not None
            else RuntimeError("request failed with no recorded error"),
            status,
        )

    # -- health monitor ----------------------------------------------------

    def _monitor_loop(self) -> None:
        while not self._stop.wait(self.serve_cfg.router_health_poll_s):
            try:
                self._poll_health()
                self._flush_pending()
            except Exception as e:  # flscheck: disable=EXC-TAXONOMY: fleet health-monitor daemon — a polling bug must not stop failover for every replica; the error is recorded on self._error and surfaced via fleet.error/stats
                self._error = e

    def _poll_health(self) -> None:
        now = time.monotonic()
        with self._lock:
            replicas = list(self._replicas)
        serving = 0
        phases: dict[int, float] = {}
        for rep in replicas:
            eng = rep.engine
            if rep.state == "serving":
                serving += 1
                pos = eng.sweep_position()
                if pos["busy"] and pos["n_shards"] > 0:
                    phases[rep.idx] = pos["shard_pos"] / pos["n_shards"]
                stalled = (
                    self.serve_cfg.watchdog_abort_s > 0
                    and pos["busy"]
                    and now - pos["watermark"]
                    > self.serve_cfg.watchdog_abort_s
                )
                if eng.error is not None:
                    self._hard_fail(
                        rep, f"engine-fatal: {type(eng.error).__name__}"
                    )
                elif stalled:
                    self._hard_fail(
                        rep,
                        "liveness watermark stalled "
                        f"{now - pos['watermark']:.1f}s",
                    )
                elif (
                    self.serve_cfg.router_drain_recoveries > 0
                    # The registry-backed ServingMetrics counter — the
                    # same value the metrics endpoint exports — read
                    # directly instead of collecting every source of
                    # every replica on every poll tick.
                    and eng.metrics.counter("engine_recoveries")
                    >= self.serve_cfg.router_drain_recoveries
                ):
                    self._start_drain(rep)
            elif rep.state in ("draining", "removing"):
                if len(eng.queue) == 0 and not eng.batcher.waves:
                    self._complete_drain(rep)
        self.metrics.gauge("replicas_serving", serving)
        self.metrics.gauge("replicas_total", len(replicas))
        if self._stagger is not None:
            self._stagger.observe(phases)
        with self._lock:
            self.metrics.gauge("pending_parked", len(self._pending))

    # -- autoscaler surface ------------------------------------------------

    def population(self) -> int:
        """Serving replica count — the autoscaler's notion of fleet
        size (draining/removing slots are already leaving)."""
        with self._lock:
            return sum(1 for r in self._replicas if r.serving)

    def serving_engines(self) -> list:
        """Engines of the serving replicas (burn-rate sampling)."""
        with self._lock:
            return [r.engine for r in self._replicas if r.serving]

    def drains_in_flight(self) -> int:
        """Replicas currently leaving (draining or removing) — a shrink
        decision must wait until this hits zero."""
        with self._lock:
            return sum(
                1 for r in self._replicas
                if r.state in ("draining", "removing")
            )

    def queue_frac(self) -> float:
        """Fleet-wide queued-work fraction: parked + per-replica queued
        requests over the fleet's total admission capacity
        (``queue_capacity`` per serving replica). Capped at 1.0 — an
        over-full park deque is 'saturated', not 'more than full'."""
        with self._lock:
            engines = [r.engine for r in self._replicas if r.serving]
            queued = len(self._pending)
        queued += sum(len(eng.queue) for eng in engines)
        cap = self.serve_cfg.queue_capacity * max(1, len(engines))
        return min(1.0, queued / max(1, cap))

    def population_target(self) -> int:
        """The population the fleet is currently trying to hold: the
        autoscaler's live target when one is running, else the
        configured boot-time ``serve_cfg.replicas``."""
        auto = self._autoscaler
        if auto is not None:
            return auto.target
        return self.serve_cfg.replicas

    def mark_replay_complete(self) -> None:
        """WAL replay finished (cli._replay_open): release the
        autoscaler's first-decision gate. No-op without one."""
        auto = self._autoscaler
        if auto is not None:
            auto.mark_replay_complete()

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict:
        """Fleet stats line: router counters/gauges + per-replica engine
        stats (each the same registry-assembled dict a single engine's
        stats line prints), plus the autoscale/stagger controller
        snapshots when elasticity is on."""
        out: dict = {"event": "fleet_stats", "router": self.metrics.snapshot()}
        if self._autoscaler is not None:
            out["autoscale"] = self._autoscaler.stats()
        if self._stagger is not None:
            out["stagger"] = self._stagger.stats()
        with self._lock:
            replicas = list(self._replicas)
        out["replicas"] = {
            str(rep.idx): {
                "state": rep.state,
                "device": str(rep.engine.device),
                **rep.engine.stats(),
            }
            for rep in replicas
        }
        return out


__all__ = ["ReplicaFleet", "ReplicaKilled"]
