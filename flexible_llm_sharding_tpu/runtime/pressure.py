"""Resource-pressure resilience: a brownout controller that degrades
instead of dying.

The architecture's whole premise is running models far bigger than the
chip by leaning on host RAM, spill disk, and the host->HBM link
(PAPER.md §0) — which makes those three resources exactly where a
production deployment dies first. Before this module every exhaustion
path was fatal: a ``MemoryError`` building a host shard, ``ENOSPC``
writing an activation spill, a saturated link starving every sweep. The
fault layer (PR 3) covers *transient* I/O blips and the fleet (PR 9)
covers replica death; this module covers **sustained resource pressure**
— overload becomes deliberate, reversible load-shedding:

- :class:`PressureMonitor` periodically samples host ``MemAvailable``,
  spill-disk free bytes (``disk_folder``'s filesystem), HBM headroom
  (the allocator's ``bytes_limit - bytes_in_use``), and the host->HBM
  link rate (delta of the executor's process streamed-bytes counter).
  Thresholds live in :class:`~flexible_llm_sharding_tpu.config.PressureConfig`;
  a threshold of 0 disables that signal, and an UNKNOWN sample (no
  /proc, no allocator stats) never trips — the ladder only acts on
  evidence.
- Hard failures the monitor cannot pre-empt — a real (or injected)
  ``MemoryError`` in a shard build, ``ENOSPC`` in a spill write — are
  reported via :func:`note_event` by the hardened paths
  (``runtime/executor.py``, ``runtime/activations.py``) and count as
  pressure for the poll they land in: an observed exhaustion is the
  strongest pressure signal there is.
- :class:`BrownoutController` walks an ordered, **reversible**
  degradation ladder — one level per threshold-pressured poll, straight
  to the shed level on a hard event (an exhaustion that already
  happened means the gentle levers were not enough), and one level back
  down per ``step_down_polls`` consecutive clean polls:

  1. shrink the host shard cache (``hostcache.apply_pressure_cap``:
     LRU-evicts down to ``cache_shrink_frac`` of the budget and pins a
     cap so auto re-resolution cannot grow it back mid-brownout);
     then the LoRA adapter store the same way
     (``adapters.loader.apply_pressure_cap`` — evicted deltas reload in
     one checksummed read), then pooled prefix-KV pages;
  2. evict device residency pins back to streaming
     (``DeviceResidencyTier.pressure_unpin``: future sources stream
     everything; live sources keep their frozen structure);
  3. shed new admissions: every attached ``AdmissionQueue`` rejects
     submits with a typed ``Overloaded`` carrying a retry-after hint
     (in-flight requests keep serving — brownout, not blackout);
  4. drain fleet replicas down to one (``ReplicaFleet.pressure_drain``)
     — the deepest cut, reserved for pressure that survived all of the
     above.

  Every transition emits a ``pressure_step`` trace instant and bumps the
  ``fls_pressure_*`` counter family (ladder level, sheds, cache shrinks,
  pin evictions, replica drains) through the process metrics registry.

The ladder is deliberately conservative about what it touches: levels
with nothing to act on (no cache, no pins, no fleet) still count as
ladder positions — pressure that persists keeps walking toward the
levels that CAN shed load.

Typed hard-failure errors live here too: :class:`HostOOMError` and
:class:`DiskFullError` are ``OSError`` subclasses on purpose — the retry
policy's transient family — so one backoff ladder (and one degrade
semantics: fail the wave, keep the engine) covers an allocation blip
exactly like an NFS blip, while the type names the resource for
operators and tests.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field

from flexible_llm_sharding_tpu.obs import events as obs_journal
from flexible_llm_sharding_tpu.obs import trace as obs_trace
from flexible_llm_sharding_tpu.obs.registry import REGISTRY as _OBS_REGISTRY


class HostOOMError(OSError):
    """A host allocation failed building a shard (MemoryError typed into
    the transient-I/O family): retried under the normal policy — after
    the brownout ladder frees host RAM, a retry can succeed — and on
    exhaustion it degrades like any shard-load failure (the serving
    engine fails only the in-flight waves) instead of killing the
    process."""


class DiskFullError(OSError):
    """``ENOSPC`` on an activation-spill (or cache) write, typed: retried
    under the normal policy (a bounded disk-full episode heals once space
    frees), surfaced with the path on exhaustion — and never leaves a
    truncated spill behind (writes are temp+rename atomic)."""


# Monitored resource names (the tripped-set vocabulary + note_event kinds).
SIGNALS = ("host", "disk", "hbm", "link")


@dataclass(frozen=True)
class PressureSnapshot:
    """One poll's readings. ``None`` = unknown (never trips)."""

    host_available_bytes: int | None = None
    disk_free_bytes: int | None = None
    hbm_free_frac: float | None = None
    link_gbps: float | None = None
    tripped: frozenset = field(default_factory=frozenset)


class PressureMonitor:
    """Samples the four pressure signals and drives the controller.

    Samplers are injectable (tests); the defaults read /proc/meminfo,
    ``os.statvfs(disk_folder)``, the device allocator stats, and the
    executor's process streamed-bytes counter. ``start()`` spawns a
    daemon thread calling ``controller.on_sample(self.sample())`` every
    ``poll_s``; ``close()`` stops it. ``sample()`` itself is thread-safe
    and side-effect-free apart from the link-rate window."""

    def __init__(
        self,
        cfg,
        controller: "BrownoutController",
        host_bytes_fn=None,
        disk_free_fn=None,
        hbm_free_frac_fn=None,
        link_bytes_fn=None,
    ):
        self.pcfg = cfg.pressure
        self._controller = controller
        self._disk_folder = cfg.disk_folder
        self._host_fn = host_bytes_fn or self._default_host_bytes
        self._disk_fn = disk_free_fn or self._default_disk_free
        self._hbm_fn = hbm_free_frac_fn or self._default_hbm_free_frac
        self._link_fn = link_bytes_fn or self._default_link_bytes
        self._link_prev: tuple[float, int] | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- default samplers --------------------------------------------------

    @staticmethod
    def _default_host_bytes() -> int | None:
        from flexible_llm_sharding_tpu.runtime.hostcache import (
            available_host_bytes,
        )

        avail = available_host_bytes()
        return avail if avail > 0 else None  # 0 = unknown (non-Linux)

    def _default_disk_free(self) -> int | None:
        try:
            st = os.statvfs(self._disk_folder)
        except OSError:
            return None  # folder absent / unstatable: unknown, never trips
        return int(st.f_bavail) * int(st.f_frsize)

    @staticmethod
    def _default_hbm_free_frac() -> float | None:
        from flexible_llm_sharding_tpu.utils.metrics import (
            device_memory_stats,
        )

        # {} on the CPU (no allocator stats: unknown, the signal never
        # trips); on a TPU a failed query raises rather than reading as
        # unknown.
        stats = device_memory_stats()
        limit = stats.get("bytes_limit")
        if not limit:
            return None
        return max(0.0, (limit - stats.get("bytes_in_use", 0.0)) / limit)

    @staticmethod
    def _default_link_bytes() -> int:
        from flexible_llm_sharding_tpu.runtime.executor import (
            process_streamed_bytes,
        )

        return process_streamed_bytes()

    # -- sampling ----------------------------------------------------------

    def sample(self) -> PressureSnapshot:
        p = self.pcfg
        host = self._host_fn()
        disk = self._disk_fn()
        hbm = self._hbm_fn()
        # Link rate over the window since the previous sample. Only ever
        # evaluated while bytes are actually flowing (a zero delta means
        # an idle stream, not a dead link — idleness must not trip).
        now = time.monotonic()
        total = self._link_fn()
        link = None
        if self._link_prev is not None:
            dt = now - self._link_prev[0]
            delta = total - self._link_prev[1]
            if dt > 0 and delta > 0:
                link = delta / dt / 1e9
        self._link_prev = (now, total)
        tripped = set()
        if p.host_min_gb > 0 and host is not None and host < p.host_min_gb * 1e9:
            tripped.add("host")
        if p.disk_min_gb > 0 and disk is not None and disk < p.disk_min_gb * 1e9:
            tripped.add("disk")
        if p.hbm_headroom_frac > 0 and hbm is not None and hbm < p.hbm_headroom_frac:
            tripped.add("hbm")
        if p.link_min_gbps > 0 and link is not None and link < p.link_min_gbps:
            tripped.add("link")
        return PressureSnapshot(
            host_available_bytes=host,
            disk_free_bytes=disk,
            hbm_free_frac=hbm,
            link_gbps=link,
            tripped=frozenset(tripped),
        )

    # -- thread ------------------------------------------------------------

    def _run(self) -> None:
        while not self._stop.wait(self.pcfg.poll_s):
            try:
                self._controller.on_sample(self.sample())
            except Exception:  # flscheck: disable=EXC-TAXONOMY: monitor daemon boundary — a sampler/ladder bug must not end pressure monitoring for the process; the next tick retries and the controller's own counters stay scrapeable
                pass

    def start(self) -> "PressureMonitor":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="pressure-monitor", daemon=True
            )
            self._thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None


class BrownoutController:
    """The ordered, reversible degradation ladder.

    ``on_sample`` (monitor thread) walks the level up one per pressured
    poll — a poll is pressured when any threshold tripped OR any hard
    resource event (``note_event``) landed since the last poll — and
    down one per ``step_down_polls`` consecutive clean polls, releasing
    the levels in reverse order. Engage/release actions run OFF the
    controller lock (they take the cache/tier/queue/fleet locks and may
    evict entries); the lock only guards the ladder state and counters.

    Components register themselves: serving engines attach their
    admission queues (``attach_queue`` — a queue attached mid-brownout
    is shed immediately), the fleet attaches itself, and the host cache
    / residency tier are found through their process accessors at engage
    time — a level with nothing to act on is still a ladder position.
    """

    # Ladder levels above 0 (normal), in engage order.
    # spec_backoff leads the ladder: speculative draft compute is pure
    # optional spend (stopping it frees host/chip cycles at unchanged
    # output, and costs only sweeps-per-token to re-earn), so it is the
    # first thing a pressured host stops buying and the last thing a
    # clean host restores on the way down.
    # adapter_evict sits right after the shard-cache shrink: evicted
    # LoRA deltas reload from disk in one checksummed read (cheapest
    # give-back after clean shard-cache bytes), and the cap latch keeps
    # later store resolutions from growing back mid-brownout.
    # kv_evict sits between it and pin eviction: pooled prefix-KV pages
    # spill to checksummed disk (or drop and re-prefill) — cheaper to
    # give back than pinned weights, dearer than a clean shard cache.
    LADDER = (
        "spec_backoff", "cache_shrink", "adapter_evict", "kv_evict",
        "pin_evict", "shed", "replica_drain",
    )

    def __init__(self, cfg):
        self.cfg = cfg
        self.pcfg = cfg.pressure
        self._lock = threading.RLock()
        self.level = 0  # guarded by: _lock
        self._clean_polls = 0  # guarded by: _lock
        self._events_pending = 0  # guarded by: _lock
        self._queues: list = []  # guarded by: _lock
        self._fleet = None  # guarded by: _lock
        self._spec_ctrls: list = []  # guarded by: _lock
        self._saved_cache_budget: int | None = None
        self._saved_adapter_budget: int | None = None
        self._last: PressureSnapshot = PressureSnapshot()
        # Counters (all exported via stats(); COUNTER-EXPORT audited).
        self.steps_up = 0
        self.steps_down = 0
        self.sheds = 0
        self.cache_shrinks = 0
        self.adapter_evictions = 0
        self.kv_evictions = 0
        self.pin_evictions = 0
        self.replica_drains = 0
        self.replica_restores = 0
        self.spec_backoffs = 0
        self.spec_restores = 0
        self.host_oom_events = 0
        self.disk_full_events = 0
        self.link_events = 0
        self.polls = 0

    # -- component registration --------------------------------------------

    def attach_queue(self, queue) -> None:
        """Register a serving engine's admission queue as a shed target.
        A queue attached while the ladder already sits at (or above) the
        shed level starts shedding immediately — a freshly recycled
        replica must not become a brownout bypass."""
        with self._lock:
            if queue not in self._queues:
                self._queues.append(queue)
            shedding = self.level >= self._level_of("shed")
        if shedding:
            queue.set_shedding(self.pcfg.shed_retry_after_s, on_shed=self.note_shed)

    def detach_queue(self, queue) -> None:
        with self._lock:
            if queue in self._queues:
                self._queues.remove(queue)
        queue.clear_shedding()

    def attach_fleet(self, fleet) -> None:
        with self._lock:
            self._fleet = fleet

    def detach_fleet(self, fleet) -> None:
        with self._lock:
            if self._fleet is fleet:
                self._fleet = None

    def attach_spec(self, ctrl) -> None:
        """Register an adaptive speculation controller (serve/spec.py) as
        the spec_backoff lever's target. One attached while the ladder
        already sits at (or above) that level backs off immediately —
        the mid-brownout attach rule the queues follow."""
        with self._lock:
            if ctrl not in self._spec_ctrls:
                self._spec_ctrls.append(ctrl)
            backed_off = self.level >= self._level_of("spec_backoff")
        if backed_off:
            ctrl.pressure_backoff()

    def detach_spec(self, ctrl) -> None:
        with self._lock:
            if ctrl in self._spec_ctrls:
                self._spec_ctrls.remove(ctrl)
        ctrl.pressure_restore()

    # -- event intake ------------------------------------------------------

    def note_event(self, kind: str) -> None:
        """A hard resource failure the monitor could not pre-empt (a real
        or injected host OOM / ENOSPC). Counts as pressure for the poll
        it lands in. Unknown kinds are dropped on purpose — a typo'd
        kind must not silently inflate a real resource's counter (the
        link has no hard-failure event: a saturated link slows, it
        never errors; ``link_events`` counts tripped-link polls
        instead, see ``on_sample``)."""
        with self._lock:
            if kind == "host_oom":
                self.host_oom_events += 1
            elif kind == "disk_full":
                self.disk_full_events += 1
            else:
                return
            self._events_pending += 1
        obs_trace.instant("pressure_event", cat="pressure", kind=kind)

    def note_shed(self) -> None:
        """One admission rejected with Overloaded (queue callback)."""
        with self._lock:
            self.sheds += 1

    # -- the ladder --------------------------------------------------------

    def _level_of(self, name: str) -> int:
        return self.LADDER.index(name) + 1

    def at_or_above(self, name: str) -> bool:
        """True while the ladder is engaged at ``name``'s level or
        higher. The public interlock probe (the autoscaler must never
        grow the fleet while pressure says the MACHINE is the
        bottleneck — at shed, adding a replica adds memory pressure,
        not capacity). Unknown names raise: a typo'd interlock stage
        must fail loudly, not read as 'never engaged'."""
        level = self._level_of(name)  # raises ValueError on unknown
        with self._lock:
            return self.level >= level

    def on_sample(self, snap: PressureSnapshot) -> None:
        """One poll: decide under the lock, act (engage/release) outside
        it. Called from the monitor thread (or directly by tests).

        Escalation policy: a tripped THRESHOLD is anticipatory — walk up
        one level per pressured poll, gentlest lever first. A hard
        resource EVENT (a real or injected OOM/ENOSPC that already
        happened) is proof the gentle levers did not prevent a failure:
        it escalates straight to the shed level (engaging every level on
        the way, in order), and only sustained further pressure reaches
        the replica-drain level above it. Step-down is always one level
        per ``step_down_polls`` consecutive clean polls, released in
        reverse order — hysteresis against flapping."""
        engage_idxs: list[int] = []
        release_idx = None
        with self._lock:
            self.polls += 1
            self._last = snap
            if "link" in snap.tripped:
                # The link has no hard-failure event (a saturated link
                # slows, it never errors): its counter counts the polls
                # where the rate signal tripped.
                self.link_events += 1
            pending, self._events_pending = self._events_pending, 0
            pressured = bool(snap.tripped) or pending > 0
            if pressured:
                self._clean_polls = 0
                target = min(len(self.LADDER), self.level + 1)
                if pending:
                    target = max(target, self._level_of("shed"))
                engage_idxs = list(range(self.level, target))
                self.steps_up += target - self.level
                self.level = target
            else:
                self._clean_polls += 1
                if (
                    self.level > 0
                    and self._clean_polls >= self.pcfg.step_down_polls
                ):
                    self._clean_polls = 0
                    release_idx = self.level - 1
                    self.level -= 1
                    self.steps_down += 1
            level = self.level
        for idx in engage_idxs:
            obs_trace.instant(
                "pressure_step", cat="pressure", direction="up", level=level,
                stage=self.LADDER[idx],
                tripped=sorted(snap.tripped), events=pending,
            )
            obs_journal.emit(
                "pressure_step", direction="up", level=level,
                stage=self.LADDER[idx], tripped=sorted(snap.tripped),
                events=pending,
            )
            self._engage(idx)
        if release_idx is not None:
            obs_trace.instant(
                "pressure_step", cat="pressure", direction="down",
                level=level, stage=self.LADDER[release_idx],
            )
            obs_journal.emit(
                "pressure_step", direction="down", level=level,
                stage=self.LADDER[release_idx],
            )
            self._release(release_idx)

    def _engage(self, idx: int) -> None:
        stage = self.LADDER[idx]
        try:
            if stage == "spec_backoff":
                with self._lock:
                    ctrls = list(self._spec_ctrls)
                for c in ctrls:
                    c.pressure_backoff()
                if ctrls:
                    with self._lock:
                        self.spec_backoffs += len(ctrls)
            elif stage == "cache_shrink":
                from flexible_llm_sharding_tpu.runtime import hostcache

                prev = hostcache.apply_pressure_cap(
                    self.pcfg.cache_shrink_frac
                )
                if prev is not None:
                    with self._lock:
                        self._saved_cache_budget = prev
                        self.cache_shrinks += 1
            elif stage == "adapter_evict":
                from flexible_llm_sharding_tpu.adapters import loader

                prev = loader.apply_pressure_cap(
                    self.pcfg.cache_shrink_frac
                )
                if prev is not None:
                    with self._lock:
                        self._saved_adapter_budget = prev
                        self.adapter_evictions += 1
            elif stage == "kv_evict":
                from flexible_llm_sharding_tpu.runtime import kvpool

                n = kvpool.process_pressure_evict()
                if n:
                    with self._lock:
                        self.kv_evictions += n
            elif stage == "pin_evict":
                from flexible_llm_sharding_tpu.runtime import residency

                tier = residency.process_tier()
                if tier is not None:
                    n = tier.pressure_unpin()
                    if n:
                        with self._lock:
                            self.pin_evictions += n
            elif stage == "shed":
                with self._lock:
                    queues = list(self._queues)
                for q in queues:
                    q.set_shedding(
                        self.pcfg.shed_retry_after_s, on_shed=self.note_shed
                    )
            else:  # replica_drain
                with self._lock:
                    fleet = self._fleet
                if fleet is not None:
                    n = fleet.pressure_drain(keep=1)
                    if n:
                        with self._lock:
                            self.replica_drains += n
        except Exception:  # flscheck: disable=EXC-TAXONOMY: brownout actions are best-effort shedding — a failed ladder step (component mid-teardown) must not kill the monitor; the level is held and the next poll keeps walking
            pass

    def _release(self, idx: int) -> None:
        stage = self.LADDER[idx]
        try:
            if stage == "spec_backoff":
                with self._lock:
                    ctrls = list(self._spec_ctrls)
                for c in ctrls:
                    c.pressure_restore()
                if ctrls:
                    with self._lock:
                        self.spec_restores += len(ctrls)
            elif stage == "cache_shrink":
                from flexible_llm_sharding_tpu.runtime import hostcache

                with self._lock:
                    restore = self._saved_cache_budget
                    self._saved_cache_budget = None
                hostcache.lift_pressure_cap(restore)
            elif stage == "adapter_evict":
                from flexible_llm_sharding_tpu.adapters import loader

                with self._lock:
                    restore = self._saved_adapter_budget
                    self._saved_adapter_budget = None
                loader.lift_pressure_cap(restore)
            elif stage == "kv_evict":
                from flexible_llm_sharding_tpu.runtime import kvpool

                kvpool.process_pressure_restore()
            elif stage == "pin_evict":
                from flexible_llm_sharding_tpu.runtime import residency

                tier = residency.process_tier()
                if tier is not None:
                    tier.pressure_restore()
            elif stage == "shed":
                with self._lock:
                    queues = list(self._queues)
                for q in queues:
                    q.clear_shedding()
            else:  # replica_drain
                with self._lock:
                    fleet = self._fleet
                if fleet is not None:
                    n = fleet.pressure_restore()
                    if n:
                        with self._lock:
                            self.replica_restores += n
        except Exception:  # flscheck: disable=EXC-TAXONOMY: best-effort reversal — a failed restore (component already torn down) must not wedge the monitor; the remaining levels still step down
            pass

    # -- observability -----------------------------------------------------

    def stats(self) -> dict:
        """The ``pressure`` registry source (-> ``fls_pressure_*``)."""
        with self._lock:
            snap = self._last
            out = {
                "level": self.level,
                "steps_up": self.steps_up,
                "steps_down": self.steps_down,
                "sheds": self.sheds,
                "spec_backoffs": self.spec_backoffs,
                "spec_restores": self.spec_restores,
                "cache_shrinks": self.cache_shrinks,
                "adapter_evictions": self.adapter_evictions,
                "kv_evictions": self.kv_evictions,
                "pin_evictions": self.pin_evictions,
                "replica_drains": self.replica_drains,
                "replica_restores": self.replica_restores,
                "host_oom_events": self.host_oom_events,
                "disk_full_events": self.disk_full_events,
                "link_events": self.link_events,
                "polls": self.polls,
            }
        if snap.host_available_bytes is not None:
            out["host_available_bytes"] = snap.host_available_bytes
        if snap.disk_free_bytes is not None:
            out["disk_free_bytes"] = snap.disk_free_bytes
        if snap.hbm_free_frac is not None:
            out["hbm_free_frac"] = round(snap.hbm_free_frac, 4)
        if snap.link_gbps is not None:
            out["link_gbps"] = round(snap.link_gbps, 4)
        return out


# -- process-wide controller -------------------------------------------------
# One controller per process (mirrors hostcache.cache_for / residency
# .tier_for): the serve engine, the fleet, and every executor report into
# the same ladder — shrinking the cache twice because two engines each run
# a private controller would double-punish one resource.

_PROCESS_CONTROLLER: BrownoutController | None = None
_PROCESS_MONITOR: PressureMonitor | None = None
_PROCESS_LOCK = threading.Lock()


def controller_for(cfg) -> BrownoutController | None:
    """The process brownout controller for ``cfg`` (None when
    ``cfg.pressure.enabled`` is off). First enabled caller creates the
    controller, registers the ``pressure`` metrics source, and starts the
    monitor thread; later callers share it (first config's thresholds
    win, the process-singleton precedent)."""
    if not cfg.pressure.enabled:
        return None
    global _PROCESS_CONTROLLER, _PROCESS_MONITOR
    with _PROCESS_LOCK:
        if _PROCESS_CONTROLLER is None:
            ctrl = BrownoutController(cfg)
            _PROCESS_CONTROLLER = ctrl
            _PROCESS_MONITOR = PressureMonitor(cfg, ctrl)
            _OBS_REGISTRY.register("pressure", ctrl.stats)
            _PROCESS_MONITOR.start()
        return _PROCESS_CONTROLLER


def process_controller() -> BrownoutController | None:
    with _PROCESS_LOCK:
        return _PROCESS_CONTROLLER


def note_event(kind: str) -> None:
    """Report a hard resource failure to the process controller, if one
    is running (the hardened failure paths call this unconditionally —
    one ``is None`` check when pressure handling is off). The event is
    ALSO journaled (obs/events.py) whether or not a controller exists:
    an OOM/ENOSPC that really happened is flight-recorder material even
    when the brownout ladder is off. Unknown kinds stay dropped (the
    controller applies the same rule to its counters)."""
    if kind in ("host_oom", "disk_full"):
        # Field named `resource` (not `kind`): the journal reserves
        # `kind` for the event kind itself.
        obs_journal.emit("pressure_event", resource=kind)
    ctrl = process_controller()
    if ctrl is not None:
        ctrl.note_event(kind)


def reset_process_pressure() -> None:
    """Stop the monitor, release every engaged ladder level, and drop the
    process controller (tests). Releasing on the way out restores the
    cache cap / pins / shedding a mid-test brownout left engaged."""
    global _PROCESS_CONTROLLER, _PROCESS_MONITOR
    with _PROCESS_LOCK:
        ctrl, _PROCESS_CONTROLLER = _PROCESS_CONTROLLER, None
        mon, _PROCESS_MONITOR = _PROCESS_MONITOR, None
    if mon is not None:
        mon.close()
    if ctrl is not None:
        while ctrl.level > 0:
            with ctrl._lock:
                idx = ctrl.level - 1
                ctrl.level -= 1
            ctrl._release(idx)
    _OBS_REGISTRY.unregister("pressure")


__all__ = [
    "BrownoutController",
    "DiskFullError",
    "HostOOMError",
    "PressureMonitor",
    "PressureSnapshot",
    "SIGNALS",
    "controller_for",
    "note_event",
    "process_controller",
    "reset_process_pressure",
]
