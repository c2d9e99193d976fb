"""Observability: structured metrics, HBM stats, profiler hooks.

The reference's observability is a wall-clock counter around the weight load
printed at the end (``/root/reference/utils.py:223,230-233,304``) plus tqdm
bars. Here (SURVEY.md §5): the same load-time counter, plus per-shard
structured events, tokens/sec/chip, peak HBM from the runtime's allocator
stats, and a ``jax.profiler`` trace context for Perfetto/XProf dumps.
"""

from __future__ import annotations

import contextlib
import json
import sys
import time
from dataclasses import dataclass, field

from flexible_llm_sharding_tpu.obs import events as obs_events
from flexible_llm_sharding_tpu.obs import trace as obs_trace


def device_memory_stats(device=None) -> dict[str, float]:
    """Allocator stats for one chip (bytes). Empty on the CPU backend, whose
    devices report none. A TPU reports them (``bytes_limit``,
    ``peak_bytes_in_use``; seen on the v5e by chip_smoke.py), so there a
    query that fails or comes back empty raises instead of reading as "no
    stats" — every HBM gate and peak figure downstream depends on it."""
    import jax

    # local_devices, not devices: on a multi-host cluster jax.devices()[0]
    # is process 0's chip, and MemoryStats on a non-addressable device
    # raises on every other rank.
    device = device or jax.local_devices()[0]
    stats = device.memory_stats()
    if not stats:
        if device.platform == "tpu":
            raise RuntimeError(
                f"{device} ({device.device_kind}) reports no memory_stats()"
            )
        return {}
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if key in stats:
            out[key] = float(stats[key])
    return out


def peak_hbm_gb(device=None) -> float | None:
    s = device_memory_stats(device)
    return s["peak_bytes_in_use"] / 1e9 if "peak_bytes_in_use" in s else None


def _host_rss_bytes() -> dict[str, int]:
    """``{"peak": VmHWM, "anon": RssAnon}`` in bytes from
    ``/proc/self/status``; empty off-Linux. Early-exits once both keys are
    parsed (RssAnon follows VmHWM) — this runs on every sampler tick."""
    out: dict[str, int] = {}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    out["peak"] = int(line.split()[1]) * 1024
                elif line.startswith("RssAnon:"):
                    out["anon"] = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    return out


def host_rss_gb() -> dict[str, float]:
    """Host memory (GB): ``peak`` (VmHWM — peak RSS, which INCLUDES
    file-backed pages the mmap checkpoint loader faulted in, so on an
    unpressured host it can approach the full model size) and ``anon``
    (RssAnon — the process's own private buffers, the number that witnesses
    the streaming design's host-memory bound). Empty off-Linux."""
    return {k: v / 1e9 for k, v in _host_rss_bytes().items()}


class LiveArrayPeakSampler:
    """Peak device-resident bytes, sampled from ``jax.live_arrays()``.

    Stand-in for the CPU backend, whose devices report no allocator stats
    (``memory_stats() is None``); on a TPU the allocator's own peak is the
    source (``peak_hbm_gb``) and this class only supplies the host-RSS
    sample. A daemon thread samples the total bytes of live JAX arrays.
    This counts weights, activations, and queued prefetch shards — everything
    the framework holds — but NOT XLA's internal scratch inside a running
    executable; pair it with ``compiled_memory_analysis`` for that side.
    Use as a context manager; read ``.peak_gb`` after exit.
    """

    def __init__(self, interval_s: float = 0.05):
        self.interval_s = interval_s
        self.peak_bytes = 0
        # Peak ANON host RSS sampled alongside: VmHWM counts mmapped
        # checkpoint pages, RssAnon is the process's own buffers — but
        # RssAnon has no kernel-tracked high-water mark, so sample it.
        self.peak_anon_bytes = 0
        self._live_arrays = True  # decided at __enter__
        self._stop = None
        self._thread = None

    def _sample(self) -> None:
        import jax
        import numpy as np

        def device_bytes(a) -> int:
            # Actual per-device buffer bytes, from sharding METADATA only: a
            # replicated array's .nbytes is its logical global size (which
            # would undercount tp-replication), and touching .data would
            # materialize view arrays that the next sample then counts.
            # Donated/deleted arrays hold no HBM.
            try:
                if a.is_deleted():
                    return 0
                sh = a.sharding
                shard_elems = int(np.prod(sh.shard_shape(a.shape)))
                return shard_elems * a.dtype.itemsize * len(sh.addressable_devices)
            except Exception:
                return a.nbytes

        # Host sample first: it has no JAX dependency and must not be
        # skipped when live-array enumeration fails (backend not up yet).
        anon = _host_rss_bytes().get("anon")
        if anon is not None:
            self.peak_anon_bytes = max(self.peak_anon_bytes, anon)
        if not self._live_arrays:
            return
        try:
            total = sum(device_bytes(a) for a in jax.live_arrays())
        except Exception:
            return
        if total > self.peak_bytes:
            self.peak_bytes = total

    def _run(self) -> None:
        while not self._stop.is_set():
            self._sample()
            self._stop.wait(self.interval_s)

    def __enter__(self) -> "LiveArrayPeakSampler":
        import threading

        # Live arrays are the peak source only where the allocator reports
        # no stats (the CPU backend); on a TPU the thread samples host RSS
        # alone and never walks jax.live_arrays().
        self._live_arrays = not device_memory_stats()

        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
        self._sample()

    @property
    def peak_gb(self) -> float:
        return self.peak_bytes / 1e9


def compiled_memory_analysis(jitted, *args, **kwargs) -> dict[str, float]:
    """XLA's own memory accounting for one jitted function at given shapes:
    argument/output/temp/generated-code bytes. The temp figure is the scratch
    a ``LiveArrayPeakSampler`` cannot see; argument+temp+output bounds the
    executable's true HBM footprint."""
    lowered = jitted.lower(*args, **kwargs)
    mem = lowered.compile().memory_analysis()
    if mem is None:
        return {}
    out = {}
    for key in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        val = getattr(mem, key, None)
        if val is not None:
            out[key] = float(val)
    return out


@dataclass
class Recorder:
    """Append-only structured event log for one run.

    Events are (name, seconds, extra) tuples; ``summary()`` aggregates by
    name. ``emit()`` writes one JSON line per event to stderr when verbose.
    """

    verbose: bool = False
    events: list[tuple[str, float, dict]] = field(default_factory=list)

    def record(self, name: str, seconds: float, **extra) -> None:
        self.events.append((name, seconds, extra))
        if self.verbose:
            print(
                json.dumps({"event": name, "seconds": round(seconds, 4), **extra}),
                file=sys.stderr,
                flush=True,
            )

    @contextlib.contextmanager
    def timed(self, name: str, **extra):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record(name, time.perf_counter() - t0, **extra)

    def total(self, name: str) -> float:
        return sum(s for n, s, _ in self.events if n == name)

    def summary(self) -> dict[str, dict[str, float]]:
        agg: dict[str, dict[str, float]] = {}
        for name, s, _ in self.events:
            d = agg.setdefault(name, {"count": 0.0, "seconds": 0.0})
            d["count"] += 1
            d["seconds"] += s
        return agg


def _latency_summary(samples: list[float]) -> dict[str, float]:
    """{count, mean, p50, p95, p99, max} in seconds for a latency sample
    list (the quantile set the Prometheus exposition and the trace
    analyzer share)."""
    if not samples:
        return {"count": 0}
    import numpy as np

    arr = np.asarray(samples, np.float64)
    return {
        "count": int(arr.size),
        "mean": round(float(arr.mean()), 4),
        "p50": round(float(np.percentile(arr, 50)), 4),
        "p95": round(float(np.percentile(arr, 95)), 4),
        "p99": round(float(np.percentile(arr, 99)), 4),
        "max": round(float(arr.max()), 4),
    }


class RetryRecorder:
    """Thread-safe transient-I/O retry accounting (fed by faults/retry.py's
    ``retry_call``). Keyed by call-site label (``shard_read``,
    ``device_put``, ...); per label: ``retries`` (backoff sleeps taken),
    ``recovered`` (calls that succeeded after >= 1 retry), ``exhausted``
    (calls that gave up — the typed ShardLoadError path), ``backoff_s``
    (total sleep). One recorder per executor/engine, so runs don't bleed
    into each other's counts."""

    def __init__(self) -> None:
        import threading

        self._lock = threading.Lock()
        self._by_label: dict[str, dict[str, float]] = {}

    def record(
        self,
        label: str,
        *,
        retries: int = 0,
        recovered: int = 0,
        exhausted: int = 0,
        backoff_s: float = 0.0,
    ) -> None:
        with self._lock:
            d = self._by_label.setdefault(
                label or "call",
                {"retries": 0, "recovered": 0, "exhausted": 0, "backoff_s": 0.0},
            )
            d["retries"] += retries
            d["recovered"] += recovered
            d["exhausted"] += exhausted
            d["backoff_s"] += backoff_s

    def total(self, key: str = "retries") -> float:
        with self._lock:
            return sum(d[key] for d in self._by_label.values())

    def snapshot(self) -> dict[str, dict[str, float]]:
        with self._lock:
            return {
                k: {
                    kk: round(vv, 4) if kk == "backoff_s" else int(vv)
                    for kk, vv in d.items()
                }
                for k, d in sorted(self._by_label.items())
            }


class IntegrityRecorder:
    """Thread-safe corruption-accounting counters (fed by the integrity
    layer: ``_HostShardLoader``, ``ActivationStore``, the executor's
    recompute path). Keys: ``integrity_failures`` (checksum mismatches /
    unreadable spills DETECTED), ``reread_heals`` (loads that came back
    clean on a re-read — page-cache/NFS corruption healed in place),
    ``recomputes`` (blocks re-derived from the last good shard boundary
    after a persistent spill mismatch), ``quarantined_shards`` (weight
    files whose corruption survived every re-read). Surfaced in executor
    stats and the serve stats line when nonzero."""

    KEYS = (
        "integrity_failures",
        "reread_heals",
        "recomputes",
        "quarantined_shards",
    )

    def __init__(self) -> None:
        import threading

        self._lock = threading.Lock()
        self._counts: dict[str, int] = {k: 0 for k in self.KEYS}

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def total(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self._counts)


# SLO class names for the per-class latency breakdown — mirrored from
# serve/sched/classes.py (importing it here would cycle: engine ->
# metrics -> serve). tests/test_sched.py pins the two tuples in sync.
# Pre-seeded so the fls_serve_ttft_by_class_* / latency_by_class_*
# families are always scrapeable ("no samples yet" vs "not exported").
SLO_CLASS_NAMES = ("interactive", "standard", "best_effort")


# The stats-line / exposition merge policy for the serve registry's
# WELL-KNOWN source names: these get the layout operators and CI greps
# already depend on (nested-when-nonzero, top-level convenience keys);
# any OTHER registered source appears as its own nested dict when it
# carries a nonzero value. This is the ONE assembly path — the engine's
# stats() and ServingMetrics.snapshot() both go through it, so the line
# can never fork again.
_SERVE_CORE_SOURCES = (
    "serve", "io_retries", "integrity", "host_cache", "residency",
)


def assemble_serve_stats(collected: dict) -> dict:
    """One serve stats line from a registry collection (see
    ``ServingMetrics.snapshot``)."""
    out: dict = {"event": "serve_stats"}
    out.update(collected.get("serve", {}))
    retries = collected.get("io_retries")
    if retries:
        out["io_retries"] = retries
    integrity = collected.get("integrity")
    if integrity and any(integrity.values()):
        out["integrity"] = integrity
    # .get(), never []: a failing source degrades to {"collect_error": 1}
    # in the collection (obs/registry.py) — the stats line must render
    # around it, not turn the tolerated failure into a KeyError that the
    # serve loop's fatal path would promote to killing the engine.
    cache = collected.get("host_cache")
    if cache is not None:
        if "hit_rate" in cache:
            out["host_cache_hit_rate"] = cache["hit_rate"]
        out["host_cache"] = cache
    res = collected.get("residency")
    if res is not None:
        if "pinned_bytes" in res:
            out["pinned_bytes"] = res["pinned_bytes"]
        if "stream_bytes_saved" in res:
            out["stream_bytes_saved"] = res["stream_bytes_saved"]
        out["residency"] = res
    for name in sorted(collected):
        if name in _SERVE_CORE_SOURCES:
            continue
        snap = collected[name]
        if any(isinstance(v, (int, float)) and v for v in snap.values()):
            out[name] = snap
    return out


class ServingMetrics:
    """Counters/gauges/latency samples for the online serving subsystem.

    Thread-safe (submitters, the serving loop, and callbacks all touch it).
    Counters: admitted / rejected / expired / cancelled / completed /
    failed / prefills / sweeps / tokens_emitted (pre-seeded to 0 so the
    Prometheus exposition always carries the full family — a scrape can
    tell "zero recoveries" from "recoveries not exported"). Gauges:
    queue_depth / active_requests / active_waves. Latency samples: ttft_s
    (submit -> first token) and token_s (per-token decode latency) — kept
    in a BOUNDED window (``sample_window`` newest samples) so a
    long-running server neither grows memory with uptime nor recomputes
    percentiles over its whole history inside the lock; the summaries are
    therefore recent-window statistics, while the counters remain
    all-time totals.

    Every part registers into ``self.registry`` (an
    ``obs.registry.MetricsRegistry``): its own counters/gauges/latency
    under ``serve``, the retry and integrity recorders, and whatever the
    engine attaches (host cache, residency tier, watchdog, tracer, the
    process stream counters). ``snapshot()`` — the periodic structured
    stats line — and the engine's Prometheus endpoint both render from
    that one registry, so the two can never drift. The same sources are
    mirrored into the process-wide registry (last engine wins, the
    process cache/tier precedent) for the batch-style one-shot dump.
    ``maybe_emit(interval)`` prints the line to stderr at most once per
    interval (0 disables)."""

    KNOWN_COUNTERS = (
        "admitted",
        "rejected",
        "expired",
        "cancelled",
        "completed",
        "failed",
        "prefills",
        # Prefix-prefill token accounting (runtime/kvpool.py reuse):
        # prefix_prefill_tokens = prefix tokens actually prefilled;
        # prefix_reuse_tokens = prefix tokens served from pooled pages
        # with ZERO prefill recompute (the share of prefix work the
        # pool saved is reuse / (reuse + prefill)).
        "prefix_prefill_tokens",
        "prefix_reuse_tokens",
        "sweeps",
        "tokens_emitted",
        "engine_recoveries",
        "waves_aborted",
        "source_restarts",
        "watchdog_stalls",
    )

    def __init__(
        self, sample_window: int = 4096, process_mirror: bool = True
    ) -> None:
        import threading
        from collections import deque

        from flexible_llm_sharding_tpu.obs.registry import MetricsRegistry

        # process_mirror=False (fleet-owned engines): keep every source in
        # this engine's OWN registry but never mirror it process-wide —
        # with N replicas the last-wins 'serve'/'io_retries'/... names
        # would otherwise expose ONE arbitrary replica's counters as the
        # process family (and drop the family entirely whenever that
        # replica is recycled). The fleet exports per-replica mirrors
        # under replica<idx> instead.
        self.process_mirror = process_mirror
        self._lock = threading.Lock()
        self._counters: dict[str, int] = {k: 0 for k in self.KNOWN_COUNTERS}
        self._gauges: dict[str, float] = {}
        self._ttft: deque[float] = deque(maxlen=sample_window)
        self._token_lat: deque[float] = deque(maxlen=sample_window)
        # Per-SLO-class breakdowns (serve/sched): TTFT and full request
        # latency, same bounded-window semantics as the aggregate above.
        self._ttft_class: dict[str, deque] = {
            c: deque(maxlen=sample_window) for c in SLO_CLASS_NAMES
        }
        self._latency_class: dict[str, deque] = {
            c: deque(maxlen=sample_window) for c in SLO_CLASS_NAMES
        }
        self._last_emit = 0.0
        # Transient-I/O retry accounting for this engine's weight stream
        # (the engine threads it into its sources' loaders).
        self.retries = RetryRecorder()
        # Corruption accounting (checksum failures / re-read heals /
        # quarantines) for the same stream — nonzero counters appear in
        # the stats line under "integrity".
        self.integrity = IntegrityRecorder()
        # Speculative-serving draft economy (serve/engine.py spec path):
        # pre-seeded so the fls_spec_* family is always scrapeable —
        # "zero drafts" vs "spec not exported" — and registered as its
        # OWN source so the exposition names are fls_spec_drafted_tokens
        # / fls_spec_accepted_tokens / fls_spec_rejected_tokens plus the
        # derived acceptance_rate and extra_tokens_per_sweep.
        self._spec: dict[str, int] = {
            "drafted_tokens": 0,
            "accepted_tokens": 0,
            "rejected_tokens": 0,
        }
        # Per-SLO-class split of the same family (pre-seeded zeros for
        # every class so fls_spec_by_class_<class>_<counter> is always
        # scrapeable) — the adaptive controller's input signal
        # (serve/spec.py) must be observable from the outside too.
        self._spec_class: dict[str, dict[str, int]] = {
            c: {
                "drafted_tokens": 0,
                "accepted_tokens": 0,
                "rejected_tokens": 0,
            }
            for c in SLO_CLASS_NAMES
        }
        self.registry = MetricsRegistry()
        self._host_cache = None
        self._residency = None
        # Mirrored names -> the exact source object registered process-
        # wide, so close() can retract THIS engine's mirrors without
        # yanking a newer engine's (unregister_if identity check).
        self._mirrored: dict[str, object] = {}
        self.register("serve", self._core_snapshot)
        self.register("io_retries", self.retries.snapshot)
        self.register("integrity", self.integrity.snapshot)
        self.register("spec", self.spec_snapshot)

    def register(self, name: str, source, mirror: bool = True) -> None:
        """Register a source into this engine's registry and (for
        engine-scoped sources) mirror it into the process-wide one — last
        engine wins there, and ``close()`` retracts the mirrors so a dead
        engine neither serves stale counters nor pins its object graph.
        Pass ``mirror=False`` for PROCESS-level sources (the stream
        counters, the tracer, the host cache, the residency tier): their
        owners register them process-wide themselves, and an engine
        mirror would tear them down with the engine."""
        from flexible_llm_sharding_tpu.obs.registry import REGISTRY

        self.registry.register(name, source)
        if mirror and self.process_mirror:
            self._mirrored[name] = source
            REGISTRY.register(name, source)

    def close(self) -> None:
        """Retract this engine's process-wide mirrors (engine shutdown).
        Idempotent; a newer engine's same-name registrations survive."""
        from flexible_llm_sharding_tpu.obs.registry import REGISTRY

        for name, source in self._mirrored.items():
            REGISTRY.unregister_if(name, source)
        self._mirrored = {}

    # Host shard cache / residency tier attached by the serving engine —
    # kept as attribute-style setters for the existing call sites, but the
    # attach IS a registry registration: the stats line and the endpoint
    # read the same source. No process-wide mirror: both objects are
    # process-level and register themselves there (cache_for / tier_for),
    # so an engine detach must not disturb the live process source.
    @property
    def host_cache(self):
        return self._host_cache

    @host_cache.setter
    def host_cache(self, cache) -> None:
        self._host_cache = cache
        if cache is not None:
            self.register("host_cache", cache.stats, mirror=False)
        else:
            self.registry.unregister("host_cache")

    @property
    def residency(self):
        return self._residency

    @residency.setter
    def residency(self, tier) -> None:
        self._residency = tier
        if tier is not None:
            self.register("residency", tier.stats, mirror=False)
        else:
            self.registry.unregister("residency")

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def observe_ttft(self, seconds: float, slo_class: str | None = None) -> None:
        from collections import deque

        with self._lock:
            self._ttft.append(seconds)
            if slo_class is not None:
                self._ttft_class.setdefault(
                    slo_class, deque(maxlen=self._ttft.maxlen)
                ).append(seconds)

    def observe_request_latency(
        self, seconds: float, slo_class: str | None = None
    ) -> None:
        """Full submit->completion latency, bucketed per SLO class — the
        per-class half of the latency story (TTFT above is the other)."""
        if slo_class is None:
            return
        from collections import deque

        with self._lock:
            self._latency_class.setdefault(
                slo_class, deque(maxlen=self._ttft.maxlen)
            ).append(seconds)

    def observe_token_latency(self, seconds: float) -> None:
        with self._lock:
            self._token_lat.append(seconds)

    def ttft_class_samples(self, slo_class: str) -> list[float]:
        """Copy of one class's bounded TTFT window (obs/slo.py reads it
        at scrape time — pull-based, nothing on the serving hot path)."""
        with self._lock:
            d = self._ttft_class.get(slo_class)
            return list(d) if d is not None else []

    def token_latency_samples(self) -> list[float]:
        """Copy of the bounded per-token latency window (obs/slo.py)."""
        with self._lock:
            return list(self._token_lat)

    def spec_count(
        self, drafted: int = 0, accepted: int = 0, rejected: int = 0,
        slo_class: str | None = None,
    ) -> None:
        """One verify pass's draft economy (serve/engine.py spec path):
        USEFUL drafted slots, accepted, rejected — drafted == accepted +
        rejected by construction (SpecVerifier.finish_pass). With
        ``slo_class`` the same delta also lands in that class's split
        (the aggregate family stays the cross-class total either way)."""
        with self._lock:
            self._spec["drafted_tokens"] += drafted
            self._spec["accepted_tokens"] += accepted
            self._spec["rejected_tokens"] += rejected
            if slo_class is not None:
                cls = self._spec_class.setdefault(
                    slo_class,
                    {
                        "drafted_tokens": 0,
                        "accepted_tokens": 0,
                        "rejected_tokens": 0,
                    },
                )
                cls["drafted_tokens"] += drafted
                cls["accepted_tokens"] += accepted
                cls["rejected_tokens"] += rejected

    def spec_snapshot(self) -> dict:
        """The ``spec`` registry source: raw counters + the two derived
        headline figures — acceptance rate (accepted / drafted) and extra
        tokens per sweep (accepted / sweeps: how many tokens beyond the
        baseline one-per-sweep each weight sweep bought)."""
        with self._lock:
            drafted = self._spec["drafted_tokens"]
            accepted = self._spec["accepted_tokens"]
            sweeps = self._counters.get("sweeps", 0)
            return {
                **self._spec,
                "acceptance_rate": round(accepted / drafted, 4)
                if drafted
                else 0.0,
                "extra_tokens_per_sweep": round(accepted / sweeps, 4)
                if sweeps
                else 0.0,
                "by_class": {
                    c: dict(v) for c, v in sorted(self._spec_class.items())
                },
            }

    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def _core_snapshot(self) -> dict:
        """The engine's own counters/gauges/latency summaries — the
        ``serve`` registry source."""
        with self._lock:
            return {
                **{k: v for k, v in sorted(self._counters.items())},
                **{k: v for k, v in sorted(self._gauges.items())},
                "ttft_s": _latency_summary(list(self._ttft)),
                "token_latency_s": _latency_summary(list(self._token_lat)),
                # Per-SLO-class breakdowns (serve/sched): always present
                # (classes pre-seeded) so the fls_serve_*_by_class_*
                # families are scrapeable even before the first sample.
                "ttft_by_class": {
                    c: _latency_summary(list(d))
                    for c, d in sorted(self._ttft_class.items())
                },
                "latency_by_class": {
                    c: _latency_summary(list(d))
                    for c, d in sorted(self._latency_class.items())
                },
            }

    def snapshot(self) -> dict:
        return assemble_serve_stats(self.registry.collect())

    def emit(self) -> None:
        print(json.dumps(self.snapshot()), file=sys.stderr, flush=True)

    def maybe_emit(self, interval_s: float) -> bool:
        """Emit the stats line if ``interval_s`` has passed since the last
        emission (0 = off). Returns whether a line was printed."""
        if not interval_s:
            return False
        now = time.monotonic()
        with self._lock:
            if now - self._last_emit < interval_s:
                return False
            self._last_emit = now
        self.emit()
        return True


class RouterMetrics:
    """Counters/gauges for the replica fleet's router (``serve/fleet.py``).

    Thread-safe (submitter threads dispatch, engine threads report
    terminal outcomes, the health monitor drains/recycles). Counters are
    PRE-SEEDED to 0 (``KNOWN_COUNTERS``) so the Prometheus exposition
    always carries the full ``fls_router_*`` family — a scrape can tell
    "zero re-dispatches happened" from "re-dispatches not exported", the
    same zero-vs-unexported contract ``ServingMetrics.KNOWN_COUNTERS``
    established. The fleet registers ``snapshot`` into the process-wide
    metrics registry under the ``router`` source name."""

    KNOWN_COUNTERS = (
        "dispatches",          # requests handed to a replica (first attempt)
        "redispatches",        # orphans re-dispatched to a surviving replica
        "expired_orphans",     # orphans whose deadline lapsed -> EXPIRED
        "stale_results",       # outcomes from attempts the fleet abandoned
        "replicas_dead",       # hard-fails (engine-fatal / stalled watermark)
        "replicas_drained",    # graceful drains completed
        "replicas_recycled",   # fresh engines brought up in a dead/drained slot
        "replicas_added",      # elastic joins
        "replicas_removed",    # elastic leaves
    )

    def __init__(self) -> None:
        import threading

        self._lock = threading.Lock()
        self._counters: dict[str, int] = {k: 0 for k in self.KNOWN_COUNTERS}
        self._gauges: dict[str, float] = {}

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                **{k: v for k, v in sorted(self._counters.items())},
                **{k: v for k, v in sorted(self._gauges.items())},
            }


@contextlib.contextmanager
def profiler_trace(log_dir: str | None):
    """``jax.profiler`` trace scope (Perfetto/XProf) when a directory is
    given; no-op otherwise. View with ``xprof`` or perfetto.dev."""
    if not log_dir:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield


class _NullBar:
    def update(self, n: int = 1) -> None:
        pass

    def set_postfix_str(self, s: str) -> None:
        pass

    def close(self) -> None:
        pass


class _WatchdogBar:
    """Wraps a progress bar with a stall watchdog: if no update lands for
    ``stall_warn_s`` a warning goes to stderr (repeated each further
    interval). A stuck transfer or compute otherwise means tens of minutes
    of silence in headless runs — the warning names the stalled loop and
    how long it has been stuck, which is the whole diagnosis."""

    def __init__(self, bar, desc: str, stall_warn_s: float):
        import threading

        self._bar = bar
        self._desc = desc
        self._interval = stall_warn_s
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def _watch(self) -> None:
        warned = 0
        while not self._stop.wait(min(self._interval / 4, 30.0)):
            idle = time.monotonic() - self._last
            if idle >= self._interval * (warned + 1):
                warned += 1
                msg = (
                    f"[stall] '{self._desc}' has made no progress for "
                    f"{idle / 60:.1f} min — accelerator transfer/compute "
                    "may be stuck; the run will continue "
                    "if it recovers, or can be killed and resumed "
                    "(--resume, disk mode)"
                )
                # tqdm.write, not print: a raw print from this thread would
                # splice into the bar's in-place-refreshed TTY line.
                writer = getattr(type(self._bar), "write", None)
                if callable(writer):
                    type(self._bar).write(msg, file=sys.stderr)
                else:
                    print(msg, file=sys.stderr, flush=True)
            elif idle < self._interval:
                warned = 0

    def update(self, n: int = 1) -> None:
        self._last = time.monotonic()
        self._bar.update(n)

    def set_postfix_str(self, s: str) -> None:
        self._bar.set_postfix_str(s)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
        self._bar.close()


class StepWatchdog:
    """Step-progress watchdog with an ABORT action — ``_WatchdogBar``'s
    stall detection generalized from warn-only to recovery.

    ``arm(token)`` before a monitored phase, ``tick()`` on every unit of
    progress, ``disarm()`` when the phase completes. If an armed phase
    goes ``abort_s`` with no tick, ``on_stall(idle_s, token)`` fires ONCE
    from the watchdog thread and the phase self-disarms (the owner re-arms
    on its next phase). ``token`` identifies WHAT the armed period guards
    (the serving engine passes its current weight source): the callback is
    handed the token its own armed period captured, so a callback delayed
    across a recovery cannot be tricked into aborting the healthy
    replacement by re-reading mutable owner state at fire time.
    ``on_stall`` runs on the watchdog thread: it must be non-blocking
    (set a flag, close a queue), never join the stalled work itself."""

    def __init__(self, desc: str, abort_s: float, on_stall, poll_s=None):
        import threading

        if abort_s <= 0:
            raise ValueError("abort_s must be > 0")
        self._desc = desc
        self._abort_s = abort_s
        self._on_stall = on_stall
        self._poll_s = poll_s if poll_s is not None else max(abort_s / 4, 0.01)
        self._armed = False
        self._token = None
        self._last = time.monotonic()
        self.stalls = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def _watch(self) -> None:
        while not self._stop.wait(self._poll_s):
            if not self._armed:
                continue
            idle = time.monotonic() - self._last
            if idle < self._abort_s:
                continue
            # Capture the armed period's token BEFORE anything that can
            # block (the print below can): the callback must act on what
            # stalled, not on whatever the owner armed next.
            token = self._token
            self._armed = False
            self.stalls += 1
            # Structured span event FIRST (non-blocking ring append): the
            # stall must be visible in the trace timeline — correlated
            # with the sweep it killed — not only as an exception text.
            obs_trace.instant(
                "watchdog_stall",
                cat="serve",
                desc=self._desc,
                idle_s=round(idle, 3),
                stalls=self.stalls,
            )
            # Durable twin of the trace instant: the stall that killed a
            # sweep must survive the recovery (or the process) it causes.
            obs_events.emit(
                "watchdog_stall",
                desc=self._desc,
                idle_s=round(idle, 3),
                stalls=self.stalls,
            )
            print(
                f"[stall] '{self._desc}' made no progress for {idle:.1f}s "
                "— aborting for recovery",
                file=sys.stderr,
                flush=True,
            )
            try:
                self._on_stall(idle, token)
            except Exception:
                pass  # recovery is best-effort; the watchdog must survive

    def stats(self) -> dict[str, int]:
        """Registry source: stall-abort count for the metrics endpoint."""
        return {"stalls": self.stalls}

    def arm(self, token=None) -> None:
        self._token = token
        self._last = time.monotonic()
        self._armed = True

    def tick(self) -> None:
        self._last = time.monotonic()

    def disarm(self) -> None:
        self._armed = False

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)


def progress_bar(total: int, desc: str, unit: str = "it", disable=None,
                 stall_warn_s: float = 600.0):
    """A tqdm bar over the streaming loops (the reference shows tqdm over the
    longer of its shard/prompt loops, ``/root/reference/utils.py:226-227,
    236-238``). ``disable=None`` = tqdm's auto mode: visible on a TTY, silent
    in CI/pipes. Falls back to a no-op if tqdm is missing. A stall watchdog
    warns on stderr when no update lands for ``stall_warn_s`` (0 disables)."""
    try:
        from tqdm import tqdm
    except ImportError:
        bar = _NullBar()
    else:
        bar = tqdm(total=total, desc=desc, unit=unit, disable=disable,
                   file=sys.stderr)
    if stall_warn_s and total > 0:
        return _WatchdogBar(bar, desc, stall_warn_s)
    return bar


def _arch_walk(cfg):
    """Shared per-layer structure walk for the analytic model-size helpers:
    (attn projection params, per-layer moe flags, dense MLP intermediate).
    ``model_flops_per_token`` and ``param_count`` both consume this so a new
    model-family field (moe pattern, shared expert, …) is resolved in ONE
    place — they differ only in counting ACTIVE vs ALL experts."""
    h = cfg.hidden_size
    hd = cfg.head_dim
    q_dim = cfg.num_attention_heads * hd
    kv_dim = cfg.num_key_value_heads * hd
    if cfg.kv_lora_rank:
        # MLA (deepseek): LoRA'd q (or dense wq), compressed kv_a, per-head
        # kv_b decompression, wo over the heads' v_head_dim outputs.
        q_p = (
            h * cfg.q_lora_rank + cfg.q_lora_rank * q_dim
            if cfg.q_lora_rank
            else h * q_dim
        )
        kv_p = h * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) + (
            cfg.kv_lora_rank
            * cfg.num_attention_heads
            * (cfg.qk_nope_head_dim + cfg.v_dim)
        )
        attn_proj = q_p + kv_p + cfg.num_attention_heads * cfg.v_dim * h
    else:
        attn_proj = h * q_dim + 2 * h * kv_dim + q_dim * h
    n = cfg.num_hidden_layers
    moe_pattern = cfg.moe_layer_pattern or (
        ((True,) * n) if cfg.num_local_experts else ((False,) * n)
    )
    dense_inter = (
        cfg.intermediate_size_mlp
        if cfg.intermediate_size_mlp is not None
        else cfg.intermediate_size
    )
    return attn_proj, moe_pattern, dense_inter


def _shared_expert_mult(cfg) -> int:
    """Width of the always-on shared expert in units of the routed expert
    width: 0 (no shared expert), 1 (llama4), or ``cfg.n_shared_experts``
    (deepseek — ONE fused MLP of n_shared x the routed width, V2 uses 2)."""
    if cfg.model_type == "llama4_text":
        return 1
    if cfg.model_type == "deepseek_v3":
        # Parse already normalized (explicit 0 preserved, absent -> 1);
        # getattr only tolerates duck-typed test configs.
        return int(getattr(cfg, "n_shared_experts", 1))
    return 0


def model_flops_per_token(cfg, context_len: int = 0) -> float:
    """Analytic forward FLOPs per processed token for a LlamaConfig.

    2 FLOPs per matmul MAC over every parameter that participates in a
    matmul (projections, MLP, lm_head — embeddings are a gather, not FLOPs),
    plus the attention score/value terms (2*ctx*(qk head_dim + v_dim) per
    query head per token at mean context ``context_len`` — the dims differ
    under MLA, equal everywhere else). MoE layers count only the
    ACTIVE experts per token (top-k, + llama4's shared expert) plus the
    router. This is the numerator of MFU — the standard "model FLOPs"
    convention (no recompute, no masking discounts).
    """
    h = cfg.hidden_size
    attn_proj, moe_pattern, dense_inter = _arch_walk(cfg)
    # QK uses the (qk) head_dim, PV uses V's own dim (MLA: 192 vs 128).
    attn_scores = (
        context_len * (cfg.head_dim + cfg.v_dim) * cfg.num_attention_heads
    )

    total = 0.0
    for is_moe in moe_pattern:
        if is_moe:
            # Always-on shared expert: width 1x for llama4, n_shared_experts x
            # the routed width for deepseek (V2 checkpoints use 2).
            active = cfg.num_experts_per_tok + _shared_expert_mult(cfg)
            mlp = active * 3 * h * cfg.intermediate_size + h * cfg.num_local_experts
        else:
            mlp = 3 * h * dense_inter
        total += 2 * (attn_proj + mlp) + 2 * attn_scores
    total += 2 * h * cfg.vocab_size  # lm_head
    return float(total)


# Peak dense bf16 FLOP/s per chip, by device_kind substring (public TPU
# specs; the MFU denominator).
_PEAK_BF16_FLOPS = (
    ("v6", 918e12),  # Trillium / v6e
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v4", 275e12),
)


def measure_host_to_hbm_gbps(device=None, mb: int = 256) -> float:
    """Effective host->device transfer bandwidth (GB/s): one timed
    ``device_put`` of an ``mb``-MB buffer, after a SAME-SHAPE warm transfer
    so backend init, first-transfer setup, and the readback compile all land
    outside the timed region. Completion is observed with a device_get of a
    scalar sum. The binding constraint of weight streaming — every
    throughput artifact should carry this number for legibility."""
    import time

    import jax

    import numpy as np

    device = device or jax.local_devices()[0]  # addressable on every rank
    buf = np.ones((mb, 1024, 1024 // 4), np.float32)
    a = jax.device_put(buf, device)  # warm: same shape/dtype as the timed put
    jax.device_get(a.sum())  # warm the readback compile too
    t0 = time.perf_counter()
    a = jax.device_put(buf, device)
    jax.device_get(a.sum())
    return buf.nbytes / 1e9 / (time.perf_counter() - t0)


def _kind_lookup(device, table, what: str) -> float | None:
    """Resolve a per-chip spec from a (device_kind substring, value) table.
    ``None`` off-TPU (the CPU has no such spec); a TPU whose kind is not in
    the table is an error, not a default — a ``None`` there would turn every
    utilisation figure and HBM gate quietly off."""
    import jax

    device = device if device is not None else jax.local_devices()[0]
    kind = (getattr(device, "device_kind", "") or "").lower()
    for token, value in table:
        if token in kind:
            return value
    if device.platform == "tpu":
        raise ValueError(
            f"no {what} known for TPU device_kind {device.device_kind!r}; "
            "add it to the table in utils/metrics.py with its source"
        )
    return None


def chip_peak_flops(device=None) -> float | None:
    """Peak bf16 FLOP/s for one chip; None on the CPU; raises on a TPU kind
    the table does not know."""
    return _kind_lookup(device, _PEAK_BF16_FLOPS, "peak bf16 FLOP/s")


# HBM per chip in GB, by device_kind substring (public TPU specs): the HBM
# gates' capacity where the allocator's stats carry no bytes_limit.
_HBM_GB = (
    ("v6e", 32.0),
    ("v6", 32.0),
    ("v5p", 95.0),
    ("v5e", 16.0),
    ("v5 lite", 16.0),
    ("v5litepod", 16.0),
    ("v4", 32.0),
    ("v3", 16.0),
    ("v2", 8.0),
)


def chip_hbm_gb(device=None) -> float | None:
    """HBM capacity of one chip in GB: the allocator's ``bytes_limit`` when
    it reports one, else the device-kind table; None on the CPU backend
    (where "device memory" is host RAM); raises on an unknown TPU kind."""
    import jax

    device = device if device is not None else jax.local_devices()[0]
    limit = device_memory_stats(device).get("bytes_limit")
    if limit:
        return limit / 1e9
    return _kind_lookup(device, _HBM_GB, "HBM capacity")


# Bytes per element at each supported compute dtype — the shared factor of
# every HBM-budget gate (resident weights, kv-on-device, fused decode).
_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def weight_bytes_per_chip(cfg, dtype: str, n_chips: int = 1) -> float:
    """Materialised parameter bytes per chip at compute dtype — the shared
    numerator of the resident-decode (config.decode_resident_enabled) and
    kv-on-device / fused-decode (runtime.decode) HBM gates."""
    return param_count(cfg) * _DTYPE_BYTES[dtype] / max(n_chips, 1)


def param_count(cfg) -> int:
    """Total parameter count for a LlamaConfig — ALL weights as materialised
    on device at compute dtype (every expert, embeddings, untied head; int8
    checkpoints dequantize on placement, executor._place), the
    resident-decode sizing numerator. Shares ``_arch_walk`` with
    ``model_flops_per_token`` but counts storage instead of active
    compute."""
    h = cfg.hidden_size
    attn, moe_pattern, dense_inter = _arch_walk(cfg)
    total = 0
    for is_moe in moe_pattern:
        if is_moe:
            mlp = cfg.num_local_experts * 3 * h * cfg.intermediate_size
            mlp += h * cfg.num_local_experts  # router
            # shared expert (llama4: 1x routed width; deepseek: n_shared x)
            mlp += _shared_expert_mult(cfg) * 3 * h * cfg.intermediate_size
        else:
            mlp = 3 * h * dense_inter
        total += attn + mlp + 2 * h  # + the two norm scale vectors
    total += h * cfg.vocab_size  # embed
    if not cfg.tie_word_embeddings:
        total += h * cfg.vocab_size  # untied lm_head
    total += h  # final norm
    return int(total)


def throughput(tokens: int, seconds: float, chips: int = 1) -> dict[str, float]:
    """tokens/sec and tokens/sec/chip — the BASELINE.md headline metric."""
    tps = tokens / seconds if seconds > 0 else 0.0
    return {
        "tokens_per_sec": round(tps, 3),
        "tokens_per_sec_per_chip": round(tps / max(chips, 1), 3),
    }


__all__ = [
    "IntegrityRecorder",
    "LiveArrayPeakSampler",
    "Recorder",
    "RetryRecorder",
    "RouterMetrics",
    "ServingMetrics",
    "StepWatchdog",
    "assemble_serve_stats",
    "chip_peak_flops",
    "model_flops_per_token",
    "compiled_memory_analysis",
    "device_memory_stats",
    "peak_hbm_gb",
    "profiler_trace",
    "progress_bar",
    "throughput",
]
