"""Sweep-timeline span tracer: a thread-safe bounded ring of timed spans.

The architecture's defining cost is that every decode step streams the
whole model through the chip, so the questions that matter are *timeline*
questions — is compute hidden under the host->HBM stream, where does a
sweep's wall time go, when did a wave join and when did its first token
land. This module records exactly that timeline: the executor's
producer/consumer, the host shard cache, the residency tier, the retry/
heal layer, and the serve wave lifecycle all emit spans here, correlated
by ``sweep_id`` / ``shard_idx`` / ``wave_id`` / ``request_id``.

Design constraints, in order:

1. **Zero-cost when disabled.** Every emit goes through a module-level
   helper that reads one bool and the profiler's flag and returns a
   shared no-op; no allocation, no lock, no timestamp is taken on the
   disabled path. Tracing must be safe to leave compiled into every hot
   loop.
2. **Bounded.** Spans land in a ring of ``capacity`` records; overflow
   drops the OLDEST spans and counts them (``trace_drops`` in
   ``stats()``), so a long-running server keeps the newest window and
   the loss is visible, never silent.
3. **Machine-readable.** ``write()`` exports Chrome trace-event JSON
   (load it at https://ui.perfetto.dev) or JSONL (one span per line, for
   ``cli trace-report`` and ad-hoc jq), chosen by file extension.

4. **On the device's clock when a profiler runs.** A span also enters a
   ``jax.profiler.TraceAnnotation`` named ``fls.<name>`` carrying the
   span's attributes whenever a profiler session is active
   (``--profile_dir``, a benchmark's traced window), whether or not the
   ring is enabled, so the program's phases land in the ``.xplane.pb``
   beside the device's ops. The sweep's span is a
   ``StepTraceAnnotation`` whose ``step_num`` is the ``sweep_id``.

The process-wide singleton is ``TRACER``; the CLIs enable it from
``--trace`` via ``ensure_configured(cfg)`` and export via
``write_configured()``. Library users call ``TRACER.enable()`` directly.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque

from jax.profiler import StepTraceAnnotation, TraceAnnotation

# Prefix of the program's spans in a profiler trace (the benchmark
# harness's own, ``benchmark/run.py``, are ``bench.``): readers select by it.
ANNOTATION_PREFIX = "fls."
# One read of the profiler's flag: true while any session records.
profiler_active = TraceAnnotation.is_enabled

# Correlation-id wells. A sweep id is unique per process (offline: one
# executor call's full pass over the shards; serving: one engine sweep),
# so spans from interleaved subsystems stitch back into one timeline.
_SWEEP_IDS = itertools.count(1)


def new_sweep_id() -> int:
    return next(_SWEEP_IDS)


class _NullSpan:
    """Shared no-op context manager returned by every emit while the ring
    is off and no profiler records — the whole disabled-path cost is one
    bool test and one read of the profiler's flag in ``span()``."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One live timed span: enters the profiler annotation ``fls.<name>``
    while a session records, and on exit records itself into the tracer
    ring when that is enabled. ``t0``/``dur_s`` stay readable after the
    exit, so a caller that keeps an account reads the same clock pair the
    trace got."""

    __slots__ = ("_tracer", "name", "cat", "attrs", "step", "t0", "dur_s",
                 "_ann", "_dropped")

    def __init__(self, tracer: "Tracer", name: str, cat: str, attrs: dict,
                 step: int | None = None):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.step = step
        self.dur_s = 0.0
        self._ann = None
        self._dropped = False

    def drop(self) -> None:
        """Keep this span out of the ring (a wait that turned out to
        belong to a resume-skipped shard); its timing stays readable."""
        self._dropped = True

    def __enter__(self) -> "_Span":
        if profiler_active():
            name = ANNOTATION_PREFIX + self.name
            if self.step is None:
                self._ann = TraceAnnotation(name, **self.attrs)
            else:
                self._ann = StepTraceAnnotation(
                    name, step_num=self.step, **self.attrs
                )
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_s = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if self._tracer.enabled and not self._dropped:
            self._tracer._append(
                (self.name, self.cat, self.t0, self.dur_s,
                 threading.get_ident(), self.attrs)
            )
        return False


class Tracer:
    """Thread-safe bounded-ring span recorder (see module docstring).

    Records are ``(name, cat, t_start_perf, dur_s | None, tid, attrs)``
    tuples; ``dur_s is None`` marks an instant event. Timestamps are
    ``time.perf_counter()`` values; ``epoch_offset`` maps them back to
    wall-clock for the exports.
    """

    DEFAULT_CAPACITY = 200_000

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self.capacity = int(capacity)
        self._ring: deque = deque()  # guarded by: _lock
        self.drops = 0  # oldest spans dropped on ring overflow  # guarded by: _lock
        self.enabled = False
        self.default_out: str = ""
        # perf_counter -> wall-clock epoch mapping, captured once so every
        # exported timestamp shares one base.
        self._perf0 = time.perf_counter()
        self._epoch0 = time.time()

    # -- recording ---------------------------------------------------------

    def _append(self, rec: tuple) -> None:
        with self._lock:
            if len(self._ring) >= self.capacity:
                self._ring.popleft()
                self.drops += 1
            self._ring.append(rec)

    def span(self, name: str, cat: str = "runtime", **attrs):
        """Timed span context manager; no-op (shared object) when the ring
        is off and no profiler session records."""
        if not self.enabled and not profiler_active():
            return _NULL_SPAN
        return _Span(self, name, cat, attrs)

    def instant(self, name: str, cat: str = "runtime", **attrs) -> None:
        """Zero-duration structured event (heals, stalls, wave admits)."""
        if not self.enabled:
            return
        self._append(
            (name, cat, time.perf_counter(), None, threading.get_ident(),
             attrs)
        )

    # -- lifecycle ---------------------------------------------------------

    def enable(self, capacity: int | None = None) -> "Tracer":
        with self._lock:
            if capacity is not None:
                self.capacity = int(capacity)
            self.enabled = True
        # The tracer's own counters are registry citizens like every other
        # subsystem's (lazy import: registry must stay importable first).
        from flexible_llm_sharding_tpu.obs.registry import REGISTRY

        REGISTRY.register("trace", self.stats)
        return self

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.drops = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    # -- observability -----------------------------------------------------

    def stats(self) -> dict[str, float]:
        with self._lock:
            out = {
                "trace_enabled": int(self.enabled),
                "trace_spans": len(self._ring),
                "trace_drops": self.drops,
            }
            if self.enabled:
                # Capacity only while recording: an all-zero snapshot keeps
                # the serve stats line free of a dead "trace" block.
                out["trace_capacity"] = self.capacity
            return out

    def snapshot(self) -> list[dict]:
        """The ring as a list of span dicts (oldest first), timestamps in
        epoch seconds. ``dur_s`` absent marks an instant event."""
        with self._lock:
            ring = list(self._ring)
            epoch0, perf0 = self._epoch0, self._perf0
        out = []
        for name, cat, t0, dur, tid, attrs in ring:
            d = {
                "name": name,
                "cat": cat,
                "ts_s": round(epoch0 + (t0 - perf0), 6),
                "tid": tid,
            }
            if dur is not None:
                d["dur_s"] = round(dur, 6)
            if attrs:
                d.update(attrs)
            out.append(d)
        return out

    # -- exports -----------------------------------------------------------

    def chrome_events(self) -> list[dict]:
        """Chrome trace-event list (Perfetto-loadable): complete ("X")
        events for spans, instant ("i") events for point events, plus one
        metadata record carrying the drop count."""
        with self._lock:
            ring = list(self._ring)
            perf0 = self._perf0
            drops = self.drops
        pid = os.getpid()
        events: list[dict] = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": "flexible-llm-sharding-tpu"},
            },
            {
                "name": "trace_meta",
                "ph": "i",
                "s": "g",
                "ts": 0,
                "pid": pid,
                "tid": 0,
                "args": {"trace_drops": drops},
            },
        ]
        for name, cat, t0, dur, tid, attrs in ring:
            ev = {
                "name": name,
                "cat": cat,
                "ts": round((t0 - perf0) * 1e6, 1),  # microseconds
                "pid": pid,
                "tid": tid,
                "args": attrs or {},
            }
            if dur is None:
                ev["ph"] = "i"
                ev["s"] = "t"
            else:
                ev["ph"] = "X"
                ev["dur"] = round(dur * 1e6, 1)
            events.append(ev)
        return events

    def write(self, path: str) -> str:
        """Export the ring: ``*.jsonl`` -> one span dict per line plus a
        trailing ``trace_meta`` record carrying the ring drop count (the
        Chrome export embeds the same record), so an overflowed —
        truncated — timeline is detectable in either format; anything
        else -> Chrome trace-event JSON."""
        if path.endswith(".jsonl"):
            spans = self.snapshot()
            with self._lock:
                drops = self.drops
            meta = {
                "name": "trace_meta",
                "cat": "meta",
                "ts_s": spans[0]["ts_s"] if spans else round(self._epoch0, 6),
                "trace_drops": drops,
            }
            with open(path, "w") as f:
                for s in spans:
                    f.write(json.dumps(s) + "\n")
                f.write(json.dumps(meta) + "\n")
        else:
            payload = {
                "traceEvents": self.chrome_events(),
                "displayTimeUnit": "ms",
            }
            with open(path, "w") as f:
                json.dump(payload, f)
        return path


TRACER = Tracer()


def span(name: str, cat: str = "runtime", **attrs):
    """Module-level emit against the process tracer (the hot-path form)."""
    if not TRACER.enabled and not profiler_active():
        return _NULL_SPAN
    return _Span(TRACER, name, cat, attrs)


def timed(name: str, cat: str = "runtime", **attrs) -> _Span:
    """A span that is always timed, for the sites whose durations feed an
    always-on account (the executor's per-sweep record): the caller reads
    ``t0``/``dur_s`` after the exit, and ring and profiler get the same
    pair."""
    return _Span(TRACER, name, cat, attrs)


def sweep_span(sweep_id: int, cat: str = "sweep", **attrs) -> _Span:
    """The span of one full pass over the shards, always timed: in a
    profiler trace a ``StepTraceAnnotation`` with ``step_num`` =
    ``sweep_id``, so the profiler's per-step views group by sweep."""
    attrs["sweep_id"] = sweep_id
    return _Span(TRACER, "sweep", cat, attrs, step=sweep_id)


def instant(name: str, cat: str = "runtime", **attrs) -> None:
    if TRACER.enabled:
        TRACER.instant(name, cat, **attrs)


def enabled() -> bool:
    return TRACER.enabled


def ensure_configured(cfg) -> None:
    """Enable the process tracer when the config asks for it
    (``cfg.trace``); never disables — tracing is process-scoped and a
    second executor with trace off must not cut a live recording short.
    Remembers ``cfg.trace_out`` as the default export path."""
    if getattr(cfg, "trace", False):
        out = getattr(cfg, "trace_out", "") or ""
        if out:
            TRACER.default_out = out
        if not TRACER.enabled:
            TRACER.enable()


def write_configured(default: str = "fls_trace.json") -> str | None:
    """Export the process tracer to its configured path (or ``default``);
    None when tracing never enabled. The CLIs call this at run end."""
    if not TRACER.enabled and not len(TRACER):
        return None
    return TRACER.write(TRACER.default_out or default)


__all__ = [
    "TRACER",
    "Tracer",
    "enabled",
    "ensure_configured",
    "instant",
    "new_sweep_id",
    "profiler_active",
    "span",
    "sweep_span",
    "timed",
    "write_configured",
]
