"""Host-resident shard cache: the steady-state fast path of the weight
stream.

The paper's core loop re-reads the whole model from disk every sweep — the
serving engine's cycling source and multi-sweep offline decode both pay
disk read + safetensors parse + checksum + stack per shard per sweep, even
though the bytes are identical sweep over sweep. This cache pins the
fully-built, upload-ready host trees (the ``build_host_shard`` output:
pre-stacked ``[k, ...]`` segment pytrees) keyed by shard identity, so a
warm sweep goes straight from cache to ``jax.device_put`` with zero host
CPU work per byte (the on-device cast in ``executor._place`` removed the
other per-byte pass).

Safety model — the cache must never serve stale or unverified bytes:

- Entries are inserted only AFTER the loader's integrity verification
  passed (a cached tree is a *verified-clean* tree by construction).
- Every entry records the backing layer files' ``(mtime_ns, size)`` at
  insert time and re-stats them on hit; any drift (a repaired shard, an
  in-place re-prepare, on-disk rot — flipping a byte updates mtime) drops
  the entry and forces a fresh verified read. The PR 4 self-healing
  machinery (re-read heals, quarantine, recompute) therefore operates on
  exactly the loads it did before.
- The cache key folds in the integrity-manifest digest, the compute
  dtype, and the tied/sliding/rope layout flags, so a re-prepared dir or
  a config change can never alias an old entry.
- ``_HostShardLoader`` calls :meth:`invalidate_path` when it quarantines
  a file, purging every entry built from it (and the crc verdict cache,
  integrity/manifest.py, drops its verdicts for the path too).

Where an entry's tree lives: as the loader built it (NumPy leaves, mmap
views of the layer files where the layout allows: pageable memory, which
the runtime stages before the chip can read it) or, for a layer that ONE
chip streams every sweep, as ``jax.Array`` leaves in that chip's
``pinned_host`` memory, which the chip reads directly (PERF.md, PR 30: 14.0
against 8.9 GB/s on a v5e). The loader asks for the second form with
:meth:`HostShardCache.pin`; the copy is made on a thread of the cache's own
(a pinned allocation costs 2-3 s a GB on that machine, once per file
generation, so it must not stand in any sweep's way) and replaces the
entry's tree in place: same key, same guard, same bytes charged (page-
locked RAM where the NumPy tree was page cache: the budget bounds either).
Whatever drops an entry (stat drift, ``invalidate_path``, eviction,
``clear``) drops the pinned tree with it. A key is chip-free: every reader
of a layer shares its one entry, so the pinned form is only for a key that
a single target reads. A reader with another target (a second replica's
chip, a broadcast source) that meets a pinned tree drops it, misses and
rebuilds the NumPy tree, and from then on that key stays NumPy for all of
its readers, as before PR 30: one entry a layer, never one a chip.

Budgeting: a byte-budgeted LRU. ``FrameworkConfig.host_cache_gb`` is the
knob — an explicit number of GB, ``0`` to disable, or ``None`` (auto):
a fraction of the host's currently-available RAM, and **disabled when
fault injection is enabled** (chaos runs exist to exercise the per-load
fault sites every sweep; a cache would silently skip them). Entries whose
leaves are mmap views (the zero-copy path) cost page cache rather than
anon RAM, but are charged against the budget at full size — conservative,
and it keeps the accounting independent of where the kernel holds the
pages.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Sequence

from flexible_llm_sharding_tpu.integrity.manifest import _file_key as _stat_key
from flexible_llm_sharding_tpu.obs import trace as obs_trace
from flexible_llm_sharding_tpu.obs.registry import REGISTRY as _OBS_REGISTRY

# Auto budget: this fraction of MemAvailable at first resolution. Small on
# purpose — the cache is an accelerator, not a requirement, and the host
# also holds prefetch queues, activation spills, and the tokenizer.
AUTO_FRACTION = 0.25

# HostShardCache._readers: a key that readers with different targets share.
_MANY = object()


def available_host_bytes() -> int:
    """MemAvailable from /proc/meminfo (bytes); 0 when unknown (non-Linux)."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return 0


def auto_budget_bytes(fraction: float = AUTO_FRACTION) -> int:
    return int(available_host_bytes() * fraction)


def _tree_nbytes(segments: Sequence[tuple[str, Any]]) -> int:
    import jax

    return sum(
        int(a.nbytes)
        for _, seg in segments
        for a in jax.tree.leaves(seg)
        if hasattr(a, "nbytes")
    )


def stat_guard(paths: Sequence[str]) -> tuple | None:
    """((path, (mtime_ns, size)), ...) for ``paths`` (deduped, order
    kept), or None when any path can't be stat'ed. Callers capture this
    BEFORE reading the files they are about to cache: a concurrent
    atomic replacement then leaves the entry guarded by the OLD
    generation's stat, so the next get() invalidates instead of serving
    bytes the new file never earned."""
    guard = []
    for p in dict.fromkeys(paths):
        st = _stat_key(p)
        if st is None:
            return None
        guard.append((p, st))
    return tuple(guard)


class HostShardCache:
    """Byte-budgeted, thread-safe LRU of upload-ready host shard trees.

    Values are the ``build_host_shard`` segment lists; callers must treat
    them as IMMUTABLE (they are shared across sweeps and across sources —
    ``device_put`` only reads them). ``get`` re-validates the entry's
    backing files by stat and returns None (dropping the entry) on any
    drift, so a hit is always byte-current with the disk state the loader
    would have read.
    """

    def __init__(self, budget_bytes: int):
        if budget_bytes <= 0:
            raise ValueError("budget_bytes must be > 0 (use None cache to disable)")
        self._lock = threading.RLock()
        self.budget_bytes = int(budget_bytes)
        # key -> (segments, nbytes, ((path, (mtime_ns, size)), ...))
        self._entries: "OrderedDict[Any, tuple[Any, int, tuple]]" = OrderedDict()  # guarded by: _lock
        self._by_path: dict[str, set] = {}  # guarded by: _lock
        self.bytes = 0  # guarded by: _lock
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0
        # put() calls that found the cache full of entries they were told
        # to spare (a cyclic scan larger than the budget: see put()).
        self.scan_refusals = 0
        # pinned_host copies (see pin()): requests by key, newest wins, and
        # the one thread that works them off while there are any.
        self._pin_requests: dict = {}  # guarded by: _lock
        self._pin_thread: threading.Thread | None = None  # guarded by: _lock
        self._pinned_keys: set = set()  # guarded by: _lock
        self.pinned_host_bytes = 0  # guarded by: _lock
        self.pin_copies = 0
        self.pin_copy_s = 0.0
        self.pin_error: str | None = None  # the first refusal ends pinning
        # key -> the one target (a pinned_host sharding, or None) all of
        # its readers so far upload to, or _MANY. Outlives the entry: a key
        # two targets have read is never pinned again (else each would drop
        # the other's copy, sweep after sweep). clear() forgets.
        self._readers: dict = {}  # guarded by: _lock

    # -- core API ----------------------------------------------------------

    def get(self, key, target=None) -> tuple[Any, int] | None:
        """(segments, nbytes) for a current entry, else None (counted as a
        miss). ``target`` is the ``pinned_host`` sharding of the one chip
        the reader uploads to, or None (several chips, a placement, no such
        memory): only a reader with an entry's own target is handed its
        pinned tree. The backing files are stat-validated OUTSIDE the lock: a
        wedged filesystem (hard-mounted NFS) blocks os.stat indefinitely,
        and holding the lock through that would stall every weight stream
        in the process — including the serve engine's recovery source,
        the one path that must keep moving when storage misbehaves."""
        with self._lock:
            if self._readers.setdefault(key, target) != target:
                self._readers[key] = _MANY
                self._pin_requests.pop(key, None)
                if key in self._pinned_keys:
                    self._drop(key)  # one chip's copy serves no other target
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
        if entry is None:
            # Emitted OFF the cache lock (like the hit/stale emits below):
            # the tracer's ring lock must never nest inside the cache's
            # critical section.
            obs_trace.instant("hostcache_miss", cat="cache")
            return None
        segments, nbytes, guard = entry
        stale = any(_stat_key(path) != stat for path, stat in guard)
        with self._lock:
            cur = self._entries.get(key)
            if cur is None or cur[2] is not guard:
                # Dropped or replaced while we were statting: our verdict
                # no longer describes what the cache holds — miss. (The
                # pinning thread's swap keeps the entry's guard: the same
                # files' bytes in another memory, which the verdict covers.)
                self.misses += 1
                hit = False
            elif stale:
                # Backing file changed (repair, re-prepare, rot): the
                # entry is stale — drop it and force a verified re-read.
                self._drop(key)
                self.invalidations += 1
                self.misses += 1
                hit = False
            else:
                segments = cur[0]
                self._entries.move_to_end(key)
                self.hits += 1
                hit = True
        if not hit:
            obs_trace.instant("hostcache_miss", cat="cache", stale=stale)
            return None
        obs_trace.instant("hostcache_hit", cat="cache", bytes=nbytes)
        return segments, nbytes

    def put(
        self,
        key,
        segments,
        paths: Sequence[str] = (),
        nbytes: int | None = None,
        guard: tuple | None = None,
        evict: bool = True,
        spare=None,
    ) -> bool:
        """Insert one shard's host tree, guarded by the backing files'
        stats — pass ``guard`` captured via :func:`stat_guard` BEFORE the
        files were read (see there); bare ``paths`` stat at insert time
        and are only race-free when the caller owns the files. Returns
        False (uncached) when any path can't be stat'ed or the entry
        alone exceeds the budget. ``evict=False`` is for a tree that is
        read once (a layer the residency tier keeps from then on): it is
        cached where there is room and pushes nothing out. ``spare`` (a
        predicate over keys): entries this tree must not push out; where
        the least recently used entry is one of them the tree stays
        uncached (``scan_refusals``). A loader spares its own model's
        shards: a sweep reads them in a cycle, and a cycle longer than the
        budget under plain LRU evicts each shard just before its next use
        (every build a miss, sweep after sweep: 13.9 GB of streamed layers
        over a budget of 11 GB read 0 hits, PERF.md PR 37); sparing them
        keeps the first shards of the cycle and reads only the rest again."""
        if guard is None:
            guard = stat_guard(paths)
            if guard is None:
                return False
        if nbytes is None:
            nbytes = _tree_nbytes(segments)
        if nbytes > self.budget_bytes:
            return False
        with self._lock:
            if key in self._entries:
                self._drop(key)
            if not evict and self.bytes + nbytes > self.budget_bytes:
                return False
            while self.bytes + nbytes > self.budget_bytes and self._entries:
                oldest = next(iter(self._entries))
                if spare is not None and spare(oldest):
                    self.scan_refusals += 1
                    return False
                self._drop(oldest)
                self.evictions += 1
            self._entries[key] = (segments, int(nbytes), tuple(guard))
            self.bytes += int(nbytes)
            for p, _ in guard:
                self._by_path.setdefault(p, set()).add(key)
            return True

    def _drop(self, key) -> None:
        # flscheck: holds=_lock: internal helper — every caller already owns the lock
        segments, nbytes, guard = self._entries.pop(key)
        self.bytes -= nbytes
        self._pin_requests.pop(key, None)
        if key in self._pinned_keys:
            self._pinned_keys.discard(key)
            self.pinned_host_bytes -= nbytes
        for p, _ in guard:
            keys = self._by_path.get(p)
            if keys is not None:
                keys.discard(key)
                if not keys:
                    del self._by_path[p]

    # -- pinned_host copies ------------------------------------------------

    def pin(self, key, segments, sharding) -> bool:
        """Ask for ``key``'s tree to be held in ``sharding``'s memory (the
        ``pinned_host`` memory of the one chip that streams it), given the
        NumPy ``segments`` the caller just got for it. Returns at once; the
        copy is made on the cache's own thread and then replaces the
        entry's tree, if the entry still holds ``segments``. False when
        nothing was queued: the entry is gone or already pinned, a reader
        with another target has read the key, or an earlier copy was
        refused (``pin_error``: the host would not pin more, and every
        later request would only fail the same way)."""
        with self._lock:
            entry = self._entries.get(key)
            if (
                self.pin_error is not None
                or self._readers.get(key, _MANY) != sharding
                or entry is None
                or entry[0] is not segments
                or key in self._pinned_keys
            ):
                return False
            self._pin_requests[key] = (segments, sharding)
            if self._pin_thread is None:
                self._pin_thread = threading.Thread(
                    target=self._pin_worker, name="fls-host-pin", daemon=True
                )
                self._pin_thread.start()
            return True

    def _pin_worker(self) -> None:
        import jax

        while True:
            with self._lock:
                if not self._pin_requests or self.pin_error is not None:
                    self._pin_requests.clear()
                    self._pin_thread = None
                    return
                key = next(iter(self._pin_requests))
                segments, sharding = self._pin_requests[key]
            try:
                with obs_trace.timed("host_pin", cat="cache") as sp:
                    trees = jax.block_until_ready(
                        jax.device_put([seg for _, seg in segments], sharding)
                    )
            except Exception as e:  # flscheck: disable=EXC-TAXONOMY: whatever the runtime raises for a pinned allocation it will not make (RESOURCE_EXHAUSTED, an unsupported memory kind) must end pinning, not the thread's owner; the NumPy tree keeps serving
                with self._lock:
                    self.pin_error = repr(e)[:200]
                continue
            pinned = [(kind, t) for (kind, _), t in zip(segments, trees)]
            with self._lock:
                self.pin_copy_s += sp.dur_s
                entry = self._entries.get(key)
                if self._pin_requests.get(key, (None,))[0] is segments:
                    del self._pin_requests[key]
                if (
                    entry is not None
                    and entry[0] is segments
                    and self._readers.get(key, _MANY) == sharding
                ):
                    self._entries[key] = (pinned,) + entry[1:]
                    self._pinned_keys.add(key)
                    self.pinned_host_bytes += entry[1]
                    self.pin_copies += 1

    def pin_wait(self, timeout_s: float = 60.0) -> bool:
        """Block until the queued copies are made (tests, and callers that
        want the steady state before they measure). False on timeout."""
        with self._lock:
            t = self._pin_thread
        if t is not None:
            t.join(timeout=timeout_s)
            return not t.is_alive()
        return True

    # -- invalidation ------------------------------------------------------

    def invalidate_path(self, path: str) -> int:
        """Drop every entry built from ``path`` (the loader's quarantine
        hook). Returns how many entries were dropped."""
        with self._lock:
            keys = list(self._by_path.get(path, ()))
            for k in keys:
                self._drop(k)
            if keys:
                self.invalidations += len(keys)
            return len(keys)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._by_path.clear()
            self._pin_requests.clear()
            self._pinned_keys.clear()
            self._readers.clear()
            self.bytes = self.pinned_host_bytes = 0

    def set_budget(self, budget_bytes: int) -> None:
        """Resize the budget. A SHRINK is safe for live readers: excess
        entries evict LRU-first (counted as evictions, not
        invalidations) while every surviving entry keeps serving hits —
        shrinking changes capacity, never correctness. This is the
        brownout ladder's cache lever (runtime/pressure.py)."""
        with self._lock:
            self.budget_bytes = max(int(budget_bytes), 0)
            while self.bytes > self.budget_bytes and self._entries:
                self._drop(next(iter(self._entries)))
                self.evictions += 1

    # -- observability -----------------------------------------------------

    def stats(self) -> dict[str, float]:
        with self._lock:
            total = self.hits + self.misses
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "scan_refusals": self.scan_refusals,
                "invalidations": self.invalidations,
                "entries": len(self._entries),
                "bytes": self.bytes,
                "budget_bytes": self.budget_bytes,
                "hit_rate": round(self.hits / total, 4) if total else 0.0,
                "pinned_host_bytes": self.pinned_host_bytes,
                "pinned_host_copies": self.pin_copies,
                "pinned_host_copy_s": round(self.pin_copy_s, 4),
            }


# -- process-wide cache ------------------------------------------------------
# One cache per process: the serving engine rebuilds its weight source on
# every recovery, offline decode builds one source per call, and DP ranks
# share a host — all of them must hit the same entries. The budget follows
# the most recent config that resolved it (set_budget re-evicts on shrink).

_PROCESS_CACHE: HostShardCache | None = None
_PROCESS_BUDGET_EXPLICIT = False
# Brownout cap (runtime/pressure.py): while set, NO budget resolution —
# explicit or auto — may exceed it. Without the latch, the very next
# source construction after a pressure shrink would resize the cache
# right back and undo the shed. _PRESSURE_INTENDED tracks the budget
# the process WOULD run at absent the cap (normal precedence applied to
# every resolution that lands mid-brownout), so lifting the cap
# restores exactly that — never blindly the pre-brownout value, which
# would override an explicit pin installed while the cap held.
_PRESSURE_CAP: int | None = None
_PRESSURE_INTENDED: int | None = None
_PROCESS_LOCK = threading.Lock()


def cache_for(cfg) -> HostShardCache | None:
    """The process cache sized per ``cfg.effective_host_cache_bytes()``,
    or None when that resolves to 0 (disabled — explicit 0, chaos mode,
    or unknown free RAM).

    An AUTO budget (host_cache_gb=None) only ever GROWS an AUTO-sized
    cache: auto re-resolves from current MemAvailable on every source
    construction, and the cache's own entries lower MemAvailable — a
    shrink-on-re-resolve would erode the budget run over run and churn
    evictions against the very entries it just built. An explicit budget
    always wins exactly (shrink re-evicts) and PINS the cap: a later
    auto-config component in the same process (a default-config decode
    call next to a capped serve engine) must not silently grow the cache
    past what the operator pinned RAM aside for."""
    budget = cfg.effective_host_cache_bytes()
    if budget <= 0:
        return None
    explicit = cfg.host_cache_gb is not None
    global _PROCESS_CACHE, _PROCESS_BUDGET_EXPLICIT, _PRESSURE_INTENDED
    with _PROCESS_LOCK:
        cap = _PRESSURE_CAP
        # Mid-brownout, precedence is decided against the INTENDED
        # (un-capped) budget, which this resolution may move; the cache
        # itself only ever sees min(intended, cap) — the ladder's cap
        # bounds every resolution, and the 1-byte floor keeps the
        # constructor/budget invariants while rendering the cache
        # effectively empty. Lifting the cap installs the intended
        # value, so an explicit pin that landed mid-brownout survives.
        if _PROCESS_CACHE is None:
            if cap is not None:
                _PRESSURE_INTENDED = budget
                budget = min(budget, max(cap, 1))
            _PROCESS_CACHE = HostShardCache(budget)
            _PROCESS_BUDGET_EXPLICIT = explicit
            # Registry citizen: the metrics endpoint / --metrics_out see
            # the same hit-rate counters the stats lines print.
            _OBS_REGISTRY.register("host_cache", _PROCESS_CACHE.stats)
        elif explicit:
            if cap is not None:
                _PRESSURE_INTENDED = budget
                budget = min(budget, max(cap, 1))
            if _PROCESS_CACHE.budget_bytes != budget:
                _PROCESS_CACHE.set_budget(budget)
            _PROCESS_BUDGET_EXPLICIT = True
        elif not _PROCESS_BUDGET_EXPLICIT:
            base = (
                _PRESSURE_INTENDED
                if cap is not None and _PRESSURE_INTENDED is not None
                else _PROCESS_CACHE.budget_bytes
            )
            if budget > base:
                if cap is not None:
                    _PRESSURE_INTENDED = budget
                    budget = min(budget, max(cap, 1))
                if budget > _PROCESS_CACHE.budget_bytes:
                    _PROCESS_CACHE.set_budget(budget)
        return _PROCESS_CACHE


def process_cache() -> HostShardCache | None:
    """The live process cache, if any (the brownout ladder and the CLI's
    end-of-run stats read it without resolving a budget)."""
    with _PROCESS_LOCK:
        return _PROCESS_CACHE


def apply_pressure_cap(shrink_frac: float) -> int | None:
    """Brownout level 1 (runtime/pressure.py): shrink the live process
    cache to ``shrink_frac`` of its current budget — evicting LRU-first,
    never invalidating surviving entries — and latch the cap so later
    ``cache_for`` resolutions (explicit or auto) cannot grow past it
    while the brownout holds (their un-capped value is tracked as the
    INTENDED budget instead). Returns the pre-shrink budget, or None
    when no cache is live."""
    global _PRESSURE_CAP, _PRESSURE_INTENDED
    with _PROCESS_LOCK:
        cache = _PROCESS_CACHE
        if cache is None:
            return None
        prev = cache.budget_bytes
        _PRESSURE_CAP = max(int(prev * shrink_frac), 1)
        _PRESSURE_INTENDED = prev
        cap = _PRESSURE_CAP
    # Eviction work runs OFF the process lock (set_budget takes the
    # cache's own lock; a long eviction walk must not stall cache_for).
    cache.set_budget(cap)
    return prev


def lift_pressure_cap(restore_bytes: int | None = None) -> None:
    """Reverse :func:`apply_pressure_cap`: drop the latch and install
    the INTENDED budget — the pre-shrink value, updated by normal
    precedence for every resolution that landed while the cap held — so
    an explicit pin installed mid-brownout is honored rather than blown
    past by a blind restore. ``restore_bytes`` (apply's return value) is
    only the fallback for callers holding state from before the
    intended-budget tracking."""
    global _PRESSURE_CAP, _PRESSURE_INTENDED
    with _PROCESS_LOCK:
        _PRESSURE_CAP = None
        intended, _PRESSURE_INTENDED = _PRESSURE_INTENDED, None
        cache = _PROCESS_CACHE
    target = intended if intended is not None else restore_bytes
    if cache is not None and target and target != cache.budget_bytes:
        cache.set_budget(target)


def pressure_cap() -> int | None:
    """The live brownout cap (tests/introspection)."""
    with _PROCESS_LOCK:
        return _PRESSURE_CAP


def reset_process_cache() -> None:
    """Drop the process cache (tests; a library caller switching models can
    simply let LRU eviction and the stat guards do their job)."""
    global _PROCESS_CACHE, _PROCESS_BUDGET_EXPLICIT, _PRESSURE_CAP
    global _PRESSURE_INTENDED
    with _PROCESS_LOCK:
        if _PROCESS_CACHE is not None:
            _PROCESS_CACHE.clear()
        _PROCESS_CACHE = None
        _PROCESS_BUDGET_EXPLICIT = False
        _PRESSURE_CAP = None
        _PRESSURE_INTENDED = None
    # A dropped cache must not leave a stale registry source behind.
    _OBS_REGISTRY.unregister("host_cache")


__all__ = [
    "HostShardCache",
    "apply_pressure_cap",
    "auto_budget_bytes",
    "available_host_bytes",
    "cache_for",
    "lift_pressure_cap",
    "pressure_cap",
    "process_cache",
    "reset_process_cache",
    "stat_guard",
]
