"""Device residency tier: pin the hottest layers in HBM, stream the rest.

The architecture's defining cost is that every sweep streams the whole
model through the host->HBM link (PAPER.md §0: the loop inversion) while
the chip's HBM sits nearly empty — the resident-vs-streaming gate was
all-or-nothing (``config.decode_resident``). This module spends leftover
HBM on a *partial* residency tier: given a byte budget
(``FrameworkConfig.hbm_pin_gb``; by default derived from the chip, see
``auto_pin_budget_bytes``), a planner selects the layers with the
highest streamed-bytes-per-sweep — the always-hot non-decoder layers
(embedding, lm_head, final norm) first, then as many transformer blocks
as fit — and the first sweep that streams them
keeps what it placed: device-resident for the process lifetime. Every
later shard build subtracts them: their bytes never cross the link
again, and the forward pass sees them merged back into the shard's
segment list at placement (consumers already iterate per-segment, so a
pinned layer is just one more pre-placed segment).

Safety model (mirrors ``runtime/hostcache.py``):

- A pin is the stream's own tree: built by ``_HostShardLoader.
  build_host_shard`` on the source's producer thread — retried,
  checksum-verified, re-read-healed, and chaos-injected like every
  streamed load, because it IS one — placed by ``executor._place``, and
  then seated (``DeviceResidencyTier.seat``) instead of dropped after
  use. No second read, crc pass or upload. A pinned tree is
  *verified-clean by construction*.
- A load whose corruption survives every re-read is NEVER pinned: the
  layer is demoted back to streaming (the stream path's typed error and
  quarantine surface through the normal degrade machinery) instead of
  poisoning a resident copy for the process lifetime.
- The pin set is frozen per source at construction and every layer of it
  is a segment of its own whether it is seated yet or not, so a wave's
  prefill and its decode steps, and the seating sweep and every sweep
  after it, see the same segment structure (nothing recompiles).
- Budget precedence follows the host cache's rule: an EXPLICIT
  ``hbm_pin_gb`` pins the cap (a later auto-config component in the same
  process cannot grow it); an auto budget only ever grows an auto-sized
  tier; auto resolves to OFF under fault injection (chaos schedules must
  keep their per-load draws) and on chips with unknown HBM.

Accounting honesty: pinned bytes are device-resident for the whole run,
so ``peak_hbm_gb`` figures are floored at the pin tier's bytes, the serve
stats line carries ``pinned_bytes`` / ``stream_bytes_saved`` and every
sweep's record ``pinned_bytes`` / ``pin_hits`` — the low-memory claim can
never silently exclude the tier.

Budget caveat: layers are charged at their on-disk (streamed) size. For
int4/int8 checkpoints the pinned copy dequantizes to the compute dtype on
placement (2-8x the packed bytes in HBM) — leave headroom accordingly
(docs/residency.md).
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Sequence

from flexible_llm_sharding_tpu.obs import trace as obs_trace
from flexible_llm_sharding_tpu.obs.registry import REGISTRY as _OBS_REGISTRY
from flexible_llm_sharding_tpu.utils import checkpoint

# Auto budget: fraction of the chip's TOTAL HBM held back for activations,
# KV caches, the prefetch queue, and XLA scratch — the pin tier only
# spends what is left of the measured free HBM after this headroom.
ACTIVATION_HEADROOM_FRACTION = 0.35
# Where a source's own in-flight shards (``in_flight_bytes``) outgrow that
# fraction, the headroom is what they take plus this fraction of the chip
# for the step's scratch and activations (measured on the v5e: 0.14-0.26
# GB, 1-2% of its HBM, at 8 to 32 prompts a batch; PERF.md, PR 26): a model
# with large shards then pins less instead of failing to allocate where,
# unpinned, it ran.
SCRATCH_HEADROOM_FRACTION = 0.05
# What a pass leaves for the programs it is about to compile when it sizes
# the pins to its blocks (``make_room_for_activations``): a compiled step
# lives on the chip too, and at the seating pass none is there yet. A model
# of three layer kinds over 16 block shapes holds 48 decoder programs of
# 20-30 MB of generated code each (AOT for the v5e; on the chip the process
# held 0.99 GB beside its pins after a window: PERF.md, PR 37); the other
# cells' programs take 0.10-0.22 GB (PR 32).
PROGRAM_HEADROOM_FRACTION = 0.10


def layer_stream_bytes(
    model_path: str, layer_names: Sequence[str], tied_embeddings: bool = False
) -> dict[int, int]:
    """Estimated streamed bytes per sweep per layer, from the layer files'
    on-disk size — what ``build_host_shard`` reads and re-uploads every
    sweep (quantized layers travel packed, so file size is the honest
    per-sweep link proxy — NEVER the dequantized logical size, which
    would inflate mixed-precision pinning budgets by the compression
    factor). The name->file mapping is the loader's own
    (``checkpoint.layer_file_for``), so the estimates cannot desync from
    what actually streams. The one layer whose stream differs from its
    file is the tied lm_head over a QUANTIZED embedding: the loader
    dequantizes, transposes, and requantizes it to int8
    (executor._load_one_raw), so what crosses the link is the int8
    [D, V] payload + fp32 [V] scale, not the embed file's packed bytes —
    estimated from the file header's shapes. Unreadable files count 0
    (and are never planned)."""
    out: dict[int, int] = {}
    for i, name in enumerate(layer_names):
        path = checkpoint.layer_file_for(model_path, name, tied_embeddings)
        try:
            if name == "lm_head" and tied_embeddings:
                out[i] = _tied_head_stream_bytes(path)
            else:
                out[i] = os.path.getsize(path)
        except OSError:
            out[i] = 0
    return out


def _tied_head_stream_bytes(embed_path: str) -> int:
    """The tied lm_head's ACTUAL per-sweep link bytes. Float embeddings
    re-materialize as a transpose (same bytes as the file); quantized
    ones requantize to int8 per output channel — q int8 [D, V] + fp32
    scale [V] — whatever the embed file's own packing was."""
    try:
        header, _ = checkpoint.safetensors_header(embed_path)
        q4 = "embedding" + checkpoint.QUANT4_SCALE_SUFFIX in header
        q8 = "embedding" + checkpoint.QUANT_SCALE_SUFFIX in header
        meta = header.get("embedding")
        if meta is None or not (q4 or q8):
            return os.path.getsize(embed_path)
        shape = meta["shape"]
        # int4 packs two values per byte along V (axis -2): the stored
        # payload is [V/2, D], so the logical vocab doubles back.
        v = int(shape[0]) * (2 if q4 else 1)
        d = int(shape[1])
        return d * v + 4 * v
    except (ValueError, KeyError, IndexError):
        # Unparseable header: fall back to the file-size proxy (the
        # integrity layer, not the planner, is where corruption fails).
        return os.path.getsize(embed_path)


@dataclasses.dataclass(frozen=True)
class ResidencyPlan:
    """Which layers a byte budget pins, and what each saves per sweep."""

    budget_bytes: int
    pinned: tuple[int, ...]  # layer idxs, execution order
    layer_bytes: tuple[tuple[int, int], ...]  # (idx, est streamed bytes)
    skipped: tuple[int, ...]  # considered but didn't fit the budget

    @property
    def pinned_set(self) -> frozenset:
        return frozenset(self.pinned)

    @property
    def pinned_bytes_est(self) -> int:
        sizes = dict(self.layer_bytes)
        return sum(sizes[i] for i in self.pinned)

    @property
    def total_bytes_est(self) -> int:
        return sum(b for _, b in self.layer_bytes)

    @property
    def pinned_fraction(self) -> float:
        total = self.total_bytes_est
        return self.pinned_bytes_est / total if total else 0.0


def plan_residency(
    model_path: str,
    layer_names: Sequence[str],
    budget_bytes: int,
    tied_embeddings: bool = False,
) -> ResidencyPlan:
    """Greedy selection under the byte budget.

    Priority order: the always-hot non-decoder layers first (embedding,
    lm_head, final norm — they run every sweep AND bracket every decode
    step's embed/head hops), then transformer blocks by descending
    streamed bytes (stable by layer index on ties — for the usual uniform
    blocks that is simply the first N). A layer that does not fit is
    skipped and the scan continues: smaller later layers may still fit
    (greedy knapsack, never an error).

    Mixed-precision checkpoints co-optimize: a pinned layer keeps its
    dtype (pinning is purely a bytes-saved lever, never a quality one),
    so streamed size stays the primary key — which ALREADY pins the
    plan's bf16 layers first for uniform-width models, since
    uncompressed layers are the most expensive to stream. The embedded
    plan's dtype (bf16 before int8 before int4) breaks SIZE TIES only:
    it must never outrank a larger lower-precision layer, which would
    strictly reduce the bytes a budget saves."""
    sizes = layer_stream_bytes(model_path, layer_names, tied_embeddings)
    dtype_rank = {}
    try:
        from flexible_llm_sharding_tpu.runtime.precisionplan import (
            PrecisionPlan,
        )

        plan = PrecisionPlan.load(model_path)
    except (ValueError, OSError):
        # Corrupt or unreadable embedded plan: planning is an
        # optimization (losing the dtype tie-break only) and must not be
        # its enforcement point — the loader's plan/manifest check
        # (executor._check_precision_plan) surfaces the typed error.
        plan = None
    if plan is not None:
        rank = {"bf16": 0, "int8": 1, "int4": 2}
        dtype_rank = {
            i: rank.get(plan.dtypes.get(name, ""), 0)
            for i, name in enumerate(layer_names)
        }

    def tier(i: int) -> int:
        return 1 if layer_names[i].startswith("model.layers.") else 0

    order = sorted(
        range(len(layer_names)),
        key=lambda i: (tier(i), -sizes[i], dtype_rank.get(i, 0), i),
    )
    pinned: list[int] = []
    skipped: list[int] = []
    used = 0
    for i in order:
        if budget_bytes > 0 and sizes[i] > 0 and used + sizes[i] <= budget_bytes:
            pinned.append(i)
            used += sizes[i]
        else:
            skipped.append(i)
    return ResidencyPlan(
        budget_bytes=int(budget_bytes),
        pinned=tuple(sorted(pinned)),
        layer_bytes=tuple((i, sizes[i]) for i in range(len(layer_names))),
        skipped=tuple(sorted(skipped)),
    )


def full_pin_plan(
    model_path: str,
    layer_names: Sequence[str],
    tied_embeddings: bool = False,
) -> ResidencyPlan:
    """A plan that pins EVERY layer — the resident draft model's case
    (``runtime/draft.py``): the model is chosen precisely because it fits
    on chip whole, so the budget is the model's own footprint and the
    greedy knapsack degenerates to "all of it". Kept here so the draft
    tier rides the same ``ResidencyPlan``/``DeviceResidencyTier``
    machinery (verified pin loads, demote-on-failure, stats) instead of
    a parallel pinning path."""
    sizes = layer_stream_bytes(model_path, layer_names, tied_embeddings)
    total = sum(sizes)
    return plan_residency(
        model_path, layer_names, max(total, 1), tied_embeddings
    )


def in_flight_bytes(cfg, layer_names: Sequence[str], tied_embeddings: bool) -> int:
    """What one weight source holds on the chip beside the pins while it
    streams: the prefetch queue's depth, the shard in the producer's hand
    and the one at the consumer, each taken at the largest shard's size
    (by the layer files, as the planner counts)."""
    from flexible_llm_sharding_tpu.parallel.planner import plan_shards_dp

    sizes = layer_stream_bytes(cfg.model_path, layer_names, tied_embeddings)
    shards = plan_shards_dp(len(layer_names), cfg.layer_num_per_shard).shards
    largest = max(sum(sizes[i] for i in shard) for shard in shards)
    return (max(1, cfg.effective_prefetch_depth()) + 2) * largest


def _chip_account(target) -> tuple[float, float]:
    """``(limit, in_use)`` of the chip behind ``target`` (a device, or a
    placement, which resolves to its first chip): the allocator's
    ``bytes_limit`` and its ``bytes_in_use`` less the process tier's own
    pins there, or the device-kind HBM table (assumed empty) where the
    device reports no stats. ``(0, 0)`` where neither is known (the CPU
    backend). On a TPU a failed stats query or an unknown device kind
    raises (utils/metrics.py); it is never read as "nothing there"."""
    from flexible_llm_sharding_tpu.utils.metrics import (
        chip_hbm_gb,
        device_memory_stats,
    )

    device = probe_chip(target)
    stats = device_memory_stats(device)
    limit = stats.get("bytes_limit")
    if not limit:
        return (chip_hbm_gb(device) or 0.0) * 1e9, 0.0
    in_use = stats.get("bytes_in_use", 0.0)
    # The process tier's own pins on this target are in ``in_use`` but are
    # the plan's to spend, not someone else's: a later source must read
    # the budget its pins were planned under, not what is left beside them.
    tier = process_tier()
    if tier is not None:
        # This target's, or the heaviest target's for a caller that probes
        # a chip under another handle than its source places on.
        in_use -= (
            tier.pinned_device_bytes(target) or tier.max_pinned_device_bytes()
        )
    return limit, in_use


def auto_pin_budget_bytes(device=None, in_flight_bytes: int = 0) -> int:
    """Auto pin budget: measured free HBM minus the headroom, which is the
    larger of ``ACTIVATION_HEADROOM_FRACTION`` of the chip and
    ``in_flight_bytes`` (see the function of that name) plus
    ``SCRATCH_HEADROOM_FRACTION`` of the chip.

    Free = what ``_chip_account`` reads. The CPU backend has no account and
    resolves to 0 (off) — the budget is only ever spent where it is
    real."""
    limit, in_use = _chip_account(device)
    headroom = max(
        ACTIVATION_HEADROOM_FRACTION * limit,
        in_flight_bytes + SCRATCH_HEADROOM_FRACTION * limit,
    )
    return int(max(0.0, limit - in_use - headroom))


def activation_budget_bytes(device, tier, in_flight_bytes: int) -> int:
    """Bytes of activations a scoring pass may keep on the chip between
    shards (``ActivationStore(device_budget=)``), for a pass whose user set
    no ``storage_location``: half of what the chip has free BY THE PLAN.

    By the plan, not by the instant: the limit less what is in use beside
    the tier's pins, less the pins ``tier`` has planned (or holds, where
    that is more: during the seating sweep the pins are not there yet, so
    the allocator's count at the pass's start flatters), less
    ``in_flight_bytes``, the shards the source holds while it streams. What
    is left is the headroom ``auto_pin_budget_bytes`` kept for the step's
    scratch and the activations together (5% of the chip at the least,
    against a step's measured 1-2%); the store is charged to it, not to a
    second reserve, and takes half so that the other half stays the
    step's. ``_decoder_block`` is given its activations to overwrite, so a
    block held here is the one the step works in: one generation of blocks
    is all a pass has on the chip. 0 on the CPU backend (no account) and
    wherever explicit pins leave nothing: every block then goes the
    ``cpu`` way, as before."""
    limit, in_use = _chip_account(device)
    pins = tier.committed_device_bytes(device) if tier is not None else 0
    return int(max(0.0, limit - in_use - pins - in_flight_bytes) // 2)


def make_room_for_activations(
    device, tier, in_flight_bytes: int, need_bytes: int, tied_embeddings: bool
) -> int:
    """``activation_budget_bytes`` for a pass whose blocks take
    ``need_bytes`` a generation, after giving up pins for them where that
    makes them fit. ONE decision, at a process's seating pass, when the
    blocks' sizes are first known and nothing is seated yet: an AUTO-sized
    tier is re-planned to what the chip has free less the shards in flight,
    less twice the need (the store takes half of what is left,
    ``activation_budget_bytes``), less ``PROGRAM_HEADROOM_FRACTION`` of the
    chip for the programs this pass is about to compile. A layer that
    streams costs its bytes over the link once a sweep; a generation of
    blocks that does not fit crosses it twice a SHARD, and the host copies
    it (a residual of several streams, ``LlamaConfig.hc_mult``: 0.71 GB a
    generation against a rest of 0.3 GB: PERF.md, PR 37). The tier keeps the
    difference (``activation_reserve_bytes``) off every later auto budget. A
    pass that fits as planned, an explicit ``hbm_pin_gb``, a brownout and a
    tier with anything seated (the plan stands: a source froze its pin set
    on it) change nothing, and what does not fit goes the ``cpu`` way as
    before."""
    budget = activation_budget_bytes(device, tier, in_flight_bytes)
    if tier is None or budget >= need_bytes:
        return budget
    limit, in_use = _chip_account(device)
    pins = int(
        limit - in_use - in_flight_bytes - 2 * need_bytes
        - PROGRAM_HEADROOM_FRACTION * limit
    )
    with _PROCESS_LOCK:
        fixed = (
            tier is not _PROCESS_TIER or _PROCESS_BUDGET_EXPLICIT
            or tier.pressure_demoted or pins >= tier.plan.budget_bytes
            or tier.pinned_device_bytes(device)
        )
    if fixed:
        return budget
    plan = plan_residency(tier.model_path, tier.layer_names, max(pins, 0), tied_embeddings)
    with _PROCESS_LOCK:
        if tier is _PROCESS_TIER and not _PROCESS_BUDGET_EXPLICIT:
            tier.activation_reserve_bytes += tier.plan.budget_bytes - plan.budget_bytes
            tier._install_plan(plan)
    return activation_budget_bytes(device, tier, in_flight_bytes)


def placement_key(device) -> tuple:
    """Stable identity of a placement target, so pins survive the target
    OBJECT being rebuilt (a NamedSharding recreated per scorer instance
    must hit the same pins, not leak a second copy)."""
    if device is None:
        return ("default",)
    if hasattr(device, "segment_target") and hasattr(device, "mesh"):
        # TpPlacement: per-kind shardings over one tp mesh.
        return (
            "tp",
            tuple(int(d.id) for d in device.mesh.devices.flat),
        )
    mesh = getattr(device, "mesh", None)
    spec = getattr(device, "spec", None)
    if mesh is not None and spec is not None:  # NamedSharding
        return (
            "sharding",
            tuple(int(d.id) for d in mesh.devices.flat),
            str(spec),
        )
    did = getattr(device, "id", None)
    if did is not None:  # a plain jax Device
        return ("device", int(did))
    return ("object", id(device))


def probe_chip(target):
    """One real jax Device of a placement target (a TpPlacement,
    NamedSharding, or raw Mesh resolves to its mesh's first chip) — for
    HBM probes that need a concrete device handle."""
    mesh = getattr(target, "mesh", None)
    if mesh is None and hasattr(getattr(target, "devices", None), "flat"):
        mesh = target  # a raw jax Mesh
    if mesh is not None:
        return next(iter(mesh.devices.flat))
    return target


def _tree_nbytes(segments) -> int:
    """Total logical bytes of a HOST tree (unsharded numpy leaves) — the
    per-sweep link traffic a pin skip saves."""
    import jax

    return sum(
        int(a.nbytes)
        for _, seg in segments
        for a in jax.tree.leaves(seg)
        if hasattr(a, "nbytes")
    )


def _placed_device_nbytes(segments) -> int:
    """Per-chip resident bytes of a PLACED tree: the most bytes any single
    device holds. ``jax.Array.nbytes`` is the GLOBAL logical size, so on a
    TP/mesh placement it overstates per-chip HBM by the shard factor —
    sharded leaves must count 1/Nth per chip, replicated leaves count
    fully on every chip."""
    import jax

    per_dev: dict = {}
    for _, seg in segments:
        for a in jax.tree.leaves(seg):
            shards = getattr(a, "addressable_shards", None)
            if shards:
                for sh in shards:
                    d = sh.device
                    per_dev[d] = per_dev.get(d, 0) + int(sh.data.nbytes)
            elif hasattr(a, "nbytes"):
                per_dev[None] = per_dev.get(None, 0) + int(a.nbytes)
    return max(per_dev.values(), default=0)


class DeviceResidencyTier:
    """Process-lifetime pins of the planned layers' placed parameter trees.

    A streaming source asks ``seat_state`` for each planned layer of a
    shard it builds: a seated layer is merged (``seated``) and costs
    nothing; an unseated one is read, verified and uploaded as the sweep
    would anyway, and the placed segments are kept (``seat``) — the
    first sweep of a process seats the whole plan from its own stream.
    ``segments(idx, device, loader)`` is the load-on-first-request form
    for a model pinned whole at construction (the resident draft).
    Callers treat the seated segments as immutable (they are shared
    across sweeps and across sources; the jitted blocks never donate
    parameter trees).

    A planned layer whose load or placement fails persistently
    (quarantined corruption, exhausted retries, no room) is demoted back
    to streaming for this tier's lifetime (``demote``): wrong bytes are
    never pinned, and the layer's typed error keeps surfacing through the
    normal stream-side degrade machinery. Demotion is one-way, and a
    demoted layer of a source's frozen pin set stays a segment of its
    own, so segment structures never change under a live source.
    """

    def __init__(
        self, model_path: str, layer_names: Sequence[str], plan: ResidencyPlan
    ):
        self.model_path = model_path
        self.layer_names = list(layer_names)
        self.plan = plan  # guarded by: _lock
        self._lock = threading.RLock()
        self._failed: set[int] = set()  # guarded by: _lock
        # idx -> host-tree bytes when seated (the exact per-sweep link
        # bytes a skip saves; recorded once, device-independent).
        self._host_nbytes: dict[int, int] = {}  # guarded by: _lock
        # Planner's byte estimates, dict-shaped once: note_skip runs under
        # the lock on every shard build of every sweep.
        self._plan_bytes: dict[int, int] = dict(plan.layer_bytes)  # guarded by: _lock
        # placement key -> {idx: placed segment list}
        self._placed: dict[tuple, dict[int, list]] = {}  # guarded by: _lock
        self._dev_bytes: dict[tuple, int] = {}  # guarded by: _lock
        self.pin_hits = 0
        self.stream_bytes_saved = 0
        self.pin_loads = 0
        self.pin_failures = 0
        # Brownout demotion (runtime/pressure.py): while True, the plan
        # is the empty pressure plan and tier_for skips every resize —
        # an auto grower racing a brownout must not re-install pins the
        # ladder just evicted. pressure_restore() re-installs the saved
        # plan. Public so tier_for can read it without a tier method.
        self.pressure_demoted = False  # guarded by: _lock
        self._saved_plan: ResidencyPlan | None = None  # guarded by: _lock
        # Bytes an auto budget leaves out of the pins for a pass's
        # activations (make_room_for_activations): tier_for takes them off
        # every later auto budget, so the plan does not grow back.
        self.activation_reserve_bytes = 0  # guarded by: _PROCESS_LOCK

    # -- membership --------------------------------------------------------

    def frozen_pinned(self, layer_idxs_groups) -> frozenset:
        """The pin set a source captures at construction: planned-and-
        healthy layers among the shards it will stream. Frozen per source
        so one source's segment structure never changes mid-life."""
        with self._lock:
            return frozenset(
                i
                for group in layer_idxs_groups
                for i in group
                if i in self.plan.pinned_set and i not in self._failed
            )

    # -- pinning -----------------------------------------------------------

    def seated(self, idx: int, device) -> list | None:
        """The layer's resident placed segments on ``device``, or None
        while it is not seated there."""
        with self._lock:
            return self._placed.get(placement_key(device), {}).get(idx)

    def seat_state(self, idx: int, devices) -> str:
        """What a shard build does with a planned layer: ``"seated"`` (on
        every one of ``devices``: merge the resident copy, upload
        nothing), ``"failed"`` (demoted: stream it) or ``"unseated"``
        (stream it this once and keep what was placed)."""
        with self._lock:
            if idx in self._failed:
                return "failed"
            for d in devices:
                if self._placed.get(placement_key(d), {}).get(idx) is None:
                    return "unseated"
            return "seated"

    def seat(self, idx: int, device, host, placed: list) -> list:
        """Keep ``placed`` — the verified host tree ``host`` of one layer,
        already uploaded — as ``idx``'s pin on ``device``, and return the seated
        list. The read-once shape: the bytes are the sweep's own, read,
        checked and uploaded by the source that needed them anyway. The
        first seat wins (a concurrent source's duplicate is dropped, never
        double-counted); a demoted layer is never seated."""
        key = placement_key(device)
        with self._lock:
            if idx in self._failed:
                return placed
            seats = self._placed.setdefault(key, {})
            if seats.get(idx) is None:
                seats[idx] = placed
                self._host_nbytes.setdefault(idx, _tree_nbytes(host))
                self._dev_bytes[key] = self._dev_bytes.get(
                    key, 0
                ) + _placed_device_nbytes(placed)
                self.pin_loads += 1
            return seats[idx]

    def demote(self, idx: int) -> None:
        """A planned layer's load or placement failed (quarantined
        corruption, exhausted retries, no room): never pin unverified
        bytes — the layer streams for this tier's lifetime, where its
        typed error surfaces through the normal degrade machinery."""
        with self._lock:
            if idx not in self._failed:
                self._failed.add(idx)
                self.pin_failures += 1

    def segments(self, idx: int, device, loader) -> list:
        """The pinned layer's placed segment list on ``device``, loaded
        through ``loader`` on first request (the resident draft's path: a
        model pinned whole at construction; a streaming source seats from
        its own stream instead, see ``seat``). Raises the loader's typed
        error when the load fails — after demoting the layer so no later
        source plans it."""
        from flexible_llm_sharding_tpu.runtime.executor import _place

        hit = self.seated(idx, device)
        if hit is not None:
            return hit
        with self._lock:
            if idx in self._failed:
                raise checkpoint_unavailable(self.layer_names[idx])
        try:
            # One traced span per pin load: pins ride the same verified/
            # retried path as the stream, but load ONCE per process — the
            # timeline shows them as one-time costs, not per-sweep ones.
            with obs_trace.span(
                "residency_pin", cat="residency",
                layer=self.layer_names[idx], idx=idx,
            ):
                host = loader.build_host_shard((idx,))
                placed = _place(host, device, np_dtype=loader.np_dtype)
        except Exception:
            self.demote(idx)
            raise
        return self.seat(idx, device, host, placed)

    def note_skip(self, idx: int) -> None:
        """One pinned layer's bytes were subtracted from one shard build
        (one sweep's worth of link traffic saved)."""
        with self._lock:
            self.pin_hits += 1
            saved = self._host_nbytes.get(idx)
            if saved is None:
                saved = self._plan_bytes.get(idx, 0)
            self.stream_bytes_saved += saved

    # -- observability -----------------------------------------------------

    def pinned_device_bytes(self, device=None) -> int:
        """Resident bytes pinned on ONE placement target — the per-chip
        HBM cost of the tier (the peak_hbm floor)."""
        with self._lock:
            return self._dev_bytes.get(placement_key(device), 0)

    def committed_device_bytes(self, device=None) -> int:
        """What the tier takes of ONE placement target by its plan: the
        planned layers' bytes, or what is seated there where that is more
        (a brownout's empty plan leaves its seats)."""
        with self._lock:
            return max(
                self.plan.pinned_bytes_est,
                self._dev_bytes.get(placement_key(device), 0),
            )

    def max_pinned_device_bytes(self) -> int:
        """The heaviest single placement target's resident bytes — the
        per-chip peak_hbm floor when the caller has no device handle (the
        process-wide ``stats()['pinned_bytes']`` sums ALL targets, which
        overstates a per-chip peak by Nx on pipeline/DP runs)."""
        with self._lock:
            return max(self._dev_bytes.values(), default=0)

    def stats(self) -> dict[str, float]:
        with self._lock:
            return {
                # Distinct layers seated on ANY placement target: DP
                # replication seats the same idxs everywhere (union ==
                # per-chip count) while pipeline mode splits the plan
                # across stage chips (a per-target max would underreport
                # an engaged tier as demotions).
                "pinned_layers": len(
                    {i for m in self._placed.values() for i in m}
                ),
                "planned_layers": len(self.plan.pinned),
                # Per-chip resident bytes summed across placement targets
                # (one chip: the tier's HBM cost; DP: the process-wide
                # total; a TP mesh target contributes its per-chip cost,
                # not the global logical size).
                "pinned_bytes": sum(self._dev_bytes.values()),
                "stream_bytes_saved": self.stream_bytes_saved,
                "pin_hits": self.pin_hits,
                "pin_loads": self.pin_loads,
                "pin_failures": self.pin_failures,
                "budget_bytes": self.plan.budget_bytes,
                # 1 while a brownout holds the empty plan (the ladder's
                # "pins evicted, not yet restored" witness).
                "pressure_demoted": int(self.pressure_demoted),
            }

    def set_budget(self, budget_bytes: int, tied_embeddings: bool = False) -> None:
        """Re-plan under a new budget. Shrink drops layers from the PLAN
        (future sources stream them; live sources keep their frozen sets
        and the already-placed trees stay until process exit — dropping
        them under a live source would desync its segment structure).

        The re-plan stats every layer file on disk, so it runs OFF the
        tier lock (a wedged filesystem must not stall note_skip/stats on
        the hot path); only the plan swap happens inside. Two concurrent
        re-plans race benignly: last swap wins, both plans are
        self-consistent snapshots."""
        plan = plan_residency(
            self.model_path, self.layer_names, budget_bytes, tied_embeddings
        )
        self._install_plan(plan)

    def _install_plan(self, plan: ResidencyPlan) -> None:
        with self._lock:
            if self.pressure_demoted:
                # A brownout demotion landed while the caller planned (or
                # between its off-lock pressure_demoted pre-check and
                # here — the pre-checks run under _PROCESS_LOCK, the
                # demotion under THIS lock, so only this check is
                # race-free): the evicted plan wins, the install is
                # dropped. pressure_restore() reinstates the saved plan.
                return
            self.plan = plan

    # -- brownout (runtime/pressure.py) ------------------------------------

    def pressure_unpin(self) -> int:
        """Brownout level 2: evict the residency pins back to streaming.
        Installs an EMPTY plan (budget 0) so every source built from now
        on streams everything, and latches ``pressure_demoted`` so
        ``tier_for`` cannot resize the plan back mid-brownout. Returns
        the number of planned layers demoted (0 when already demoted or
        nothing was planned).

        The already-placed device trees are NOT dropped: live sources
        froze their pin sets at construction and merge those exact
        segments every build — yanking the seats would either desync
        their segment structure or force a reload under the very memory
        pressure this lever exists to relieve. The placed copies free
        once the live sources cycle (the serve engine rebuilds its
        source on every recovery; offline runs build one per call);
        what this lever guarantees immediately is that no NEW HBM is
        spent on pins and no new source plans any."""
        with self._lock:
            if self.pressure_demoted:
                return 0
            demoted = len(self.plan.pinned)
            self._saved_plan = self.plan
            self.plan = ResidencyPlan(
                budget_bytes=0,
                pinned=(),
                layer_bytes=self.plan.layer_bytes,
                skipped=tuple(range(len(self.layer_names))),
            )
            self.pressure_demoted = True
        obs_trace.instant(
            "pressure_unpin", cat="pressure", layers=demoted
        )
        return demoted

    def pressure_restore(self) -> int:
        """Reverse :meth:`pressure_unpin`: re-install the saved plan.
        Pins whose placed trees survived (live sources kept them seated)
        serve again immediately; dropped ones are seated again from the
        next source's own stream. Returns the number of layers restored
        to the plan."""
        with self._lock:
            if not self.pressure_demoted:
                return 0
            saved, self._saved_plan = self._saved_plan, None
            if saved is not None:
                self.plan = saved
            self.pressure_demoted = False
            restored = len(self.plan.pinned)
        obs_trace.instant(
            "pressure_repin", cat="pressure", layers=restored
        )
        return restored


def checkpoint_unavailable(name: str):
    """The typed error for a layer demoted after a failed pin: the same
    ShardCorruptError family the stream path raises, so the serving
    degrade machinery applies unchanged."""
    from flexible_llm_sharding_tpu.integrity.manifest import ShardCorruptError

    return ShardCorruptError(
        f"{name}: the pin's load failed persistently; layer demoted to "
        "streaming (audit with the `verify` CLI subcommand)"
    )


# -- process-wide tier -------------------------------------------------------
# One tier per process (mirrors hostcache.cache_for): the serving engine
# rebuilds its weight source on every recovery, offline decode builds one
# source per call — all of them must find the SAME pins (load once, resident
# for the process lifetime). Budget precedence follows the host cache's
# rule: explicit pins the cap; auto only grows an auto-sized tier.

_PROCESS_TIER: DeviceResidencyTier | None = None
_PROCESS_TIER_KEY: tuple | None = None
_PROCESS_BUDGET_EXPLICIT = False
_PROCESS_LOCK = threading.Lock()


def tier_for(
    cfg, layer_names: Sequence[str], tied_embeddings: bool, device=None
) -> DeviceResidencyTier | None:
    """The process residency tier for ``cfg``, or None when the budget
    resolves to 0 (hbm_pin_gb=0, chaos auto-off, unknown HBM)."""
    explicit = cfg.hbm_pin_gb is not None
    # An auto budget leaves room for what the source will hold in flight.
    budget = cfg.effective_hbm_pin_bytes(
        device,
        0 if explicit else in_flight_bytes(cfg, layer_names, tied_embeddings),
    )
    if budget <= 0:
        return None
    key = (
        os.path.abspath(cfg.model_path),
        cfg.dtype,
        bool(cfg.verify_weights),
        tuple(layer_names),
        bool(tied_embeddings),
    )
    global _PROCESS_TIER, _PROCESS_TIER_KEY, _PROCESS_BUDGET_EXPLICIT
    if not explicit:
        with _PROCESS_LOCK:
            if _PROCESS_TIER is not None and _PROCESS_TIER_KEY == key:
                # What a pass's activations were given stays theirs.
                budget -= _PROCESS_TIER.activation_reserve_bytes
    # Planning stats every layer file on disk, so it never runs under
    # _PROCESS_LOCK (a wedged filesystem would stall process_tier() and
    # every source construction in the process): decide under the lock,
    # plan outside, install/adjust under the lock again.
    resize = False
    with _PROCESS_LOCK:
        tier = (
            _PROCESS_TIER
            if _PROCESS_TIER is not None and _PROCESS_TIER_KEY == key
            else None
        )
        if tier is not None:
            if tier.pressure_demoted:
                # Mid-brownout: the ladder evicted the pins; no caller —
                # explicit or auto — may re-plan them until the pressure
                # lifts (pressure_restore re-installs the saved plan).
                resize = False
            elif explicit:
                resize = tier.plan.budget_bytes != budget
                if not resize:
                    # The cap is already in effect; when a resize IS
                    # needed the latch waits for the install (a failed
                    # off-lock re-plan must not leave the process marked
                    # explicit with the cap never applied, permanently
                    # blocking auto growth).
                    _PROCESS_BUDGET_EXPLICIT = True
            else:
                resize = (
                    not _PROCESS_BUDGET_EXPLICIT
                    and budget > tier.plan.budget_bytes
                )
    if tier is not None:
        if resize:
            _apply_process_budget(tier, budget, explicit, tied_embeddings)
        return tier
    plan = plan_residency(cfg.model_path, layer_names, budget, tied_embeddings)
    with _PROCESS_LOCK:
        if _PROCESS_TIER is not None and _PROCESS_TIER_KEY == key:
            # Lost the install race to a concurrent first caller: reuse the
            # winner's tier, but still apply THIS caller's budget
            # precedence — an explicit cap must pin the process budget
            # (and resize to it) even when an auto caller won the install,
            # or a later auto call could grow past the pinned cap.
            tier = _PROCESS_TIER
            if tier.pressure_demoted:
                resize = False  # brownout holds the empty plan (see above)
            elif explicit:
                resize = tier.plan.budget_bytes != budget
                if not resize:
                    _PROCESS_BUDGET_EXPLICIT = True
            else:
                resize = (
                    not _PROCESS_BUDGET_EXPLICIT
                    and budget > tier.plan.budget_bytes
                )
        else:
            _PROCESS_TIER = DeviceResidencyTier(cfg.model_path, layer_names, plan)
            _PROCESS_TIER_KEY = key
            _PROCESS_BUDGET_EXPLICIT = explicit
            # Registry citizen: pinned_bytes / stream_bytes_saved on the
            # metrics endpoint are the same numbers the stats lines print.
            _OBS_REGISTRY.register("residency", _PROCESS_TIER.stats)
            return _PROCESS_TIER
    if resize:
        # Reuse the plan computed above — it was planned for exactly this
        # budget; re-planning would repeat the full disk-stat sweep.
        _apply_process_budget(tier, budget, explicit, tied_embeddings, plan=plan)
    return tier


def _apply_process_budget(
    tier: DeviceResidencyTier,
    budget: int,
    explicit: bool,
    tied_embeddings: bool,
    plan: ResidencyPlan | None = None,
) -> None:
    """Re-plan ``tier`` to ``budget`` and install the plan iff this
    caller's budget precedence STILL holds at install time. Planning stats
    every layer file off all locks, so another caller can land while this
    one is planning — without the re-check under _PROCESS_LOCK, a late
    last-swap-wins install would silently override an explicitly pinned
    cap, and of two racing auto growers the SMALLER budget could land
    last (auto must only ever grow). Callers that already planned for
    exactly ``budget`` (the tier_for install-race loser) pass ``plan`` to
    skip the second disk-stat sweep."""
    if plan is None:
        plan = plan_residency(
            tier.model_path, tier.layer_names, budget, tied_embeddings
        )
    global _PROCESS_BUDGET_EXPLICIT
    with _PROCESS_LOCK:
        if tier.pressure_demoted:
            # A brownout landed while this caller planned off-lock: the
            # evicted plan wins; this install is dropped (the explicit
            # latch is NOT taken either — the budget was never applied).
            return
        if explicit:
            # Latch only here, with the plan in hand: the install and the
            # explicit mark land together, so a re-plan failure above
            # leaves the process un-marked and auto growth alive.
            _PROCESS_BUDGET_EXPLICIT = True
        elif _PROCESS_BUDGET_EXPLICIT or budget <= tier.plan.budget_bytes:
            # An explicit cap was pinned, or a bigger auto budget was
            # installed, while we planned; either way it wins.
            return
        tier._install_plan(plan)


def process_tier() -> DeviceResidencyTier | None:
    """The live process tier (the CLI's end-of-run stats read it)."""
    with _PROCESS_LOCK:
        return _PROCESS_TIER


def reset_process_tier() -> None:
    """Drop the process tier and its pins (tests; the benchmark between a
    run's window and its reference).
    The placed device arrays free once the last source's references go."""
    global _PROCESS_TIER, _PROCESS_TIER_KEY, _PROCESS_BUDGET_EXPLICIT
    with _PROCESS_LOCK:
        _PROCESS_TIER = None
        _PROCESS_TIER_KEY = None
        _PROCESS_BUDGET_EXPLICIT = False
    # A dropped tier must not leave a stale registry source behind.
    _OBS_REGISTRY.unregister("residency")


def plan_report(model_path: str, budget_bytes: int) -> dict:
    """Dry-run planner audit for the ``verify`` CLI: which layers the
    budget would pin and their per-sweep byte savings — no device, no
    loads, just the plan."""
    from flexible_llm_sharding_tpu.config import LlamaConfig

    model_cfg = LlamaConfig.from_pretrained(model_path)
    layer_names = checkpoint.layer_names_for(
        model_cfg.num_hidden_layers, tie_word_embeddings=False
    )
    plan = plan_residency(
        model_path, layer_names, budget_bytes, model_cfg.tie_word_embeddings
    )
    sizes = dict(plan.layer_bytes)
    return {
        "model_path": model_path,
        "budget_gb": round(budget_bytes / 1e9, 3),
        "pinned": [
            {"layer": layer_names[i], "bytes": sizes[i]} for i in plan.pinned
        ],
        "pinned_layers": len(plan.pinned),
        "total_layers": len(layer_names),
        "pinned_bytes": plan.pinned_bytes_est,
        "total_bytes": plan.total_bytes_est,
        "pinned_fraction": round(plan.pinned_fraction, 4),
        # Every sweep that would have streamed these layers now skips
        # exactly these bytes on the host->HBM link.
        "stream_bytes_saved_per_sweep": plan.pinned_bytes_est,
        "skipped_layers": len(plan.skipped),
    }


__all__ = [
    "ACTIVATION_HEADROOM_FRACTION",
    "PROGRAM_HEADROOM_FRACTION",
    "DeviceResidencyTier",
    "ResidencyPlan",
    "activation_budget_bytes",
    "make_room_for_activations",
    "auto_pin_budget_bytes",
    "in_flight_bytes",
    "layer_stream_bytes",
    "placement_key",
    "plan_report",
    "plan_residency",
    "process_tier",
    "reset_process_tier",
    "tier_for",
]
