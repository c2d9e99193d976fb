"""CLI batch driver — the reference's ``main.py`` surface, TPU-native inside.

Same flag set and pickle contracts (``/root/reference/main.py:30-49,55-58,
92-98``): input is a pickle of ``[(prefix_str, (suffix_str, ...)), ...]``;
outputs are a score pickle (one float32 ``[n_suffixes, num_gen_token, vocab]``
array per prompt) and a ``*_updated.pkl`` prompts file with generated text
appended to each suffix.

Differences, all deliberate:
- ``--data_parallel`` parses real booleans (the reference's ``type=bool``
  treats any non-empty string as True, ``/root/reference/main.py:40``).
- ``--storage_location`` accepts ``tpu`` (activations stay in HBM); ``gpu``
  is kept as an alias. Not set, a scoring pass keeps on the chip the
  activations that fit its free memory (``residency.
  activation_budget_bytes``) and sends the rest the ``cpu`` way.
- TPU-specific knobs (``--dtype``, ``--block_size``, ``--prefetch_depth``,
  ``--num_devices``, ``--max_token_len``) extend the surface.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import sys

from flexible_llm_sharding_tpu.config import (
    DEFAULT_MAX_TOKEN_LEN,
    FAULT_SITES,
    FaultConfig,
    FrameworkConfig,
    PressureConfig,
)
from flexible_llm_sharding_tpu.utils.compile_cache import configure_compile_cache


# --- KNOB-SYNC declarations (machine-checked by flscheck, analysis/rules.py).
# A FrameworkConfig/FaultConfig flag normally belongs in BOTH parsers (the
# recurring review defect was adding a knob to one and forgetting the other);
# a flag listed here is deliberately single-parser, for the stated reason.
BATCH_ONLY_FLAGS = frozenset({
    # Workload shape of one offline batch run — serving has no fixed batch.
    "num_batch", "num_gen_token", "disk_folder", "max_activation_in_cpu",
    "resume", "long_context",
    # Multi-chip layouts: serving v1 drives a single placement target
    # (ServeEngine rejects data_parallel/tensor_parallel loudly).
    "data_parallel", "num_devices", "tensor_parallel",
    # Sampling: serving is greedy-only for now (per-request rng streams
    # under sampling are future work; ServeEngine rejects temperature > 0).
    "temperature", "top_k", "top_p", "seed",
    # KV-decode specials that don't compose with the sweep engine. NOTE:
    # the serve parser ALSO defines --speculative_k, but that one sets
    # ServeConfig.speculative_k (the serving-path speculation knob,
    # docs/speculative.md) — this declaration covers the batch parser's
    # FrameworkConfig.speculative_k (the offline scorer's knob); the two
    # are distinct fields behind one flag name, and KNOB-SYNC resolves
    # each parser's flag against its own config class.
    "decode_fused", "speculative_k",
    # Offline observability/profiling of a single run.
    "verbose_metrics", "profile_dir",
})
SERVE_ONLY_FLAGS = frozenset()
# Flags that drive the run (inputs/outputs/cluster wiring/demo pacing) and
# set no config field.
DRIVER_FLAGS = frozenset({
    "prompt_pickle", "output_file", "kv_cache",
    "coordinator_address", "num_processes", "process_id",
    "stagger_ms",
    # One-shot metrics-registry JSON dump path (batch CLI output file).
    "metrics_out",
})


def _str2bool(v: str) -> bool:
    if v.lower() in ("true", "1", "yes"):
        return True
    if v.lower() in ("false", "0", "no", ""):
        return False
    raise argparse.ArgumentTypeError(f"expected a boolean, got {v!r}")


def _str2bool_or_auto(v: str) -> bool | None:
    if v.lower() == "auto":
        return None
    return _str2bool(v)


def _float_or_auto(v: str) -> float | None:
    if v.lower() == "auto":
        return None
    return float(v)


def _add_robustness_flags(p: argparse.ArgumentParser) -> None:
    """Shared by the batch and serve parsers: transient-I/O retry knobs and
    the deterministic chaos (fault-injection) switch."""
    p.add_argument("--io_retry_attempts", type=int, default=4,
                   help="attempts per weight-stream I/O call (layer read, "
                        "host->device put) before a typed ShardLoadError "
                        "surfaces; 1 disables retrying")
    p.add_argument("--io_retry_base_s", type=float, default=0.05,
                   help="first retry backoff; doubles per attempt (jittered)")
    p.add_argument("--io_retry_deadline_s", type=float, default=60.0,
                   help="overall wall cap per retried call (0 = none)")
    p.add_argument("--chaos", action="store_true",
                   help="enable deterministic fault injection at the named "
                        "sites (faults/inject.py) — proves the retry/degrade "
                        "layer on real workloads without waiting for real "
                        "outages; off = zero overhead")
    p.add_argument("--chaos_seed", type=int, default=0,
                   help="injection schedule seed (same seed = same faults)")
    p.add_argument("--chaos_error_rate", type=float, default=0.1,
                   help="probability of an injected IOError per site fire")
    p.add_argument("--chaos_truncate_rate", type=float, default=0.0,
                   help="probability of an injected truncated read")
    p.add_argument("--chaos_latency_rate", type=float, default=0.0,
                   help="probability of an injected latency spike")
    p.add_argument("--chaos_sites", type=str, default="",
                   help=f"comma list of sites to inject at (default all): "
                        f"{','.join(FAULT_SITES)}")
    p.add_argument("--chaos_max_faults", type=int, default=-1,
                   help="total faults injected before the schedule goes "
                        "permanently clean (-1 = unlimited) — models a "
                        "transient outage that ENDS; e.g. replica_kill "
                        "with a budget of 1 kills exactly one replica and "
                        "lets the fleet prove clean failover")
    p.add_argument("--verify_weights", type=_str2bool, default=True,
                   help="checksum-verify every streamed layer against the "
                        "model dir's integrity.json (mismatches re-read to "
                        "heal page-cache corruption; persistent corruption "
                        "raises a typed ShardCorruptError). The crc pass is "
                        "amortized: each file generation is hashed once and "
                        "later sweeps reuse the cached verdict. false skips "
                        "it entirely on a trusted medium")
    p.add_argument("--host_cache_gb", type=_float_or_auto, default=None,
                   help="host-resident shard cache budget in GB: warm "
                        "sweeps (serving, multi-token decode, multi-batch "
                        "runs) skip disk read+parse+checksum and go "
                        "straight to device_put. 'auto' (default) = a "
                        "fraction of free RAM (off under --chaos); 0 = off")
    p.add_argument("--hbm_pin_gb", type=_float_or_auto, default=None,
                   help="device residency tier budget in GB: pin the "
                        "hottest layers (embedding, lm_head, norms, then "
                        "as many transformer blocks as fit) permanently in "
                        "HBM and stream only the rest — every sweep's "
                        "host->HBM traffic drops by "
                        "exactly the pinned bytes, outputs token-identical; "
                        "the first sweep streams everything and keeps what "
                        "it placed of the planned layers. 'auto' (default) "
                        "= measured free HBM minus a headroom: 35%% of the "
                        "chip, or what the weight stream holds in flight "
                        "((prefetch depth + 2) shards) plus 5%% if that is "
                        "more; a model that fits becomes resident whole "
                        "(off under --chaos and on the CPU backend); "
                        "0 = off")
    p.add_argument("--kv_page_tokens", type=int, default=16,
                   help="rows per paged prefix-KV page (runtime/kvpool.py) "
                        "— the cross-wave sharing granularity; <= 0 "
                        "disables the pool")
    p.add_argument("--kv_pool_gb", type=_float_or_auto, default=None,
                   help="host-RAM budget in GB for resident prefix-KV "
                        "pages: a recurring prefix prefills once per "
                        "PROCESS and later same-prefix waves reuse its "
                        "pages (refcounted, copy-on-write). 'auto' "
                        "(default) = a small slice of free RAM (stays on "
                        "under --chaos: spill reads are chaos sites); "
                        "0 = off")
    p.add_argument("--kv_host_spill", type=_str2bool, default=True,
                   help="true (default): cold prefix-KV pages spill to "
                        "checksummed disk files that heal on read "
                        "(re-read + .crc sidecars, typed SpillCorruptError "
                        "when corruption persists); false: drop them and "
                        "re-prefill on next use")
    p.add_argument("--readahead_threads", type=int, default=2,
                   help="threads in the loader's page-cache readahead pool "
                        "(posix_fadvise issuers, ~zero CPU each)")
    p.add_argument("--score_sink_max_device", type=int, default=16,
                   help="max head-stage score slices kept device-resident "
                        "before older ones resolve to host numpy (bigger = "
                        "fewer host syncs on big batches, more HBM)")


def _add_adapter_flags(p: argparse.ArgumentParser) -> None:
    """Shared by the batch and serve parsers: multi-tenant LoRA adapter
    serving (adapters/; docs/adapters.md has the registry layout, the
    apply math, and the one-base-stream accounting)."""
    p.add_argument("--adapter_dir", type=str, default="",
                   help="registry root of named LoRA adapters — one "
                        "subdir per adapter holding per-layer delta "
                        "safetensors + adapter_plan.json + an integrity "
                        "manifest (build one from a HF PEFT checkpoint "
                        "with `prepare-adapter`). Requests carrying an "
                        "adapter_id decode under that adapter's low-rank "
                        "delta INSIDE the shared base-model sweep: N "
                        "tenants ride one base stream for near-zero "
                        "extra link bytes. Empty (default) = adapter "
                        "serving off — adapter_id requests are rejected "
                        "typed and the sweep is byte-identical to a "
                        "build without adapters")
    p.add_argument("--adapter_max_gb", type=_float_or_auto, default=None,
                   help="host-resident adapter-factor LRU budget in GB "
                        "(adapters/loader.py, the delta-weight analog of "
                        "--host_cache_gb): 'auto' (default) = a small "
                        "fraction of free RAM — auto stays ON under "
                        "--chaos, unlike the shard cache, because the "
                        "delta reads are themselves chaos sites; "
                        "0 = adapter serving off even with --adapter_dir")


def _adapter_config_from_args(args: argparse.Namespace):
    from flexible_llm_sharding_tpu.config import AdapterConfig

    return AdapterConfig(
        dir=args.adapter_dir,
        max_gb=args.adapter_max_gb,
    )


def _add_pressure_flags(p: argparse.ArgumentParser) -> None:
    """Shared by the batch and serve parsers: the resource-pressure
    brownout controller (runtime/pressure.py; docs/pressure.md has the
    ladder stages and recovery semantics)."""
    p.add_argument("--pressure", action="store_true",
                   help="enable the brownout controller: monitor host "
                        "RAM, spill-disk space, HBM headroom, and the "
                        "host->HBM link; under sustained pressure walk a "
                        "reversible degradation ladder (shrink the host "
                        "cache, evict pooled prefix-KV pages, evict "
                        "residency pins, shed admissions with typed "
                        "Overloaded rejections, drain fleet "
                        "replicas) instead of dying — and step back down "
                        "when pressure lifts. Off = zero overhead")
    p.add_argument("--pressure_poll_s", type=float, default=1.0,
                   help="pressure-monitor sampling interval (seconds)")
    p.add_argument("--pressure_host_min_gb", type=float, default=1.0,
                   help="MemAvailable floor in GB; below it the ladder "
                        "steps up (0 = host signal off)")
    p.add_argument("--pressure_disk_min_gb", type=float, default=1.0,
                   help="spill-disk (--disk_folder filesystem) free-bytes "
                        "floor in GB (0 = disk signal off)")
    p.add_argument("--pressure_hbm_headroom_frac", type=float, default=0.05,
                   help="device free/limit HBM headroom floor (0 = off)")
    p.add_argument("--pressure_link_min_gbps", type=float, default=0.0,
                   help="host->HBM streamed-bytes rate floor in GB/s "
                        "while streaming (0 = link signal off)")
    p.add_argument("--pressure_cache_shrink_frac", type=float, default=0.5,
                   help="ladder level 1: host shard cache budget "
                        "multiplier (LRU-evicts down to this fraction)")
    p.add_argument("--pressure_shed_retry_after_s", type=float, default=1.0,
                   help="retry-after hint carried by Overloaded "
                        "rejections while shedding (ladder level 3)")
    p.add_argument("--pressure_step_down_polls", type=int, default=3,
                   help="consecutive clean polls required per ladder "
                        "step DOWN (hysteresis against flapping)")


def _add_observability_flags(p: argparse.ArgumentParser) -> None:
    """Shared by the batch and serve parsers: sweep-timeline tracing
    (obs/trace.py; docs/observability.md has the span model and the
    Perfetto how-to)."""
    p.add_argument("--trace", action="store_true",
                   help="record the sweep timeline (shard loads, device "
                        "puts, compute, source waits, cache hits, pin "
                        "loads, retry/heal events, serve wave lifecycle) "
                        "into a bounded ring, exported at run end to "
                        "--trace_out; analyze with `trace-report` or load "
                        "in Perfetto. Off = zero overhead")
    p.add_argument("--trace_out", type=str, default="",
                   help="trace export path (default fls_trace.json): "
                        "Chrome trace-event JSON, or JSONL when the path "
                        "ends in .jsonl")
    # Black-box flight recorder (obs/events.py + obs/incident.py;
    # docs/incidents.md).
    p.add_argument("--journal_dir", type=str, default="",
                   help="durable append-only JSONL event journal: every "
                        "failure-path event (engine recoveries, wave "
                        "aborts, replica death/drain/redispatch, "
                        "quarantines, heals, pressure steps, watchdog "
                        "stalls, preemptions, SLO budget exhaustion) is "
                        "written here with monotonic seq + correlation "
                        "ids, surviving the process that emitted it. "
                        "Rotates atomically at --journal_max_mb; a write "
                        "failure degrades to a counted drop "
                        "(fls_journal_events_dropped), never an error. "
                        "Empty = off (zero overhead)")
    p.add_argument("--journal_max_mb", type=float, default=16.0,
                   help="journal rotation size in MB (one previous "
                        "generation is kept)")
    p.add_argument("--incidents_dir", type=str, default="",
                   help="arm the incident recorder: a journal event at "
                        "(or above) --incident_trigger severity captures "
                        "a self-contained bundle dir here — journal "
                        "tail, full metrics snapshot, trace ring as "
                        "Chrome trace JSON, resolved config, manifest — "
                        "debounced so a failure storm yields ONE bundle. "
                        "Disk-budgeted (--incidents_max_mb), oldest "
                        "bundle evicted first. Inspect with `cli "
                        "incidents list/show/analyze`. Empty = off")
    p.add_argument("--incidents_max_mb", type=float, default=256.0,
                   help="incidents dir disk budget in MB (oldest bundles "
                        "evicted; the newest always survives)")
    p.add_argument("--incident_trigger", type=str, default="error",
                   choices=("info", "warning", "error", "critical"),
                   help="minimum journal-event severity that captures an "
                        "incident bundle")
    p.add_argument("--incident_debounce_s", type=float, default=60.0,
                   help="after a capture, trigger events within this "
                        "window only count (fls_journal_debounces) — a "
                        "failure storm yields one bundle, not hundreds")
    p.add_argument("--incident_settle_s", type=float, default=1.0,
                   help="capture settles this long after the trigger "
                        "(extended while trigger-severity events keep "
                        "landing, bounded) so the whole storm — replica "
                        "death, re-dispatch, recycle — lands inside the "
                        "bundle's journal tail; 0 = capture immediately")


def _add_sched_flags(p: argparse.ArgumentParser) -> None:
    """Serve parser only: the multi-tenant sweep scheduler
    (serve/sched/; docs/scheduling.md has the class semantics, fairness
    math, preemption state machine, and coalescing contract)."""
    p.add_argument("--sched", action="store_true",
                   help="enable the multi-tenant sweep scheduler: strict "
                        "SLO-class priority (interactive > standard > "
                        "best_effort) with deficit-weighted round-robin "
                        "across tenants inside a class, per-tenant token-"
                        "bucket rate limits (typed RateLimited with a "
                        "retry-after hint), sweep-boundary preemption of "
                        "best-effort waves by waiting interactive work "
                        "(resumed token-identically), and same-prefix "
                        "request coalescing into one shared prefill. "
                        "Off = the plain FIFO admission path")
    p.add_argument("--sched_interactive_deadline_s", type=float, default=0.0,
                   help="default admission deadline for interactive "
                        "requests that name none (0 = fall back to "
                        "--deadline_s)")
    p.add_argument("--sched_standard_deadline_s", type=float, default=0.0,
                   help="default admission deadline for standard requests "
                        "(0 = fall back to --deadline_s)")
    p.add_argument("--sched_best_effort_deadline_s", type=float, default=0.0,
                   help="default admission deadline for best_effort "
                        "requests (0 = fall back to --deadline_s)")
    p.add_argument("--sched_tenant_weights", type=str, default="",
                   help="deficit-round-robin weights, 'tenantA=4,tenantB=1' "
                        "(unlisted tenants weigh 1): a weight-w tenant "
                        "gets ~w shares of each class's admission budget "
                        "while backlogged")
    p.add_argument("--sched_tenant_limits", type=str, default="",
                   help="token-bucket rate limits in requests/second, "
                        "'tenantA=5' (unlisted = unlimited); over-limit "
                        "submits resolve as typed RateLimited carrying "
                        "retry_after_s")
    p.add_argument("--sched_tenant_burst", type=float, default=4.0,
                   help="token-bucket capacity (burst requests) for every "
                        "rate-limited tenant")
    p.add_argument("--sched_preempt", type=_str2bool, default=True,
                   help="allow a waiting interactive request to retire the "
                        "youngest best-effort wave at a shard-0 boundary "
                        "(never mid-sweep); the preempted requests resume "
                        "token-identically with their generated-so-far "
                        "tokens folded into the prefill")
    p.add_argument("--sched_coalesce", type=_str2bool, default=True,
                   help="merge same-tokenized-prefix requests admitted at "
                        "one boundary into a single wave entry that "
                        "prefills the shared prefix KV once")
    p.add_argument("--sched_interactive_phase_boost", type=float, default=2.0,
                   help="fleet routing: multiply the router's phase weight "
                        "by this for interactive requests, so they land "
                        "on the replica nearest its next shard-0 "
                        "admission point (1 = no boost)")


def _add_slo_flags(p: argparse.ArgumentParser) -> None:
    """Serve parser only: SLO targets + error budgets (obs/slo.py;
    docs/incidents.md has the budget math)."""
    p.add_argument("--slo", action="store_true",
                   help="enable SLO error-budget tracking over the "
                        "per-class latency streams: per-class p95 TTFT "
                        "targets, an aggregate per-token-latency target, "
                        "and an availability target export fls_slo_* "
                        "burn-rate/remaining-budget gauges, and a class "
                        "that exhausts its budget emits an "
                        "slo_budget_exhausted journal event (capturing "
                        "an incident bundle when the recorder is armed). "
                        "Off = the per-class exports carry no contract")
    p.add_argument("--slo_ttft_p95_s", type=str, default="",
                   help="per-class p95 TTFT targets in seconds, "
                        "'interactive=0.5,standard=2.0' (unlisted "
                        "classes carry no target)")
    p.add_argument("--slo_token_latency_p95_s", type=float, default=0.0,
                   help="aggregate per-token decode-latency p95 target "
                        "in seconds (0 = off)")
    p.add_argument("--slo_availability_target", type=float, default=0.0,
                   help="fraction of requests that must complete, e.g. "
                        "0.999 — failures burn the 1-target budget "
                        "(0 = off)")
    p.add_argument("--slo_min_samples", type=int, default=20,
                   help="budgets are not judged below this many samples "
                        "(a single slow first request must not page)")


def _slo_config_from_args(args: argparse.Namespace):
    from flexible_llm_sharding_tpu.config import SLOConfig

    if not args.slo:
        return SLOConfig()
    return SLOConfig(
        enabled=True,
        ttft_p95_s=args.slo_ttft_p95_s,
        token_latency_p95_s=args.slo_token_latency_p95_s,
        availability_target=args.slo_availability_target,
        min_samples=args.slo_min_samples,
    )


def _add_autoscale_flags(p: argparse.ArgumentParser) -> None:
    """Serve parser only: closed-loop fleet elasticity + sweep-phase
    stagger (serve/autoscale.py; docs/autoscale.md)."""
    p.add_argument("--autoscale", action="store_true",
                   help="enable the fleet autoscaler: a control loop "
                        "polls SLO burn rate, queue depth, and the "
                        "brownout pressure level and grows/drains the "
                        "replica fleet between --autoscale_min/max with "
                        "anti-flap hysteresis (consecutive-poll "
                        "confirmation, per-direction cooldowns) and hard "
                        "interlocks (never grow at shed-or-above "
                        "pressure; never shrink below min or over an "
                        "in-flight drain; WAL replay completes first). "
                        "Also engages the sweep-phase stagger controller "
                        "(replica offsets held at i/N so worst-case "
                        "admission wait is sweep/N). Off = static fleet, "
                        "free-drifting phases")
    p.add_argument("--autoscale_min", type=int, default=1,
                   help="fleet size floor the controller may drain to")
    p.add_argument("--autoscale_max", type=int, default=4,
                   help="fleet size ceiling the controller may grow to")
    p.add_argument("--autoscale_poll_s", type=float, default=1.0,
                   help="controller poll interval in seconds (decisions "
                        "at most once per poll)")
    p.add_argument("--autoscale_grow_burn_rate", type=float, default=1.0,
                   help="grow when the worst per-class SLO burn rate "
                        "sustains at or above this (1.0 = spending the "
                        "entire error budget)")
    p.add_argument("--autoscale_grow_queue_frac", type=float, default=0.75,
                   help="grow when queue depth / capacity sustains at or "
                        "above this fraction")
    p.add_argument("--autoscale_shrink_burn_rate", type=float, default=0.25,
                   help="shrink only when burn rate AND queue fraction "
                        "are both below their shrink thresholds "
                        "(hysteresis: must be <= the grow threshold)")
    p.add_argument("--autoscale_shrink_queue_frac", type=float, default=0.10,
                   help="queue-fraction half of the shrink band "
                        "(must be <= the grow fraction)")
    p.add_argument("--autoscale_confirm_polls", type=int, default=3,
                   help="a breach must persist this many CONSECUTIVE "
                        "polls before the controller acts — one spiky "
                        "sample never scales the fleet")
    p.add_argument("--autoscale_grow_cooldown_s", type=float, default=10.0,
                   help="after any scale action, grow again only after "
                        "this many seconds")
    p.add_argument("--autoscale_shrink_cooldown_s", type=float, default=30.0,
                   help="after any scale action, shrink only after this "
                        "many seconds (longer than grow by default: "
                        "capacity is cheap to hold, expensive to miss)")
    p.add_argument("--autoscale_dry_run", action="store_true",
                   help="journal every decision (autoscale_* events with "
                        "dry_run=true) without acting — shadow-mode "
                        "rehearsal before trusting the loop")
    p.add_argument("--autoscale_no_stagger", action="store_true",
                   help="disable the sweep-phase stagger controller "
                        "(replica offsets drift free again)")
    p.add_argument("--autoscale_stagger_tolerance", type=float,
                   default=0.15,
                   help="normalized stagger error at or under this "
                        "counts as converged (0 = perfect i/N spread, "
                        "1 = all replicas in phase)")
    p.add_argument("--autoscale_stagger_hold_max_frac", type=float,
                   default=0.5,
                   help="cap on a single boundary hold as a fraction of "
                        "one measured sweep wall")


def _autoscale_config_from_args(args: argparse.Namespace):
    from flexible_llm_sharding_tpu.config import AutoscaleConfig

    if not args.autoscale:
        return AutoscaleConfig()
    return AutoscaleConfig(
        enabled=True,
        min=args.autoscale_min,
        max=args.autoscale_max,
        poll_s=args.autoscale_poll_s,
        grow_burn_rate=args.autoscale_grow_burn_rate,
        grow_queue_frac=args.autoscale_grow_queue_frac,
        shrink_burn_rate=args.autoscale_shrink_burn_rate,
        shrink_queue_frac=args.autoscale_shrink_queue_frac,
        confirm_polls=args.autoscale_confirm_polls,
        grow_cooldown_s=args.autoscale_grow_cooldown_s,
        shrink_cooldown_s=args.autoscale_shrink_cooldown_s,
        dry_run=args.autoscale_dry_run,
        stagger=not args.autoscale_no_stagger,
        stagger_tolerance=args.autoscale_stagger_tolerance,
        stagger_hold_max_frac=args.autoscale_stagger_hold_max_frac,
    )


def _serve_wants_fleet(serve_cfg) -> bool:
    """True when serve must run the replica fleet instead of a single
    engine: more than one replica, or elasticity requested. The
    autoscaler lives in ReplicaFleet, and "start at one replica, grow
    under load" is the canonical elastic config — gating on the replica
    count alone would silently disable ``--autoscale`` exactly there."""
    return serve_cfg.replicas > 1 or serve_cfg.autoscale.enabled


def _sched_config_from_args(args: argparse.Namespace):
    from flexible_llm_sharding_tpu.config import SchedConfig

    if not args.sched:
        return SchedConfig()
    return SchedConfig(
        enabled=True,
        interactive_deadline_s=args.sched_interactive_deadline_s,
        standard_deadline_s=args.sched_standard_deadline_s,
        best_effort_deadline_s=args.sched_best_effort_deadline_s,
        tenant_weights=args.sched_tenant_weights,
        tenant_limits=args.sched_tenant_limits,
        tenant_burst=args.sched_tenant_burst,
        preempt=args.sched_preempt,
        coalesce=args.sched_coalesce,
        interactive_phase_boost=args.sched_interactive_phase_boost,
    )


def _pressure_config_from_args(args: argparse.Namespace) -> PressureConfig:
    if not args.pressure:
        return PressureConfig()
    return PressureConfig(
        enabled=True,
        poll_s=args.pressure_poll_s,
        host_min_gb=args.pressure_host_min_gb,
        disk_min_gb=args.pressure_disk_min_gb,
        hbm_headroom_frac=args.pressure_hbm_headroom_frac,
        link_min_gbps=args.pressure_link_min_gbps,
        cache_shrink_frac=args.pressure_cache_shrink_frac,
        shed_retry_after_s=args.pressure_shed_retry_after_s,
        step_down_polls=args.pressure_step_down_polls,
    )


def _fault_config_from_args(args: argparse.Namespace) -> FaultConfig:
    if not args.chaos:
        return FaultConfig()
    return FaultConfig(
        enabled=True,
        seed=args.chaos_seed,
        error_rate=args.chaos_error_rate,
        truncate_rate=args.chaos_truncate_rate,
        latency_rate=args.chaos_latency_rate,
        sites=tuple(s for s in args.chaos_sites.split(",") if s),
        max_faults=args.chaos_max_faults,
    )


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="flexible-llm-sharding-tpu",
        description="Layer-streaming LLM scorer/generator for TPU",
    )
    p.add_argument("--model_path", type=str, default="./")
    p.add_argument("--prompt_pickle", type=str, required=True,
                   help="Path to the input prompt pickle file")
    p.add_argument("--output_file", type=str, required=True,
                   help="Path to the LLM output scores file")
    p.add_argument("--num_batch", type=int, default=1)
    p.add_argument("--layer_num_per_shard", type=int, default=1)
    p.add_argument("--storage_location", type=str, default=None,
                   help="'tpu' (HBM), 'cpu' (host RAM), or 'disk'; 'gpu' = alias of 'tpu'. "
                        "Not set: a scoring pass keeps the activations that fit the "
                        "chip's free memory in HBM and sends the rest the 'cpu' way; "
                        "the pipeline and KV decode read it as 'cpu'")
    p.add_argument("--max_activation_in_cpu", type=int, default=100)
    p.add_argument("--data_parallel", type=_str2bool, default=False,
                   help="True: split prompts across chips; False: interleaved layer pipeline across chips")
    p.add_argument("--disk_folder", type=str, default="./temp")
    p.add_argument("--num_gen_token", type=int, default=1,
                   help="how many new tokens to be generated")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy (reference behaviour); >0 samples p^(1/T)")
    p.add_argument("--top_k", type=int, default=0,
                   help="sampling: keep only the k most probable tokens (0 = off)")
    p.add_argument("--top_p", type=float, default=0.0,
                   help="sampling: nucleus truncation at cumulative mass p (0 = off)")
    p.add_argument("--seed", type=int, default=0,
                   help="sampling rng seed (temperature > 0)")
    p.add_argument("--kv_cache", type=_str2bool, default=False,
                   help="fast generation: reuse per-layer KV across tokens "
                        "(token-id append semantics; greedy or sampled)")
    p.add_argument("--decode_resident", type=str, default="auto",
                   choices=("auto", "on", "off"),
                   help="kv_cache mode: keep streamed weights on chip after "
                        "prefill when they fit (auto = judge against the "
                        "chip's HBM), so decode steps move zero weight bytes")
    p.add_argument("--decode_fused", type=str, default="auto",
                   choices=("auto", "on", "off"),
                   help="resident kv_cache mode: run ALL greedy decode steps "
                        "as one compiled program per block (on-device argmax, "
                        "zero per-token host round-trips); 'on' errors if the "
                        "preconditions don't hold")
    p.add_argument("--speculative_k", type=int, default=0,
                   help="kv_cache mode: verify this many prompt-lookup "
                        "drafted tokens per streamed pass (greedy-exact; "
                        "divides weight streams per token by the acceptance "
                        "factor when the model must re-stream); 0 = off")
    # --- TPU-specific ---
    p.add_argument("--dtype", type=str, default="bfloat16",
                   choices=["bfloat16", "float16", "float32"])
    p.add_argument("--block_size", type=int, default=8)
    p.add_argument("--prefetch_depth", type=int, default=None,
                   help="shards uploaded ahead of compute; default auto "
                        "(2 on TPU, 0 on the CPU backend where there is no "
                        "host->device link to overlap); 0 = serialized")
    p.add_argument("--num_devices", type=int, default=0, help="0 = all visible chips")
    p.add_argument("--bucket_multiple", type=int, default=64,
                   help="sequence lengths padded up to a multiple of this "
                        "(fewer jit shapes; more padding)")
    p.add_argument("--tensor_parallel", type=int, default=1,
                   help="shard every streamed layer's matmuls over this many "
                        "chips (Megatron layout over ICI); cuts per-chip "
                        "weight HBM by the factor. 1 = off")
    p.add_argument("--max_token_len", type=int, default=DEFAULT_MAX_TOKEN_LEN)
    p.add_argument("--use_pallas", type=_str2bool_or_auto, default=None,
                   help="Pallas flash-attention kernels: true/false, or "
                        "'auto' (default: on when running on a real TPU; "
                        "off elsewhere, where they would run interpreted)")
    p.add_argument("--verbose_metrics", type=_str2bool, default=False,
                   help="emit one JSON line per structured timing event")
    p.add_argument("--profile_dir", type=str, default="",
                   help="write a jax.profiler (Perfetto/XProf) trace here")
    p.add_argument("--resume", type=_str2bool, default=False,
                   help="disk mode: resume a crashed run from the last "
                        "completed shard (single-device/DP) or pipeline "
                        "stage (MP)")
    p.add_argument("--long_context", type=_str2bool, default=False,
                   help="score prefixes longer than max_token_len exactly "
                        "via sequence parallelism (cap becomes "
                        "n_chips * max_token_len) instead of truncating")
    p.add_argument("--coordinator_address", type=str, default=None,
                   help="multi-host (DCN) cluster coordinator, host:port; "
                        "omit for single-host")
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    p.add_argument("--metrics_out", type=str, default="",
                   help="write a one-shot JSON dump of the metrics "
                        "registry (executor/stream/cache/residency/"
                        "integrity counters — the machine-readable form "
                        "of the final stats line) to this path at run end")
    _add_robustness_flags(p)
    _add_adapter_flags(p)
    _add_pressure_flags(p)
    _add_observability_flags(p)
    return p


def config_from_args(args: argparse.Namespace) -> FrameworkConfig:
    return FrameworkConfig(
        model_path=args.model_path,
        num_batch=args.num_batch,
        layer_num_per_shard=args.layer_num_per_shard,
        storage_location=args.storage_location,
        max_activation_in_cpu=args.max_activation_in_cpu,
        data_parallel=args.data_parallel,
        disk_folder=args.disk_folder,
        num_gen_token=args.num_gen_token,
        max_token_len=args.max_token_len,
        dtype=args.dtype,
        block_size=args.block_size,
        prefetch_depth=args.prefetch_depth,
        num_devices=args.num_devices,
        bucket_multiple=args.bucket_multiple,
        tensor_parallel=args.tensor_parallel,
        use_pallas=args.use_pallas,
        verbose_metrics=args.verbose_metrics,
        profile_dir=args.profile_dir,
        resume=args.resume,
        long_context=args.long_context,
        decode_resident=args.decode_resident,
        decode_fused=args.decode_fused,
        speculative_k=args.speculative_k,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        seed=args.seed,
        io_retry_attempts=args.io_retry_attempts,
        io_retry_base_s=args.io_retry_base_s,
        io_retry_deadline_s=args.io_retry_deadline_s,
        verify_weights=args.verify_weights,
        host_cache_gb=args.host_cache_gb,
        kv_page_tokens=args.kv_page_tokens,
        kv_pool_gb=args.kv_pool_gb,
        kv_host_spill=args.kv_host_spill,
        hbm_pin_gb=args.hbm_pin_gb,
        readahead_threads=args.readahead_threads,
        score_sink_max_device=args.score_sink_max_device,
        trace=args.trace,
        trace_out=args.trace_out,
        journal_dir=args.journal_dir,
        journal_max_mb=args.journal_max_mb,
        incidents_dir=args.incidents_dir,
        incidents_max_mb=args.incidents_max_mb,
        incident_trigger=args.incident_trigger,
        incident_debounce_s=args.incident_debounce_s,
        incident_settle_s=args.incident_settle_s,
        faults=_fault_config_from_args(args),
        pressure=_pressure_config_from_args(args),
        adapters=_adapter_config_from_args(args),
    )


def _updated_path(p: str, rank: int | None = None) -> str:
    # Robust form of the reference's .replace('.pkl', '_updated.pkl')
    # contract (/root/reference/main.py:92-94): only the extension is
    # rewritten, so an input without '.pkl' is never silently clobbered.
    root, ext = os.path.splitext(p)
    tag = "_updated" if rank is None else f"_updated.rank{rank}"
    return f"{root}{tag}{ext or '.pkl'}"


def build_serve_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="flexible-llm-sharding-tpu serve",
        description="Online serving: shard-aware continuous batching over "
        "the streaming runtime. Requests join at shard-0 boundaries of the "
        "decode sweep; in-flight requests are never re-prefilled.",
    )
    p.add_argument("--model_path", type=str, default="./")
    # Runtime knobs shared with the offline CLI.
    p.add_argument("--dtype", type=str, default="bfloat16",
                   choices=["bfloat16", "float16", "float32"])
    p.add_argument("--layer_num_per_shard", type=int, default=1)
    p.add_argument("--storage_location", type=str, default=None,
                   help="'tpu' parks per-wave KV in HBM; 'cpu' (and not set) in host RAM")
    p.add_argument("--block_size", type=int, default=8)
    p.add_argument("--bucket_multiple", type=int, default=64)
    p.add_argument("--prefetch_depth", type=int, default=None)
    p.add_argument("--max_token_len", type=int, default=DEFAULT_MAX_TOKEN_LEN)
    p.add_argument("--use_pallas", type=_str2bool_or_auto, default=None)
    p.add_argument("--decode_resident", type=str, default="auto",
                   choices=("auto", "on", "off"),
                   help="keep the model on chip across sweeps when it fits "
                        "(auto judges against the chip's HBM); off "
                        "re-streams the weights every sweep (the large-"
                        "model regime)")
    # Serving knobs (ServeConfig).
    p.add_argument("--queue_capacity", type=int, default=64,
                   help="admission queue bound; submissions beyond it are "
                        "rejected with a reason (backpressure)")
    p.add_argument("--max_wave_requests", type=int, default=8,
                   help="requests coalesced into one wave at a shard-0 "
                        "boundary (the prefill batch size)")
    p.add_argument("--max_active_requests", type=int, default=32,
                   help="total in-flight requests across all waves")
    p.add_argument("--max_new_tokens", type=int, default=16,
                   help="per-request generation budget (requests may "
                        "carry their own in jsonl mode)")
    p.add_argument("--deadline_s", type=float, default=0.0,
                   help="queue-wait deadline: a request not admitted "
                        "within this many seconds is evicted as expired "
                        "(0 = none)")
    p.add_argument("--stats_interval_s", type=float, default=10.0,
                   help="periodic structured serve-stats JSON line on "
                        "stderr (0 = off)")
    p.add_argument("--watchdog_abort_s", type=float, default=600.0,
                   help="streamed-weights mode: abort and recover a sweep "
                        "that makes no shard progress for this long — the "
                        "stalled wave's requests fail with a structured "
                        "error instead of hanging forever (0 = off)")
    p.add_argument("--metrics_port", type=int, default=None,
                   help="serve a Prometheus /metrics endpoint (plus "
                        "/metrics.json) on 127.0.0.1 at this port: queue "
                        "depth, TTFT quantiles, streamed bytes, cache hit "
                        "rate, residency savings, retry/heal/recovery "
                        "counters in one scrape; 0 = ephemeral port, "
                        "omit = off")
    # Replica fleet (serve/fleet.py): N engines behind a shard-phase-aware
    # router with health-driven draining and exactly-once re-dispatch.
    p.add_argument("--replicas", type=int, default=1,
                   help="serving engine replicas (thread-per-engine, one "
                        "process, shared host shard cache). >1 runs the "
                        "replica fleet: requests route to the healthiest "
                        "replica by shard-phase proximity + queue depth, "
                        "and a dead replica's queued/in-flight requests "
                        "re-dispatch to a survivor exactly once, "
                        "token-identically")
    p.add_argument("--router_phase_weight", type=float, default=1.0,
                   help="router score weight on sweep-phase proximity "
                        "(fraction of a sweep until the replica's next "
                        "shard-0 admission point)")
    p.add_argument("--router_depth_weight", type=float, default=1.0,
                   help="router score weight on normalized queue depth "
                        "((queued + active) / max_active_requests)")
    p.add_argument("--router_health_poll_s", type=float, default=0.2,
                   help="fleet health-monitor poll interval: each tick "
                        "reads per-replica registry health + the sweep "
                        "liveness watermark (a busy replica stalled past "
                        "--watchdog_abort_s is hard-failed)")
    p.add_argument("--router_drain_recoveries", type=int, default=0,
                   help="gracefully drain + recycle a replica whose "
                        "engine_recoveries counter reaches this (a flaky-"
                        "but-alive engine); 0 = off")
    p.add_argument("--max_request_tokens", type=int, default=0,
                   help="admission-side request size cap: estimated "
                        "prompt tokens (longest suffix included) + "
                        "max_new_tokens above this are rejected typed "
                        "(RequestTooLarge) at submit, before they can "
                        "join a wave and fail it at allocation; 0 = off")
    p.add_argument("--speculative_k", type=int, default=0,
                   help="speculative decoding on the serving path: each "
                        "in-flight request drafts this many prompt-lookup "
                        "tokens per sweep and the engine verifies all "
                        "drafts batch-wide inside the SAME weight sweep "
                        "(K+1-slot verify pass) — accepted drafts "
                        "multiply tokens-per-sweep at no extra stream "
                        "cost, and output stays token-identical to 0 "
                        "(greedy-exact verification); 0 = off")
    p.add_argument("--draft_model_path", type=str, default="",
                   help="resident draft model (docs/speculative.md): "
                        "checkpoint dir of a SMALL model pinned whole on "
                        "chip through its own residency tier and used as "
                        "the speculative draft source instead of prompt "
                        "lookup — draft decode runs against the pinned "
                        "weights, adding ZERO bytes to the per-sweep "
                        "weight stream; '' = off (prompt-lookup drafts)")
    p.add_argument("--spec_adaptive", action="store_true",
                   help="SLO-aware adaptive draft depth (serve/spec.py): "
                        "per-class k follows windowed live acceptance "
                        "between --spec_k_min and --spec_k_max, funds "
                        "interactive rows first under --spec_draft_budget, "
                        "and backs off to 0 as the brownout ladder's first "
                        "lever; requires --speculative_k >= 1 (starting k)")
    p.add_argument("--spec_k_min", type=int, default=0,
                   help="adaptive-k lower bound (0 lets a class stop "
                        "drafting entirely when drafts keep missing)")
    p.add_argument("--spec_k_max", type=int, default=8,
                   help="adaptive-k upper bound; the verify slot budget is "
                        "provisioned at this k so k can grow mid-wave")
    p.add_argument("--spec_window", type=int, default=8,
                   help="acceptance window: a class's k moves only after "
                        "this many observed drafting passes")
    p.add_argument("--spec_raise_threshold", type=float, default=0.6,
                   help="raise a class's k when its windowed acceptance "
                        "reaches this")
    p.add_argument("--spec_backoff_threshold", type=float, default=0.2,
                   help="shrink a class's k when its windowed acceptance "
                        "falls to this or below")
    p.add_argument("--spec_draft_budget", type=int, default=0,
                   help="per-pass draft-token budget across the wave, "
                        "spent in strict SLO-class priority order "
                        "(interactive first); 0 = unlimited")
    p.add_argument("--wal_dir", type=str, default="",
                   help="crash-safe serving (docs/recovery.md): directory "
                        "for the durable request WAL — every admission, "
                        "sweep-boundary progress mark, and terminal "
                        "outcome is journaled, and on the next start "
                        "every still-open request is replayed "
                        "token-identically before new traffic is "
                        "accepted; empty = WAL off")
    p.add_argument("--wal_fsync", type=str, default="admit",
                   choices=["always", "admit", "never"],
                   help="WAL durability/throughput trade: 'always' fsyncs "
                        "every record, 'admit' (default) fsyncs the "
                        "records that change what a restart owes "
                        "(admissions + terminals) and lets progress marks "
                        "ride the kernel buffers, 'never' leaves all "
                        "durability to the OS (still crash-consistent — "
                        "torn tails truncate, never corrupt)")
    p.add_argument("--wal_max_mb", type=float, default=64.0,
                   help="WAL segment rotation size; sealed segments whose "
                        "every request is terminal are compacted "
                        "(deleted) automatically")
    _add_robustness_flags(p)
    _add_adapter_flags(p)
    _add_pressure_flags(p)
    _add_observability_flags(p)
    _add_sched_flags(p)
    _add_slo_flags(p)
    _add_autoscale_flags(p)
    # Demo driver: submit a prompt pickle at staggered times, write the
    # offline-contract outputs. Without it, requests are read as JSON lines
    # from stdin: {"prefix": ..., "suffixes": [...], "max_new_tokens": N}.
    p.add_argument("--prompt_pickle", type=str, default=None,
                   help="demo mode: submit this offline prompt pickle's "
                        "entries as staggered online requests, then write "
                        "--output_file like the batch path")
    p.add_argument("--output_file", type=str, default=None)
    p.add_argument("--stagger_ms", type=float, default=0.0,
                   help="demo mode: delay between submissions, so late "
                        "arrivals exercise mid-stream wave admission")
    return p


def serve_main(argv: list[str] | None = None, tokenizer=None) -> None:
    args = build_serve_parser().parse_args(argv)
    print(args, file=sys.stderr)
    configure_compile_cache()
    if args.prompt_pickle and not args.output_file:
        raise SystemExit("--prompt_pickle (demo mode) requires --output_file")
    from flexible_llm_sharding_tpu.config import ServeConfig

    cfg = FrameworkConfig(
        model_path=args.model_path,
        layer_num_per_shard=args.layer_num_per_shard,
        storage_location=args.storage_location,
        dtype=args.dtype,
        block_size=args.block_size,
        bucket_multiple=args.bucket_multiple,
        prefetch_depth=args.prefetch_depth,
        max_token_len=args.max_token_len,
        use_pallas=args.use_pallas,
        decode_resident=args.decode_resident,
        io_retry_attempts=args.io_retry_attempts,
        io_retry_base_s=args.io_retry_base_s,
        io_retry_deadline_s=args.io_retry_deadline_s,
        verify_weights=args.verify_weights,
        host_cache_gb=args.host_cache_gb,
        kv_page_tokens=args.kv_page_tokens,
        kv_pool_gb=args.kv_pool_gb,
        kv_host_spill=args.kv_host_spill,
        hbm_pin_gb=args.hbm_pin_gb,
        readahead_threads=args.readahead_threads,
        score_sink_max_device=args.score_sink_max_device,
        trace=args.trace,
        trace_out=args.trace_out,
        journal_dir=args.journal_dir,
        journal_max_mb=args.journal_max_mb,
        incidents_dir=args.incidents_dir,
        incidents_max_mb=args.incidents_max_mb,
        incident_trigger=args.incident_trigger,
        incident_debounce_s=args.incident_debounce_s,
        incident_settle_s=args.incident_settle_s,
        faults=_fault_config_from_args(args),
        pressure=_pressure_config_from_args(args),
        adapters=_adapter_config_from_args(args),
    )
    serve_cfg = ServeConfig(
        queue_capacity=args.queue_capacity,
        max_wave_requests=args.max_wave_requests,
        max_active_requests=args.max_active_requests,
        default_max_new_tokens=args.max_new_tokens,
        default_deadline_s=args.deadline_s,
        stats_interval_s=args.stats_interval_s,
        watchdog_abort_s=args.watchdog_abort_s,
        metrics_port=args.metrics_port,
        replicas=args.replicas,
        router_phase_weight=args.router_phase_weight,
        router_depth_weight=args.router_depth_weight,
        router_health_poll_s=args.router_health_poll_s,
        router_drain_recoveries=args.router_drain_recoveries,
        max_request_tokens=args.max_request_tokens,
        speculative_k=args.speculative_k,
        draft_model_path=args.draft_model_path,
        spec_adaptive=args.spec_adaptive,
        spec_k_min=args.spec_k_min,
        spec_k_max=args.spec_k_max,
        spec_window=args.spec_window,
        spec_raise_threshold=args.spec_raise_threshold,
        spec_backoff_threshold=args.spec_backoff_threshold,
        spec_draft_budget=args.spec_draft_budget,
        wal_dir=args.wal_dir,
        wal_fsync=args.wal_fsync,
        wal_max_mb=args.wal_max_mb,
        sched=_sched_config_from_args(args),
        slo=_slo_config_from_args(args),
        autoscale=_autoscale_config_from_args(args),
    )
    if tokenizer is None:
        from transformers import AutoTokenizer

        tokenizer = AutoTokenizer.from_pretrained(cfg.model_path)
        tokenizer.pad_token = tokenizer.eos_token

    import time

    from flexible_llm_sharding_tpu.serve import ReplicaFleet, ServeEngine

    from flexible_llm_sharding_tpu.serve.request import RequestStatus

    # --replicas > 1 swaps the single engine for the replica fleet
    # (serve/fleet.py) — same submit/drain/shutdown/stats surface, so the
    # demo and jsonl frontends below drive either interchangeably.
    if _serve_wants_fleet(serve_cfg):
        engine = ReplicaFleet(cfg, serve_cfg, tokenizer=tokenizer)
    else:
        engine = ServeEngine(cfg, serve_cfg, tokenizer=tokenizer)
    if engine.metrics_server is not None:
        print(
            f"metrics endpoint: http://{engine.metrics_server.host}:"
            f"{engine.metrics_server.port}/metrics",
            file=sys.stderr,
            flush=True,
        )

    # Crash-safe serving (docs/recovery.md): `wal` is None unless
    # --wal_dir is set. Replay runs at the top of whichever frontend
    # branch executes — every still-open request from the previous boot
    # is re-admitted BEFORE new traffic, so the oldest owed work reaches
    # the scheduler first.
    wal = getattr(engine, "_wal", None)

    def _replay_open(callback=None) -> None:
        if wal is not None:
            from flexible_llm_sharding_tpu.serve import recovery

            summary = recovery.replay(engine, wal, callback=callback)
            print(
                f"wal replay: {summary['replayed']} reopened, "
                f"{summary['skipped_terminal']} already terminal, "
                f"kv restored {summary['kv_restored']} "
                f"(failed {summary['kv_failed']})",
                file=sys.stderr,
                flush=True,
            )
        # Autoscaler interlock: the controller's first scale decision
        # waits until replay has re-admitted the owed work (idempotent;
        # a fleet without a controller no-ops).
        mark = getattr(engine, "mark_replay_complete", None)
        if mark is not None:
            mark()

    import signal as _signal

    def _on_sigterm(signum, frame):
        # Graceful restart contract: stop admission, let the in-flight
        # wave reach its sweep boundary, journal + spill, exit clean.
        # Queued and in-flight requests land back in the WAL and replay
        # on the next start.
        engine.shutdown_for_restart()
        raise SystemExit(143)

    try:
        _signal.signal(_signal.SIGTERM, _on_sigterm)
    except ValueError:
        # Embedded call from a non-main thread: signals are unavailable;
        # the host process owns shutdown sequencing.
        pass
    try:
        if args.prompt_pickle:
            _replay_open()
            with open(args.prompt_pickle, "rb") as f:
                prompts = pickle.load(f)
            requests = []
            for prefix, suffixes in prompts:
                # The offline contract is one score per prompt, so the demo
                # submitter BLOCKS on backpressure (retry until a queue
                # slot frees) instead of dropping rejected prompts — a
                # pickle larger than --queue_capacity must still fully
                # serve. An engine-fatal error breaks the retry loop; the
                # root cause surfaces at the gather below.
                while True:
                    req = engine.submit(prefix, tuple(suffixes))
                    if (
                        req.status is not RequestStatus.REJECTED
                        or engine.error is not None
                    ):
                        break
                    time.sleep(0.05)
                requests.append(req)
                if args.stagger_ms:
                    time.sleep(args.stagger_ms / 1000.0)
            results = [r.future.result() for r in requests]
            with open(args.output_file, "wb") as f:
                pickle.dump([r.scores for r in results], f)
            with open(_updated_path(args.prompt_pickle), "wb") as f:
                pickle.dump([r.updated for r in results], f)
        else:
            # JSONL request stream on stdin; one JSON response line per
            # completion on stdout (scores stay server-side — tokens and
            # text travel).
            import threading

            out_lock = threading.Lock()

            def reply(req) -> None:
                try:
                    res = req.future.result(timeout=0)
                    line = {
                        "id": req.request_id,
                        "status": req.status.value,
                        "updated_suffixes": list(res.updated[1]),
                        "tokens": res.tokens.tolist(),
                        "ttft_s": round(res.ttft_s, 4),
                        "latency_s": round(res.latency_s, 4),
                    }
                except Exception as e:  # rejected/expired/failed
                    line = {
                        "id": req.request_id,
                        "status": req.status.value,
                        "error": str(e),
                    }
                # The caller's own id: request_id is per-process, so this
                # is the one identity that survives a restart — a client
                # deduping replayed (re-emitted) results keys on it.
                if req.client_id is not None:
                    line["client_id"] = req.client_id
                with out_lock:
                    print(json.dumps(line), flush=True)

            _replay_open(callback=reply)
            for line_no, raw in enumerate(sys.stdin, 1):
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    d = json.loads(raw)
                    engine.submit(
                        d["prefix"],
                        tuple(d.get("suffixes") or ("",)),
                        max_new_tokens=d.get("max_new_tokens"),
                        deadline_s=d.get("deadline_s"),
                        callback=reply,
                        # Multi-tenant scheduling (serve/sched): an
                        # unknown slo_class raises typed and lands in the
                        # bad-request reply below, never a silent default.
                        slo_class=d.get("slo_class"),
                        tenant_id=d.get("tenant_id"),
                        # Multi-tenant LoRA (adapters/): an unknown or
                        # corrupt adapter fails ONLY this request, typed,
                        # at wave assembly — never the server.
                        adapter_id=d.get("adapter_id"),
                        # WAL identity: the caller's "id" rides into the
                        # admission record so replayed results remain
                        # attributable across restarts.
                        client_id=d.get("id"),
                    )
                except Exception as e:
                    # One malformed line must not take the server down for
                    # every other client: reject-with-reason, keep serving
                    # (backpressure/deadline rejects already flow through
                    # the callback; this covers parse/validation errors).
                    with out_lock:
                        print(
                            json.dumps(
                                {
                                    "line": line_no,
                                    "status": "rejected",
                                    "error": f"bad request line: {e!r}",
                                }
                            ),
                            flush=True,
                        )
    except BaseException as e:
        if engine.error is not None and not isinstance(e, SystemExit):
            # A fatal engine error cancels queued requests, so the gather
            # raises the secondary ServeClosed — name the ROOT cause
            # instead of the symptom.
            raise SystemExit(
                f"serve engine failed: {engine.error!r}"
            ) from e
        raise
    finally:
        engine.shutdown(drain=True)
        # Trace export in the FINALLY: a run that died is exactly the run
        # whose timeline (wave aborts, recoveries, watchdog stalls) the
        # operator needs — exiting through the error paths above without
        # writing it would discard the one diagnostic artifact tracing
        # exists to produce.
        if cfg.trace:
            from flexible_llm_sharding_tpu.obs import trace as obs_trace

            path = obs_trace.write_configured()
            if path:
                print(
                    f"trace written -> {path} (analyze: `trace-report "
                    f"--trace {path}`, or load in Perfetto)",
                    file=sys.stderr,
                )
    if engine.error is not None:
        raise SystemExit(f"serve engine failed: {engine.error!r}")
    print(json.dumps(engine.stats()), file=sys.stderr)


def build_verify_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="flexible-llm-sharding-tpu verify",
        description="Offline integrity audit: recompute every checksum in "
        "a prepared model dir (against integrity.json) and/or a spill dir "
        "(against the per-.npy sidecars). Prints a per-file report and "
        "exits nonzero on any problem — including manifest/dir structural "
        "drift, which the tolerant load path deliberately does not fail on.",
    )
    p.add_argument("--model_path", type=str, default=None,
                   help="prepared per-layer checkpoint dir to audit")
    p.add_argument("--spill_dir", type=str, default=None,
                   help="activation spill dir (--disk_folder of a run) to "
                        "audit")
    p.add_argument("--adapter_dir", type=str, default=None,
                   help="LoRA adapter registry root (the serve flag of "
                        "the same name) to audit: every adapter's delta "
                        "safetensors recomputed against its integrity "
                        "manifest, plan <-> file structural drift "
                        "reported (adapter_mismatch / plan_missing_file "
                        "/ corrupt_plan)")
    p.add_argument("--hbm_pin_gb", type=str, default=None,
                   help="dry-run the device residency planner at this HBM "
                        "budget (GB, or 'auto' for the local chip's "
                        "measured free HBM minus headroom): reports which "
                        "layers the budget would pin and the per-sweep "
                        "stream bytes saved; requires --model_path. Audit "
                        "only — nothing is loaded or pinned")
    p.add_argument("--json", action="store_true",
                   help="emit the full structured report as one JSON object "
                        "on stdout instead of human-readable lines")
    return p


def verify_main(argv: list[str] | None = None) -> None:
    args = build_verify_parser().parse_args(argv)
    if not args.model_path and not args.spill_dir and not args.adapter_dir:
        raise SystemExit(
            "verify: give --model_path, --spill_dir and/or --adapter_dir"
        )
    if args.hbm_pin_gb is not None and not args.model_path:
        raise SystemExit("verify: --hbm_pin_gb requires --model_path")
    from flexible_llm_sharding_tpu.integrity.verify import (
        format_report,
        verify_adapter_dir,
        verify_model_dir,
        verify_spill_dir,
    )

    reports = []
    if args.model_path:
        reports.append(verify_model_dir(args.model_path))
    if args.spill_dir:
        reports.append(verify_spill_dir(args.spill_dir))
    if args.adapter_dir:
        reports.append(verify_adapter_dir(args.adapter_dir))
    residency_plan = None
    if args.hbm_pin_gb is not None:
        from flexible_llm_sharding_tpu.runtime.residency import (
            auto_pin_budget_bytes,
            plan_report,
        )

        if args.hbm_pin_gb.lower() == "auto":
            budget = auto_pin_budget_bytes()
        else:
            try:
                gb = float(args.hbm_pin_gb)
            except ValueError:
                raise SystemExit(
                    "verify: --hbm_pin_gb must be a GB number or 'auto', "
                    f"got {args.hbm_pin_gb!r}"
                )
            if gb < 0:
                raise SystemExit("verify: --hbm_pin_gb must be >= 0")
            budget = int(gb * 1e9)
        residency_plan = plan_report(args.model_path, budget)
    if args.json:
        out = {"reports": reports}
        if residency_plan is not None:
            out["residency_plan"] = residency_plan
        print(json.dumps(out))
    else:
        for r in reports:
            print(format_report(r))
        if residency_plan is not None:
            rp = residency_plan
            print(
                f"residency plan @ {rp['budget_gb']} GB: pins "
                f"{rp['pinned_layers']}/{rp['total_layers']} layers, "
                f"{rp['pinned_bytes'] / 1e9:.3f} GB "
                f"({rp['pinned_fraction']:.1%} of streamed bytes) — saves "
                f"{rp['stream_bytes_saved_per_sweep'] / 1e9:.3f} GB of "
                "host->HBM traffic per sweep"
            )
            for entry in rp["pinned"]:
                print(f"  pin {entry['layer']}  {entry['bytes']} bytes")
    if not all(r["ok"] for r in reports):
        raise SystemExit(2)


def build_plan_precision_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="flexible-llm-sharding-tpu plan-precision",
        description="Mixed-precision calibration (docs/precision.md): "
        "probe per-layer quality sensitivity on a calibration batch "
        "(one-layer-at-a-time quantization vs the bf16 oracle), plan an "
        "int4/int8/bf16 dtype per layer under a bytes-per-sweep budget "
        "OR an end-to-end divergence cap, and emit the serializable "
        "PrecisionPlan (optionally materializing the mixed checkpoint).",
    )
    p.add_argument("--model_path", type=str, required=True,
                   help="FLOAT native per-layer checkpoint dir (the "
                        "original precision — quantized dirs are "
                        "rejected, requantize_native's rule)")
    p.add_argument("--calib_pickle", type=str, required=True,
                   help="calibration prompts pickle, the batch CLI's "
                        "[(prefix, (suffixes...)), ...] format")
    p.add_argument("--calib_limit", type=int, default=8,
                   help="use at most this many calibration prompts (the "
                        "probe runs one forward per layer per candidate "
                        "dtype per row)")
    p.add_argument("--bytes_budget_gb", type=float, default=None,
                   help="plan mode 1: fit the sweep under this many GB "
                        "of streamed weight bytes, minimizing divergence")
    p.add_argument("--divergence_cap", type=float, default=None,
                   help="plan mode 2: minimize streamed bytes subject to "
                        "this cap on calibration next-token KL vs the "
                        "bf16 oracle")
    p.add_argument("--out", type=str, default=None,
                   help="write the plan JSON here (default: print only)")
    p.add_argument("--apply", type=str, default=None,
                   help="also materialize the mixed checkpoint into this "
                        "dir (requantize_native(plan=...); embeds the "
                        "plan + per-layer dtype manifest)")
    p.add_argument("--json", action="store_true",
                   help="emit the plan as JSON on stdout")
    return p


def plan_precision_main(argv: list[str] | None = None, tokenizer=None) -> None:
    args = build_plan_precision_parser().parse_args(argv)
    if (args.bytes_budget_gb is None) == (args.divergence_cap is None):
        raise SystemExit(
            "plan-precision: give exactly one of --bytes_budget_gb / "
            "--divergence_cap"
        )
    from flexible_llm_sharding_tpu.runtime.precisionplan import build_plan
    from flexible_llm_sharding_tpu.utils.checkpoint import requantize_native

    with open(args.calib_pickle, "rb") as f:
        prompts = pickle.load(f)
    prompts = prompts[: max(1, args.calib_limit)]
    if tokenizer is None:
        from transformers import AutoTokenizer

        tokenizer = AutoTokenizer.from_pretrained(args.model_path)
    plan = build_plan(
        args.model_path,
        prompts,
        tokenizer,
        bytes_budget=(
            int(args.bytes_budget_gb * 1e9)
            if args.bytes_budget_gb is not None
            else None
        ),
        divergence_cap=args.divergence_cap,
    )
    if args.out:
        plan.write(args.out)
    if args.json:
        print(json.dumps(plan.to_json()))
    else:
        counts = plan.counts()
        print(
            f"plan: {counts['bf16']} bf16 / {counts['int8']} int8 / "
            f"{counts['int4']} int4 layers — "
            f"{plan.est_bytes / 1e9:.3f} GB/sweep vs "
            f"{plan.baseline_bytes / 1e9:.3f} GB uniform bf16 "
            f"({plan.bytes_saved_frac:.1%} saved); measured divergence "
            f"{plan.measured_divergence:.3e} (declared cap "
            f"{plan.divergence_cap:.3e})"
        )
        for name, dt in plan.layers:
            print(f"  {dt:>5}  {name}")
    if args.apply:
        done = requantize_native(args.model_path, args.apply, plan=plan)
        print(
            f"materialized {len(done)} mixed-precision layers -> "
            f"{args.apply}",
            file=sys.stderr,
        )


def build_prepare_adapter_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="flexible-llm-sharding-tpu prepare-adapter",
        description="Convert a HF PEFT LoRA checkpoint dir "
        "(adapter_config.json + adapter_model.safetensors) into the "
        "serving registry layout under --adapter_dir: per-decoder-layer "
        "delta safetensors, an adapter_plan.json (per-layer ranks, "
        "alpha, hidden size), and an integrity manifest so `verify` can "
        "audit it and corrupt deltas raise typed at serve time. "
        "Per-module lora_alpha/r is pre-folded into the stored B "
        "factors (apply scale exactly 1.0); v1 converts square target "
        "modules only (docs/adapters.md).",
    )
    p.add_argument("--peft_dir", type=str, required=True,
                   help="HF PEFT checkpoint dir to convert (must hold "
                        "adapter_model.safetensors — torch-pickle .bin "
                        "checkpoints are rejected typed)")
    p.add_argument("--adapter_dir", type=str, required=True,
                   help="registry root to write into (the serve flag of "
                        "the same name); the adapter lands at "
                        "<adapter_dir>/<name>")
    p.add_argument("--name", type=str, required=True,
                   help="adapter name — the adapter_id serving requests "
                        "carry")
    p.add_argument("--json", action="store_true",
                   help="emit the written plan as JSON on stdout")
    return p


def prepare_adapter_main(argv: list[str] | None = None) -> None:
    args = build_prepare_adapter_parser().parse_args(argv)
    from flexible_llm_sharding_tpu.adapters.registry import (
        AdapterPlan,
        convert_peft_checkpoint,
    )

    try:
        adir = convert_peft_checkpoint(
            args.peft_dir, args.adapter_dir, args.name
        )
    except ValueError as e:
        raise SystemExit(f"prepare-adapter: {e}")
    plan = AdapterPlan.load(adir)
    if args.json:
        print(json.dumps(plan.to_json()))
    else:
        ranks = plan.ranks
        print(
            f"adapter {plan.name!r} -> {adir}: {len(plan.layers)} layers, "
            f"rank {plan.rank} (alpha {plan.alpha:g}, scale "
            f"{plan.scale:g}), hidden {plan.hidden_size}, "
            f"{plan.nbytes() / 1e6:.2f} MB of deltas"
        )
        for lname, _ in plan.layers:
            print(f"  r={ranks[lname]:<3d} {lname}")
        print(
            f"serve with: --adapter_dir {args.adapter_dir} ; requests "
            f'carry {{"adapter_id": "{plan.name}"}}'
        )


def build_incidents_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="flexible-llm-sharding-tpu incidents",
        description="Inspect flight-recorder incident bundles "
        "(--incidents_dir; docs/incidents.md): list the bundles in a "
        "directory, show one bundle's manifest, or analyze one into a "
        "human timeline (journal events + correlation ids + the "
        "embedded trace's report).",
    )
    p.add_argument("action", choices=("list", "show", "analyze"),
                   help="list bundles in --dir; show one bundle's "
                        "manifest; analyze one bundle into a timeline")
    p.add_argument("bundle", nargs="?", default=None,
                   help="bundle directory (show/analyze)")
    p.add_argument("--dir", type=str, default="incidents",
                   help="incidents directory to list (default: "
                        "./incidents)")
    p.add_argument("--json", action="store_true",
                   help="emit the structured report as JSON on stdout")
    return p


def incidents_main(argv: list[str] | None = None) -> None:
    args = build_incidents_parser().parse_args(argv)
    from flexible_llm_sharding_tpu.obs.report import (
        analyze_bundle,
        format_incident,
        journal_tail_len,
        load_manifest,
    )

    if args.action == "list":
        try:
            names = sorted(os.listdir(args.dir))
        except OSError as e:
            raise SystemExit(f"incidents: cannot list {args.dir}: {e}")
        rows = []
        for name in names:
            path = os.path.join(args.dir, name)
            if not name.startswith("incident-") or not os.path.isdir(path):
                continue
            try:
                # Manifest + tail line count only: listing a full
                # incidents dir must not parse every bundle's multi-MB
                # trace export.
                manifest = load_manifest(path)
            except ValueError:
                continue  # half-written/foreign dir: skip, never crash
            trig = manifest.get("trigger", {})
            rows.append(
                {
                    "bundle": name,
                    "captured_at": manifest.get("captured_at"),
                    "trigger": trig.get("kind"),
                    "severity": trig.get("severity"),
                    "journal_events": journal_tail_len(path),
                }
            )
        if args.json:
            print(json.dumps(rows))
        elif not rows:
            print(f"no incident bundles under {args.dir}")
        else:
            for r in rows:
                print(
                    f"{r['bundle']}  {r['captured_at']}  "
                    f"trigger={r['trigger']} ({r['severity']})  "
                    f"journal_events={r['journal_events']}"
                )
        return None
    if not args.bundle:
        raise SystemExit(f"incidents {args.action}: give a bundle dir")
    try:
        if args.action == "show":
            manifest = load_manifest(args.bundle)
            print(json.dumps(manifest, indent=None if args.json else 1))
            return None
        report = analyze_bundle(args.bundle)
    except ValueError as e:
        raise SystemExit(f"incidents: {e}")
    if args.json:
        print(json.dumps(report))
    else:
        print(format_incident(report))
    return None


def main(argv: list[str] | None = None, tokenizer=None) -> None:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "serve":
        return serve_main(argv[1:], tokenizer=tokenizer)
    if argv and argv[0] == "verify":
        return verify_main(argv[1:])
    if argv and argv[0] == "plan-precision":
        # Mixed-precision calibration planner (docs/precision.md).
        return plan_precision_main(argv[1:], tokenizer=tokenizer)
    if argv and argv[0] == "prepare-adapter":
        # HF PEFT LoRA checkpoint -> serving registry layout
        # (adapters/registry.py, docs/adapters.md).
        return prepare_adapter_main(argv[1:])
    if argv and argv[0] == "check":
        # flscheck: the project-invariant static analyzer (docs/analysis.md).
        from flexible_llm_sharding_tpu.analysis import main as check_main

        rc = check_main(argv[1:])
        if rc:
            raise SystemExit(rc)
        return None
    if argv and argv[0] == "incidents":
        # Flight-recorder bundle inspector (obs/report.py,
        # docs/incidents.md): list / show / analyze.
        return incidents_main(argv[1:])
    if argv and argv[0] == "trace-report":
        # Trace analyzer (obs/report.py): link utilization, overlap
        # efficiency, sweep breakdown, TTFT/token-latency quantiles from
        # a --trace recording.
        from flexible_llm_sharding_tpu.obs.report import main as report_main

        rc = report_main(argv[1:])
        if rc:
            raise SystemExit(rc)
        return None
    args = build_parser().parse_args(argv)
    print(args, file=sys.stderr)
    configure_compile_cache()
    if (args.top_k or args.top_p) and args.temperature <= 0:
        # Friendly form of the FrameworkConfig validation: silent no-op
        # filters would masquerade as sampling.
        raise SystemExit("--top_k/--top_p require --temperature > 0")
    if args.decode_resident == "on" and not args.kv_cache:
        # Same silent-no-op defence: the flag only drives the KV-decode
        # path; without --kv_cache weights would quietly re-stream.
        raise SystemExit("--decode_resident on requires --kv_cache true")
    if args.speculative_k:
        if not args.kv_cache:
            raise SystemExit("--speculative_k requires --kv_cache true")
        if args.data_parallel:
            raise SystemExit(
                "--speculative_k does not compose with --data_parallel "
                "(the broadcast source's round count is fixed up front)"
            )
        if args.long_context:
            raise SystemExit(
                "--speculative_k is not supported with --long_context yet"
            )
    cfg = config_from_args(args)

    if args.coordinator_address is not None:
        from flexible_llm_sharding_tpu.parallel.sharding import initialize_multihost

        idx = initialize_multihost(
            args.coordinator_address, args.num_processes, args.process_id
        )
        print(f"joined cluster as process {idx}", file=sys.stderr)
    elif args.num_processes is not None or args.process_id is not None:
        # Without a coordinator every host would silently run the full
        # workload as process 0 and race on the output files.
        raise SystemExit(
            "--num_processes/--process_id require --coordinator_address"
        )

    if cfg.storage_location == "disk":
        os.makedirs(cfg.disk_folder, exist_ok=True)

    with open(args.prompt_pickle, "rb") as f:
        prompts = pickle.load(f)

    import jax

    if jax.process_count() > 1:
        # Multi-host: each process scores its own contiguous prompt slice
        # (array_split semantics, matching DP) on its LOCAL chips, and writes
        # rank-suffixed output files — otherwise every host would run the
        # full workload and race on the same pickles.
        from flexible_llm_sharding_tpu.parallel.planner import split_prompts_dp

        rank = jax.process_index()
        lo, hi = split_prompts_dp(len(prompts), jax.process_count())[rank]
        prompts = prompts[lo:hi]
        output_file = f"{args.output_file}.rank{rank}"
        updated_file = _updated_path(args.prompt_pickle, rank)
        print(
            f"process {rank}: prompts [{lo}:{hi}) -> {output_file}",
            file=sys.stderr,
        )
    else:
        output_file = args.output_file
        updated_file = _updated_path(args.prompt_pickle)

    from flexible_llm_sharding_tpu.runtime.generation import generation_loop
    from flexible_llm_sharding_tpu.runtime.orchestration import (
        pick_devices,
        run_prompts,
    )

    if tokenizer is None:
        from transformers import AutoTokenizer

        tokenizer = AutoTokenizer.from_pretrained(cfg.model_path)
        tokenizer.pad_token = tokenizer.eos_token

    import time

    from flexible_llm_sharding_tpu.utils.metrics import (
        LiveArrayPeakSampler,
        peak_hbm_gb,
        profiler_trace,
        throughput,
    )

    from flexible_llm_sharding_tpu.runtime.tokenization import count_tokens

    # tokens_processed counts every real prefix/suffix token each full-model
    # pass runs (the reference's stats count only generated tokens, which
    # understates the work by orders of magnitude for scoring workloads).
    tokens_processed = 0

    from flexible_llm_sharding_tpu.runtime.executor import (
        process_streamed_bytes,
        reset_process_streamed_bytes,
    )
    from flexible_llm_sharding_tpu.runtime.orchestration import (
        LAST_DP_RANK_STATS,
    )

    # Fresh per-run accumulators (a library caller may run cli.main twice
    # in one process).
    LAST_DP_RANK_STATS.clear()
    reset_process_streamed_bytes()

    # Brownout controller (--pressure): started HERE for the offline
    # path — the monitor thread, ladder, and fls_pressure_* export are
    # process-wide singletons that serve engines start themselves, but a
    # batch run has no engine, and without this call the flag would
    # parse and thread yet never act (the silent-no-op class KNOB-SYNC
    # can't see because the args ARE read).
    from flexible_llm_sharding_tpu.runtime import pressure as _pressure

    _pressure.controller_for(cfg)
    # Flight recorder (--journal_dir/--incidents_dir): armed here for the
    # offline path — serve engines arm it themselves, but a batch run's
    # failure paths (quarantines, heals, pressure events) must journal
    # and bundle too.
    from flexible_llm_sharding_tpu.obs import incident as _incident

    _incident.ensure_configured(cfg)

    t0 = time.perf_counter()
    # Peak HBM comes from the allocator (memory_stats()) on a TPU; the
    # sampler stands in on the CPU backend only, which reports no stats,
    # and supplies the host anon-RSS peak everywhere.
    hbm_sampler = LiveArrayPeakSampler()
    with profiler_trace(cfg.profile_dir or None), hbm_sampler:
        if args.kv_cache:
            # Sampling composes (cfg carries temperature/top_k/top_p/seed);
            # --long_context composes: run_decode routes over-length
            # prefixes to the sp-mesh LongContextDecoder.
            from flexible_llm_sharding_tpu.runtime.orchestration import run_decode

            # Multi-chip: --data_parallel true splits prompts across chips;
            # default is the interleaved MP pipeline with per-stage KV.
            output_scores, updated, tokens_processed = run_decode(
                cfg, prompts, tokenizer=tokenizer
            )
        else:

            # Long-context mode actually processes prefixes up to
            # n_chips * max_token_len; count with the same cap.
            count_cap = cfg.max_token_len * (
                len(pick_devices(cfg)) if cfg.long_context else 1
            )

            def score_fn(ps):
                nonlocal tokens_processed
                tokens_processed += count_tokens(tokenizer, ps, count_cap)
                return run_prompts(cfg, ps, tokenizer=tokenizer)

            from flexible_llm_sharding_tpu.config import LlamaConfig

            output_scores, updated = generation_loop(
                score_fn,
                prompts,
                cfg.num_gen_token,
                tokenizer,
                temperature=args.temperature,
                seed=args.seed,
                top_k=args.top_k,
                top_p=args.top_p,
                model_cfg=LlamaConfig.from_pretrained(cfg.model_path),
                max_token_len=cfg.max_token_len,
            )
    wall = time.perf_counter() - t0

    # Reference file contract (/root/reference/main.py:92-98).
    with open(updated_file, "wb") as f:
        pickle.dump(updated, f)
    with open(output_file, "wb") as f:
        pickle.dump(output_scores, f)
    # Final stats line — the reference prints its per-device weight-load time
    # here (/root/reference/utils.py:304); ours adds throughput and peak HBM.
    gen_tokens = sum(s.shape[0] for s in output_scores) * cfg.num_gen_token
    stats = {
        "prompts": len(prompts),
        "num_gen_token": cfg.num_gen_token,
        "wall_s": round(wall, 3),
        "generated_tokens": gen_tokens,
        "tokens_processed": tokens_processed,
        **throughput(tokens_processed, wall, chips=len(pick_devices(cfg))),
    }
    peak = peak_hbm_gb()
    if peak is not None:
        stats["peak_hbm_gb"] = round(peak, 3)
        stats["peak_hbm_source"] = "allocator"  # device.memory_stats() peak
    elif hbm_sampler.peak_bytes:
        # CPU backend only: peak_hbm_gb() raises on a TPU without stats.
        stats["peak_hbm_gb"] = round(hbm_sampler.peak_gb, 3)
        stats["peak_hbm_source"] = "live_arrays"  # excludes XLA scratch
        if len(pick_devices(cfg)) > 1:
            # live_arrays sums across every local chip; on multi-chip runs
            # this is the process-wide total, not the per-chip peak.
            stats["peak_hbm_scope"] = "process"
    # Total host shard bytes built for upload this process — for a
    # single-chip stream this is the model bytes that crossed the host->HBM
    # link (x num_batch passes), the scale artifact's "the whole model
    # really streamed through" witness.
    from flexible_llm_sharding_tpu.runtime.residency import process_tier

    tier = process_tier()
    if tier is not None:
        rs = tier.stats()
        # HBM accounting honesty: the pin tier is device-resident for the
        # whole run. The allocator peak already includes it; the
        # live-arrays fallback samples it too, but on a backend where
        # neither produced a figure the tier's own bytes become the floor
        # — the low-memory claim can never silently exclude the pins.
        stats["pinned_bytes"] = int(rs["pinned_bytes"])
        if rs["stream_bytes_saved"]:
            stats["stream_bytes_saved"] = int(rs["stream_bytes_saved"])
        if "peak_hbm_gb" not in stats and rs["pinned_bytes"]:
            # Per-chip figure: the heaviest single placement target, NOT
            # the process-wide sum (a 4-stage pipeline pins on 4 chips;
            # the per-chip peak is one stage's bytes, not all four).
            stats["peak_hbm_gb"] = round(
                tier.max_pinned_device_bytes() / 1e9, 3
            )
            stats["peak_hbm_source"] = "pinned_floor"
    sb = process_streamed_bytes()
    if sb:
        stats["streamed_bytes"] = sb
        # These are HOST shard builds. Single chip: equals host->HBM link
        # traffic. DP broadcast: each host build uploads to every active
        # rank, so link traffic is ~n_ranks x this (the read-once design's
        # point); scope the number so artifacts can't misstate it.
        stats["streamed_bytes_scope"] = "host_loads"
        if cfg.data_parallel and len(pick_devices(cfg)) > 1:
            stats["streamed_bytes_note"] = (
                "broadcast: link traffic ~= n_ranks x host_loads"
            )
    # Host memory: VmHWM (peak RSS — an UPPER bound that includes mmapped
    # checkpoint pages the loader faulted in, so it can approach model size
    # on an unpressured host) plus the sampled peak ANON RSS, the process's
    # own buffers — the honest witness of the streaming host-memory bound.
    from flexible_llm_sharding_tpu.utils.metrics import host_rss_gb

    rss = host_rss_gb()
    if "peak" in rss:
        stats["peak_host_rss_gb"] = round(rss["peak"], 3)
        stats["peak_host_rss_note"] = "includes mmapped checkpoint pages"
    if hbm_sampler.peak_anon_bytes:
        stats["peak_host_anon_gb"] = round(
            hbm_sampler.peak_anon_bytes / 1e9, 3
        )
    if LAST_DP_RANK_STATS:
        stats["dp_ranks"] = {
            str(r): {
                k: int(v) if k == "prompts" else round(v, 3)
                for k, v in s.items()
            }
            for r, s in sorted(LAST_DP_RANK_STATS.items())
        }
    print(json.dumps(stats), file=sys.stderr)
    if args.metrics_out:
        # One-shot machine-readable dump: the metrics registry every
        # subsystem registered into (executor stats, stream counters,
        # host cache, residency tier, tracer) plus the final stats line —
        # the scrapeable form of everything printed above.
        from flexible_llm_sharding_tpu.obs.registry import REGISTRY

        with open(args.metrics_out, "w") as f:
            json.dump({"stats": stats, "metrics": REGISTRY.collect()}, f,
                      indent=1)
        print(f"metrics written -> {args.metrics_out}", file=sys.stderr)
    if cfg.trace:
        from flexible_llm_sharding_tpu.obs import trace as obs_trace

        path = obs_trace.write_configured()
        if path:
            print(
                f"trace written -> {path} (analyze: `trace-report --trace "
                f"{path}`, or load in Perfetto)",
                file=sys.stderr,
            )


if __name__ == "__main__":
    main()
