"""The online serving loop: continuous batching over the streaming runtime.

One worker thread drives an endless sequence of **weight sweeps**. Each
sweep walks the model's shards in order (resident on chip, or re-streamed
through the cycling ``ShardWeightSource``); at every shard, every active
wave advances one shard's worth of work — a freshly admitted wave runs its
PREFILL segments (capturing per-layer KV, ``runtime/decode`` machinery),
in-flight waves run one DECODE step against their cached KV. New waves are
admitted only at the shard-0 boundary (``ShardAwareBatcher``), so a
mid-stream join never re-triggers prefill for in-flight requests: the
late wave's prefill and the old waves' decode ride the *same* sweep.

Per-request results resolve through futures/callbacks the moment the
request's own token budget is reached — requests with different budgets
coexist in one wave. Graceful drain (serve out queued + in-flight, refuse
new) and hard shutdown (cancel queued, finish in-flight) are first-class.

Degrade, don't die (docs/faults.md): an exhausted shard load
(ShardLoadError), a watchdog-aborted stall, or a stray transient OSError
mid-sweep fails ONLY the in-flight waves — each request's future resolves
with a structured WaveAborted carrying the root cause — then the cycling
weight source restarts and the loop keeps serving the queue. Anything
else stays engine-fatal (every future resolves with the root cause and
the loop stops).

Speculative serving (``ServeConfig.speculative_k`` > 0,
docs/speculative.md): each in-flight request carries its own prompt-lookup
draft stream over its accepted context, and every decode sweep becomes ONE
K+1-slot batch verify pass (``runtime/decode.SpecVerifier`` — the same
core the offline scorer uses), emitting 1..K+1 tokens per suffix per
sweep. Per-suffix acceptance differs, so per-suffix KV slot clocks drift
exactly as the offline path handles; output stays greedy-exact
(token-identical to ``speculative_k=0``, which remains the default).

Serving scope (v1, loud rejects): single placement target, greedy
selection (per-request rng streams under sampling are future work), no
long-context routing.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import threading
import time
from itertools import islice
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from flexible_llm_sharding_tpu.adapters import apply as adapter_apply
from flexible_llm_sharding_tpu.adapters.registry import (
    AdapterCorruptError,
    AdapterNotFound,
)
from flexible_llm_sharding_tpu.config import (
    FrameworkConfig,
    LlamaConfig,
    ServeConfig,
)
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.obs import events as obs_events
from flexible_llm_sharding_tpu.obs import incident as obs_incident
from flexible_llm_sharding_tpu.obs import trace as obs_trace
from flexible_llm_sharding_tpu.obs.slo import SLOTracker
from flexible_llm_sharding_tpu.parallel.planner import plan_shards_dp
from flexible_llm_sharding_tpu.integrity.manifest import SpillCorruptError
from flexible_llm_sharding_tpu.runtime import kvpool
from flexible_llm_sharding_tpu.runtime.decode import (
    KVStore,
    SpecVerifier,
    _decode_decoders,
    _decode_norm_head,
    _prefill_decoders,
    _spec_decoders,
    _spec_norm_head,
    _suffix_prefill_decoders,
    draft_contexts,
    extend_gen_kv,
)
from flexible_llm_sharding_tpu.runtime.schedcore import SchedCore
from flexible_llm_sharding_tpu.faults.inject import FaultInjector
from flexible_llm_sharding_tpu.runtime.executor import (
    ShardLoadError,
    ShardWeightSource,
    SourceClosed,
    _DTYPES,
    _embed_block,
    _head_block,
    _norm_block,
    np_dtype_for,
)
from flexible_llm_sharding_tpu.runtime.tokenization import (
    PromptTokenizer,
    check_longrope_regime,
    extend_tokenized,
    longrope_total_len,
    make_blocks,
)
from flexible_llm_sharding_tpu.serve.batcher import ShardAwareBatcher, Wave
from flexible_llm_sharding_tpu.serve.queue import AdmissionQueue
from flexible_llm_sharding_tpu.serve.request import (
    Request,
    RequestStatus,
    RestartPending,
    WaveAborted,
)
from flexible_llm_sharding_tpu.serve.sched import (
    SweepScheduler,
    build_entries,
    class_deadline_s,
    parse_class,
)
from flexible_llm_sharding_tpu.utils import checkpoint
from flexible_llm_sharding_tpu.utils.metrics import ServingMetrics, StepWatchdog


@dataclasses.dataclass
class _WaveState:
    """Engine-private compute state for one wave (same structures as the
    offline DecodeGenerator run, scoped to the wave's requests)."""

    toks: list
    blocks: list[list[int]]
    meta: dict[int, tuple]
    kv_store: KVStore
    scores: dict[int, list[np.ndarray]]
    tok_hist: dict[int, list[np.ndarray]]
    loc: dict[int, tuple[int, int]]  # wave-entry index -> (block, row)
    slots: int
    norm_p: Any = None  # per-sweep: norm params ride shard->head shard
    # Speculative serving (ServeConfig.speculative_k > 0 and the wave
    # decodes at all): one SpecVerifier per block — per-request draft
    # streams, ragged per-suffix histories, per-suffix KV slot clocks.
    # None = the wave decodes plain (the default path, and waves whose
    # budget ends at prefill).
    spec: dict[int, SpecVerifier] | None = None
    # Per-sweep slot offsets fixed at the embed segment (shard 0) and
    # consumed by every decoder segment of the same sweep.
    spec_base: dict[int, np.ndarray] = dataclasses.field(default_factory=dict)
    # Per-block [B][S] SLO-class name of each suffix row's OWNING request
    # (None for bucket padding) — drives the per-class fls_spec_* split
    # and the adaptive controller's per-row k assignment.
    spec_classes: dict[int, list] = dataclasses.field(default_factory=dict)
    # Paged prefix-KV pool (runtime/kvpool.py): one PrefixHandle per wave
    # entry — the entry's lease on its block table, held from admission
    # to retire/preempt/abort — and the blocks whose EVERY row reuses a
    # sealed pool entry (those skip the prefix prefill and run the
    # suffix-only scan over assembled pages).
    pool_handles: dict[int, Any] = dataclasses.field(default_factory=dict)
    reuse_blocks: set[int] = dataclasses.field(default_factory=set)
    # Multi-tenant LoRA (adapters/): wave-level row grouping fixed at
    # init. ``adapter_scales`` is None for a base-only wave — the delta
    # kwarg then stays None at every decoder jit call, keeping the
    # traced computation byte-identical to pre-adapter serving.
    # ``adapter_ab`` caches the [k, G, D, R]/[k, G, R, D] device factor
    # stacks per (shard_pos, decoder-segment) — built on first touch,
    # reused by every later sweep of this wave, so delta bytes cross
    # the host->HBM link once per wave, not once per sweep.
    adapter_names: list = dataclasses.field(default_factory=list)
    adapter_scales: Any = None            # [G] f32 host; None = base-only
    adapter_factors: dict = dataclasses.field(default_factory=dict)
    adapter_rank: int = 0                 # wave max rank (zero-pad target)
    adapter_g: dict = dataclasses.field(default_factory=dict)  # b -> [B] i32
    adapter_ab: dict = dataclasses.field(default_factory=dict)
    adapter_gdev: dict = dataclasses.field(default_factory=dict)
    adapter_scale_dev: Any = None


class ServeEngine:
    """Continuous-batching server over the streaming decode runtime.

    ``submit()`` is thread-safe and non-blocking (backpressure raises
    through the returned request's future); results resolve via
    ``Request.future`` and the optional per-request callback.
    """

    def __init__(
        self,
        cfg: FrameworkConfig,
        serve_cfg: ServeConfig | None = None,
        tokenizer=None,
        device=None,
        start: bool = True,
        process_metrics_mirror: bool = True,
        scheduler=None,
        wal=None,
    ):
        # scheduler: a SHARED SweepScheduler (serve/fleet.py passes the
        # fleet-wide instance so tenant rate limits and DRR fairness span
        # replicas instead of multiplying by the replica count). None =
        # this engine builds its own when serve_cfg.sched.enabled.
        # wal (serve/wal.RequestWAL or None): the durable request ledger
        # for crash-safe serving — admission records write ahead of the
        # queue, progress records land at sweep boundaries, and graceful
        # restart (shutdown_for_restart) parks unfinished requests for a
        # token-identical replay (serve/recovery.py). The fleet passes
        # its shared instance so recycled replicas inherit the same log.
        if cfg.temperature > 0:
            raise ValueError(
                "serving is greedy-only for now (per-request rng streams "
                "under sampling are future work); set temperature=0"
            )
        if cfg.speculative_k:
            raise ValueError(
                "FrameworkConfig.speculative_k is the OFFLINE scorer's "
                "knob; serving speculation is ServeConfig.speculative_k "
                "(--speculative_k on the serve parser)"
            )
        if cfg.long_context:
            raise ValueError("long_context serving is not supported yet")
        if cfg.data_parallel or cfg.tensor_parallel > 1:
            raise ValueError(
                "serving v1 drives a single placement target; drop "
                "data_parallel/tensor_parallel"
            )
        self.cfg = cfg
        self.serve_cfg = serve_cfg or ServeConfig()
        # Speculative serving: 0 keeps the plain one-token-per-sweep
        # decode (the parity baseline every spec test pins against).
        self._spec_k = self.serve_cfg.speculative_k
        self.device = device
        self.model_cfg = LlamaConfig.from_pretrained(cfg.model_path)
        self.model_cfg.require_one_attention_shape("the serve engine")
        self.model_cfg.require_single_visit("the serve engine")
        self.dtype = _DTYPES[cfg.dtype]
        if tokenizer is None:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(cfg.model_path)
        self.raw_tokenizer = tokenizer
        self.tokenizer = PromptTokenizer(
            tokenizer,
            max_token_len=cfg.max_token_len,
            bucket_multiple=cfg.bucket_multiple,
        )
        self.layer_names = checkpoint.layer_names_for(
            self.model_cfg.num_hidden_layers, tie_word_embeddings=False
        )
        self.shards = list(
            plan_shards_dp(
                len(self.layer_names), cfg.layer_num_per_shard
            ).shards
        )
        self._n_layers = len(self.layer_names)
        self._use_pallas = cfg.pallas_enabled()
        self._resident = cfg.decode_resident_enabled(
            self.model_cfg, 1, device
        )
        # Sweep-timeline tracing (obs/trace.py): process-wide, enabled by
        # --trace; every span below is a no-op bool check when off.
        obs_trace.ensure_configured(cfg)
        # Flight recorder (obs/events.py + obs/incident.py): the durable
        # event journal every failure path below writes through, and the
        # incident recorder that bundles journal tail + metrics + trace
        # on trigger-severity events. Both process-wide, both zero-cost
        # no-ops unless --journal_dir/--incidents_dir configured them.
        obs_events.ensure_configured(cfg)
        obs_incident.ensure_configured(cfg, self.serve_cfg)
        # process_metrics_mirror=False: fleet-owned replica — this
        # engine's sources stay out of the process-wide registry's bare
        # 'serve'/... names (the fleet exports replica<idx> mirrors).
        self.metrics = ServingMetrics(process_mirror=process_metrics_mirror)
        # Chaos injector (None unless cfg.faults.enabled) and the weight
        # stream's retry policy — threaded into the admission queue and
        # every source this engine builds.
        self._injector = FaultInjector.from_config(cfg.faults)
        self._retry_policy = cfg.retry_policy()
        # Host shard cache: the cycling source's steady-state sweeps hit it
        # and skip disk read/parse/checksum entirely — and because the
        # cache outlives any one source, a recovery's source restart warms
        # instantly too. The stats line carries its hit rate.
        from flexible_llm_sharding_tpu.runtime import hostcache, residency

        self._host_cache = hostcache.cache_for(cfg)
        self.metrics.host_cache = self._host_cache
        # Device residency tier: the hottest layers load once (manifest-
        # verified) and stay on chip for the PROCESS lifetime — pins
        # survive source restarts and wave recoveries, and every sweep's
        # stream skips exactly their bytes. Moot when the whole model is
        # already resident (decode_resident), so skipped there.
        self._residency = (
            None
            if self._resident
            else residency.tier_for(
                cfg, self.layer_names, self.model_cfg.tie_word_embeddings,
                device,
            )
        )
        self.metrics.residency = self._residency
        # The engine registry (ServingMetrics.registry) additionally
        # exposes the process stream counters and the tracer's own
        # accounting, so ONE scrape answers the routing/health questions:
        # queue depth, TTFT quantiles, streamed bytes, cache hit rate,
        # residency savings, retry/heal/recovery counters.
        from flexible_llm_sharding_tpu.runtime.executor import stream_stats

        self.metrics.register(
            "stream", stream_stats,
            mirror=False,  # process-level: executor registers it globally
        )
        self.metrics.register(
            "trace", obs_trace.TRACER.stats,
            mirror=False,  # process-level: the tracer registers on enable
        )
        self.metrics.register(
            "journal", obs_events.JOURNAL.stats,
            mirror=False,  # process-level: the journal registers on enable
        )
        # SLO error budgets (obs/slo.py): always registered so the
        # fls_slo_* family scrapes pre-seeded even before targets are
        # configured; with --slo on, budget exhaustion journals (and,
        # recorder armed, captures an incident bundle).
        self._slo = SLOTracker(self.serve_cfg.slo, self.metrics)
        self.metrics.register("slo", self._slo.stats)
        # Prometheus endpoint (ServeConfig.metrics_port / --metrics_port):
        # None = off; 0 = ephemeral port (tests) — the bound port is
        # self.metrics_server.port.
        self.metrics_server = None
        if self.serve_cfg.metrics_port is not None:
            from flexible_llm_sharding_tpu.obs.registry import MetricsServer

            self.metrics_server = MetricsServer(
                self.metrics.registry, port=self.serve_cfg.metrics_port
            )
        # Multi-tenant sweep scheduler (serve/sched/, docs/scheduling.md):
        # None keeps the strict-FIFO pop (the pre-scheduler path, and the
        # parity baseline tests/test_sched.py pins against). When on, the
        # queue pops by class priority + tenant DRR, submit enforces
        # per-tenant rate limits, boundaries may preempt best-effort
        # waves, and same-prefix admissions coalesce into one prefill.
        self._sched = scheduler
        if self._sched is None and self.serve_cfg.sched.enabled:
            self._sched = SweepScheduler(self.serve_cfg.sched)
        if self._sched is not None:
            self.metrics.register("sched", self._sched.stats)
        # Crash-safe request WAL (serve/wal.py): built here from the
        # config unless the fleet handed down its shared instance.
        if wal is None and self.serve_cfg.wal_dir:
            from flexible_llm_sharding_tpu.serve.wal import wal_for

            wal = wal_for(self.serve_cfg)
        self._wal = wal
        if self._wal is not None:
            self.metrics.register("wal", self._wal.stats)
        self.queue = AdmissionQueue(
            self.serve_cfg.queue_capacity, metrics=self.metrics,
            injector=self._injector,
            max_request_tokens=self.serve_cfg.max_request_tokens,
            size_fn=self._request_size_tokens,
            scheduler=self._sched,
            wal=self._wal,
        )
        # Resource-pressure brownout (runtime/pressure.py): the process
        # controller (None unless cfg.pressure.enabled) sheds through
        # this queue at its shed level — attached after construction so
        # an engine joining mid-brownout starts shedding immediately —
        # and its counters ride this engine's endpoint/stats line.
        from flexible_llm_sharding_tpu.runtime import pressure as _pressure

        self._pressure = _pressure.controller_for(cfg)
        if self._pressure is not None:
            self._pressure.attach_queue(self.queue)
            self.metrics.register(
                "pressure", self._pressure.stats,
                mirror=False,  # process-level: controller_for registers it
            )
        # Resident draft model (runtime/draft.py): a small model pinned
        # whole on chip through its OWN residency tier and used as the
        # draft source instead of prompt lookup — draft decode runs
        # against the pinned weights, so speculation adds ZERO bytes to
        # the per-sweep weight stream. Construction is fail-fast (a
        # draft model that would stream per call defeats its premise).
        self._draft_model = None
        if self.serve_cfg.draft_model_path:
            from flexible_llm_sharding_tpu.runtime.draft import DraftModel

            self._draft_model = DraftModel(
                self.serve_cfg.draft_model_path,
                device=device,
                retry_policy=self._retry_policy,
                injector=self._injector,
            )
            self.metrics.register("draft", self._draft_model.stats)
        # SLO-aware adaptive k (serve/spec.py): per-class draft depth
        # follows windowed live acceptance. The verify slot budget is
        # provisioned at spec_k_max so the controller can raise k
        # without re-planning waves; per-pass depths are assigned via
        # SpecVerifier.set_pass_k. Registered with the brownout ladder
        # as the spec_backoff lever's target.
        self._spec_ctrl = None
        if self.serve_cfg.spec_adaptive:
            from flexible_llm_sharding_tpu.serve.spec import SpecController

            self._spec_k = self.serve_cfg.spec_k_max
            self._spec_ctrl = SpecController(
                self.serve_cfg.speculative_k,
                self.serve_cfg.spec_k_min,
                self.serve_cfg.spec_k_max,
                self.serve_cfg.spec_window,
                self.serve_cfg.spec_raise_threshold,
                self.serve_cfg.spec_backoff_threshold,
                self.serve_cfg.spec_draft_budget,
            )
            self.metrics.register("spec_ctrl", self._spec_ctrl.stats)
            if self._pressure is not None:
                self._pressure.attach_spec(self._spec_ctrl)
        # The one scheduling policy object (runtime/schedcore.py): wave
        # admission quotas, generated-KV slot sizing, and the residency
        # decision — shared verbatim with the offline DecodeGenerator so
        # the two paths cannot drift.
        self._sched_core = SchedCore(cfg)
        # Paged prefix-KV pool (runtime/kvpool.py): a recurring prefix
        # prefills once per PROCESS; later same-prefix waves reuse its
        # refcounted pages with zero prefix recompute (copy-on-write at
        # the first divergent token). Longrope models opt out: their
        # prefix KV depends on the prompt's TOTAL length through the
        # rope-table switch, so same prefix tokens != same prefix KV.
        self._kv_pool = (
            None
            if self.model_cfg.rope_scaling_kind == "longrope"
            else kvpool.pool_for(cfg)
        )
        if self._kv_pool is not None:
            self._kv_pool.set_injector(self._injector)
            self.metrics.register(
                "kvpool", kvpool.process_stats,
                mirror=False,  # process-level: pool_for registers it
            )
        # Multi-tenant LoRA adapters (adapters/, docs/adapters.md): the
        # process-wide host-resident delta store — None when
        # --adapter_dir is unset. Requests carry an adapter_id; waves
        # group rows by adapter and the decoder scans apply the grouped
        # low-rank shift at each layer entry, so N tenants' fine-tunes
        # decode in one sweep over ONE base-model stream.
        from flexible_llm_sharding_tpu.adapters import loader as adapter_loader

        self._adapter_store = adapter_loader.store_for(cfg)
        if self._adapter_store is not None:
            self._adapter_store.injector = self._injector
            self.metrics.register(
                "adapter", self._adapter_store.stats,
                mirror=False,  # process-level: store_for registers it
            )
        self.batcher = ShardAwareBatcher(
            self.queue,
            self.serve_cfg.max_wave_requests,
            self.serve_cfg.max_active_requests,
            metrics=self.metrics,
            sched_core=self._sched_core,
            # Prefix coalescing (serve/sched/coalesce.py): keyed by the
            # TOKENIZED prefix, so string-distinct prefixes that tokenize
            # identically still share one prefill.
            entry_builder=(
                (lambda reqs: build_entries(reqs, self._prefix_key))
                if self._sched is not None and self.serve_cfg.sched.coalesce
                else None
            ),
        )
        self._kept: list | None = None  # resident: placed shards
        self._source: ShardWeightSource | None = None  # streamed: cycling
        self._src_iter = None
        self._watchdog: StepWatchdog | None = None
        self._error: BaseException | None = None
        self._thread: threading.Thread | None = None
        # Graceful-restart flag (shutdown_for_restart): checked at the
        # TOP of the run loop, so every in-flight wave has finished its
        # current sweep (prefill complete, pool handles sealed) before
        # the drain exports KV and parks the requests for replay.
        self._restart_pending = False
        # Process-death chaos drill (tests/test_wal.py, chaos smoke):
        # SIGKILL this process mid-sweep after N completed sweeps. Env,
        # not config: only the crash harness may aim this gun.
        self._crash_sweeps = int(
            os.environ.get("FLS_WAL_CRASH_SWEEPS", "0") or 0
        )
        self._sweeps_done = 0
        # Fleet hooks (serve/fleet.py). _sweep_pos/_heartbeat are the
        # sweep-progress watermark the router's phase scoring and liveness
        # check read lock-free (scalar writes from the engine thread only;
        # a torn read just skews one routing score by one shard).
        # fleet_hook, when set, is called once per shard step from the
        # engine thread — the fleet's replica-level chaos sites fire there.
        self._sweep_pos = 0
        self._heartbeat = time.monotonic()
        self.fleet_hook: Callable[[int], Any] | None = None
        if start:
            self.start()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ServeEngine":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="serve-engine", daemon=True
            )
            self._thread.start()
        return self

    def __enter__(self) -> "ServeEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=exc == (None, None, None))

    def submit(
        self,
        prefix: str,
        suffixes: tuple[str, ...] | list[str],
        max_new_tokens: int | None = None,
        deadline_s: float | None = None,
        callback: Callable[[Request], Any] | None = None,
        slo_class: str | None = None,
        tenant_id: str | None = None,
        adapter_id: str | None = None,
        client_id=None,
    ) -> Request:
        """Enqueue one request (any thread). Backpressure/closed/deadline
        outcomes surface through the returned request's future; an
        unknown ``slo_class`` raises typed (UnknownSLOClass) to the
        submitter. Deadline precedence: the request's own, else the SLO
        class's default (scheduler on), else the serve-level default.
        ``client_id`` is the caller's stable correlation id — recorded in
        the WAL and echoed in replies, it is the identity a client dedups
        by across a crash/restart (``request_id`` is per-process)."""
        slo = parse_class(slo_class)
        if deadline_s is None:
            deadline_s = class_deadline_s(self.serve_cfg.sched, slo)
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
        """Enqueue a pre-built request (the fleet path: a re-dispatched
        request must keep its stable ``dispatch_id`` and fleet-owned
        callback across replicas, so the fleet builds the Request itself
        instead of going through ``submit``'s constructor)."""
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        return self.queue.submit(req)

    def drain(self, timeout: float | None = None) -> bool:
        """Graceful shutdown: refuse new submissions, serve out everything
        queued and in flight, then stop. Returns whether the loop exited
        within ``timeout``."""
        return self.shutdown(drain=True, timeout=timeout)

    def shutdown(self, drain: bool = True, timeout: float | None = None) -> bool:
        if self._pressure is not None:
            # A dead engine's queue must stop being a shed target (and a
            # recycled replica's fresh queue attaches on construction).
            self._pressure.detach_queue(self.queue)
            if self._spec_ctrl is not None:
                self._pressure.detach_spec(self._spec_ctrl)
        self.queue.close(drain=drain)
        ok = True
        if self._thread is not None:
            self._thread.join(timeout)
            ok = not self._thread.is_alive()
        if self.metrics_server is not None:
            self.metrics_server.close()
        if self._draft_model is not None:
            self._draft_model.close()
        # Retract this engine's process-wide registry mirrors: a dead
        # engine must neither serve stale counters to a later process-
        # wide dump nor pin its object graph for the process lifetime.
        self.metrics.close()
        return ok

    def shutdown_for_restart(self, timeout: float | None = None) -> bool:
        """Graceful-restart shutdown (SIGTERM / preemption notice): stop
        admission, let every in-flight wave finish its CURRENT sweep,
        then — at the sweep boundary — flush progress + spilled-KV refs
        to the WAL, park every unfinished request as ``RestartPending``
        (no terminal record: they stay open for replay), and exit clean.
        The next boot's ``serve.recovery.replay`` re-admits everything
        parked here and serves it token-identically. Requires a WAL;
        without one this is just ``shutdown(drain=False)``."""
        if self._wal is None:
            return self.shutdown(drain=False, timeout=timeout)
        if self._pressure is not None:
            self._pressure.detach_queue(self.queue)
            if self._spec_ctrl is not None:
                self._pressure.detach_spec(self._spec_ctrl)
        # Park still-QUEUED requests first (persist=True -> RestartPending,
        # admission records stay open), then flag the loop: it drains the
        # in-flight waves at the next boundary and exits.
        self.queue.close(drain=False, persist=True)
        self._restart_pending = True
        ok = True
        if self._thread is not None:
            self._thread.join(timeout)
            ok = not self._thread.is_alive()
        self._wal.flush(sync=True)
        self._wal.maybe_compact()
        obs_events.emit(
            "shutdown_drain",
            clean=ok,
            open_requests=self._wal.stats()["open_requests"],
        )
        if self.metrics_server is not None:
            self.metrics_server.close()
        if self._draft_model is not None:
            self._draft_model.close()
        self.metrics.close()
        return ok

    def _drain_for_restart(self) -> None:
        """Run-loop side of ``shutdown_for_restart``, at a sweep boundary:
        every wave just completed a full sweep, so per-request progress
        and pool KV are consistent. Export each live request's prefix-KV
        pages (checksummed, via the pool's verified spill machinery) so
        the restarted process can warm-start instead of re-prefilling,
        write the final progress records, and park the requests."""
        for wave in self.batcher.waves:
            st = wave.state
            for r in wave.requests:
                if r.status.terminal or r.wal_id is None:
                    continue
                kv_refs = None
                if (
                    self._kv_pool is not None
                    and st is not None
                    and wave.steps > 0
                ):
                    e_idx, _, _ = wave.locate(r)
                    handle = st.pool_handles.get(e_idx)
                    tp = st.toks[e_idx]
                    if handle is not None:
                        kv_refs = self._kv_pool.export_entry(
                            handle,
                            self._wal.wal_dir,
                            tuple(
                                int(t)
                                for t in tp.prefix_ids[: tp.prefix_len]
                            ),
                            salt=self._entry_adapter(wave.entries[e_idx]),
                        )
                self._wal.progress(r, kv=kv_refs)
        waves = list(self.batcher.waves)  # fail_all_active clears the list
        self.batcher.fail_all_active(
            RestartPending(
                "serve process restarting; in-flight request journaled "
                "for token-identical replay"
            )
        )
        for w in waves:
            if w.state is not None:
                w.state.kv_store.clear()
                self._release_pool_handles(w.state)

    @property
    def error(self) -> BaseException | None:
        return self._error

    def stats(self) -> dict:
        return self.metrics.snapshot()

    # -- fleet hooks (serve/fleet.py) --------------------------------------

    @property
    def slo_tracker(self):
        """The engine's ``SLOTracker`` (obs/slo.py) — always present
        (the ``fls_slo_*`` family pre-seeds even with SLO tracking off).
        The fleet autoscaler reads burn rates and the windowed burn
        trend through this instead of reaching into ``_slo``."""
        return self._slo

    def sweep_position(self) -> dict:
        """Router/health snapshot, callable from any thread (lock-free
        scalar reads). ``boundary_frac`` is the fraction of a weight sweep
        remaining until this engine's next shard-0 admission point — the
        phase-proximity term of the router's score (0.0 for an idle
        engine: it sits AT the boundary polling its queue). ``watermark``
        is the last monotonic instant the sweep made progress; a busy
        engine whose watermark stalls past ``watchdog_abort_s`` is
        declared dead by the fleet."""
        n = len(self.shards)
        pos = self._sweep_pos
        sweeping = bool(self.batcher.waves)
        return {
            "shard_pos": pos,
            "n_shards": n,
            "boundary_frac": (n - pos) / n if sweeping else 0.0,
            "watermark": self._heartbeat,
            "busy": sweeping or len(self.queue) > 0,
        }

    def reclaim_inflight(self) -> list[Request]:
        """Dead-replica orphan handoff: collect every request this engine
        still holds non-terminal — queued AND in-flight — and return them
        with their original prompts and ``dispatch_id``s so the caller
        (the fleet's hard-fail path) can RE-DISPATCH them to a surviving
        replica instead of surfacing an error. Without this, a dead
        engine's in-flight requests were simply lost: ``_recover`` fails
        them with WaveAborted only when the engine thread is alive to run
        it, and a wedged/killed thread never does.

        Each reclaimed request's own future resolves WaveAborted
        (first-wins — a wedged engine thread waking up later loses the
        claim, so a re-dispatched request is never double-served) but its
        callback is deliberately NOT fired: the caller owns the onward
        re-dispatch, and the callback path would surface the abort to the
        submitter instead. Only call this once the engine has been
        declared dead or is being force-recycled."""
        err = WaveAborted(
            "replica declared dead; request reclaimed for re-dispatch"
        )
        orphans: list[Request] = []
        pools: list[list[Request]] = [self.queue.reclaim()]
        # list() copies: the batcher's wave list may still be mutated by a
        # not-quite-dead engine thread; iteration must not race it.
        pools.append(
            [r for w in list(self.batcher.waves) for r in list(w.requests)]
        )
        for r in [r for pool in pools for r in pool]:
            if not r.status.terminal and r.future.claim():
                r.status = RequestStatus.FAILED
                r.finished_at = time.monotonic()
                r.future.finish_error(err)
                orphans.append(r)
        return orphans

    # -- the serving loop --------------------------------------------------

    def _run(self) -> None:
        try:
            self._acquire_weights()
        except BaseException as e:  # noqa: BLE001 — surfaced via futures  # flscheck: disable=EXC-TAXONOMY: daemon-thread boundary — the error is surfaced through every pending future via _fatal, never swallowed
            self._fatal(e)
            return
        wd = None
        if self.serve_cfg.watchdog_abort_s > 0 and not self._resident:
            # Step-progress watchdog over the streamed sweep: if no shard
            # lands for watchdog_abort_s, abort the source (non-blocking,
            # from the watchdog thread) — the consumer get below then
            # raises SourceClosed, which the recovery path turns into a
            # failed wave + source restart instead of futures hanging
            # forever. Resident sweeps move no weight bytes; a stall there
            # is a compute wedge the source can't unwedge, so no watchdog.
            wd = StepWatchdog(
                "serve-sweep", self.serve_cfg.watchdog_abort_s, self._on_stall
            )
            self.metrics.register("watchdog", wd.stats)
        self._watchdog = wd
        try:
            while True:
                # ---- shard-0 boundary: the admission point ----------------
                # Boundary passes are liveness too: an idle engine polling
                # its empty queue must not look wedged to the fleet.
                self._heartbeat = time.monotonic()
                if self._restart_pending:
                    # Graceful restart: every wave just finished a full
                    # sweep (we are AT the boundary), so KV/handles are
                    # consistent — export them, park every unfinished
                    # request for WAL replay, and stop.
                    self._drain_for_restart()
                    break
                # Preemption BEFORE admission: a retired best-effort wave
                # frees slots this same boundary's pop hands to the
                # waiting interactive work (serve/sched, never mid-sweep).
                self._maybe_preempt()
                wave = self.batcher.admit_at_boundary()
                if wave is not None and not self._init_wave(wave):
                    continue  # wave failed at tokenization; re-check queue
                if wave is not None:
                    obs_trace.instant(
                        "wave_admit",
                        cat="serve",
                        wave_id=wave.wave_id,
                        requests=len(wave.requests),
                        request_ids=[r.request_id for r in wave.requests],
                    )
                if not self.batcher.waves:
                    if self.queue.closed and len(self.queue) == 0:
                        break
                    # The stats heartbeat must keep beating while IDLE too —
                    # monitoring that watches for the periodic line would
                    # otherwise read quiet traffic as a wedged server.
                    self.metrics.maybe_emit(self.serve_cfg.stats_interval_s)
                    if len(self.queue) == 0:
                        time.sleep(self.serve_cfg.idle_poll_s)
                    continue
                t0 = time.perf_counter()
                try:
                    if wd is not None:
                        # The armed period guards THIS source: the token
                        # rides inside the watchdog, so a stall callback
                        # delayed across a recovery can never abort the
                        # fresh replacement.
                        wd.arm(token=self._source)
                    self._sweep()
                except (
                    ShardLoadError, SourceClosed, OSError, SpillCorruptError,
                ) as e:
                    # Degrade, don't die: an exhausted shard load, a
                    # watchdog-aborted stall, a transient I/O error that
                    # escaped the retry layer, or a pooled KV page whose
                    # corruption survived every re-read (the pool already
                    # dropped it, so the retry re-prefills) fails ONLY the
                    # in-flight waves; queued and future requests keep
                    # being served.
                    self._recover(e)
                    continue
                finally:
                    if wd is not None:
                        wd.disarm()
                self._post_sweep(time.perf_counter() - t0)
                self.metrics.maybe_emit(self.serve_cfg.stats_interval_s)
        except BaseException as e:  # noqa: BLE001  # flscheck: disable=EXC-TAXONOMY: daemon-thread boundary — engine-fatal errors resolve every in-flight and queued future with the root cause
            self._fatal(e)
        finally:
            if wd is not None:
                wd.close()
            self._release_weights()

    def _fatal(self, error: BaseException) -> None:
        """Engine-fatal: every in-flight AND queued request fails with the
        root cause; the loop stops; later submits see ServeClosed."""
        self._error = error
        obs_events.emit(
            "engine_fatal",
            error=type(error).__name__,
            detail=str(error)[:200],
            waves=len(self.batcher.waves),
            wave_ids=[w.wave_id for w in self.batcher.waves],
        )
        for w in self.batcher.waves:
            if w.state is not None:
                self._release_pool_handles(w.state)
        self.batcher.fail_all_active(error)
        self.queue.close(drain=False)  # cancels queued; futures resolve
        self._release_weights()

    def _recover(self, root: BaseException) -> None:
        """Recoverable mid-sweep fault. The sweep died partway, so every
        in-flight wave's compute state (KV, partial scores) is unusable:
        fail exactly those requests with a structured WaveAborted carrying
        the root cause, drop their KV, restart the weight source, and keep
        serving — the admission queue and later submissions are untouched."""
        # Recovery is progress: a fleet watching the watermark must see a
        # self-healing engine as live (only a recovery that itself wedges —
        # e.g. blocks joining a dead producer — re-stalls the watermark and
        # escalates to replica death).
        self._heartbeat = time.monotonic()
        if self._watchdog is not None:
            # Recovery itself can block (joining a wedged producer); an
            # armed watchdog firing mid-recovery would abort the FRESH
            # source built below. The sweep loop re-arms on its next pass.
            self._watchdog.disarm()
        n_waves = len(self.batcher.waves)
        for w in self.batcher.waves:
            if w.state is not None:
                w.state.kv_store.clear()
                self._release_pool_handles(w.state)
        err = WaveAborted(
            f"in-flight wave aborted by a recoverable engine fault "
            f"({type(root).__name__}: {root}); the engine recovered and "
            "keeps serving — resubmit"
        )
        err.__cause__ = root
        for w in self.batcher.waves:
            obs_trace.instant(
                "wave_abort", cat="serve", wave_id=w.wave_id,
                error=type(root).__name__,
            )
            obs_events.emit(
                "wave_abort", wave_id=w.wave_id,
                error=type(root).__name__,
                request_ids=[r.request_id for r in w.requests],
            )
        self.batcher.fail_all_active(err)
        self.metrics.count("engine_recoveries")
        obs_trace.instant(
            "engine_recovery", cat="serve", error=type(root).__name__,
            waves=n_waves,
        )
        obs_events.emit(
            "engine_recovery", error=type(root).__name__,
            detail=str(root)[:200], waves=n_waves,
        )
        if n_waves:
            self.metrics.count("waves_aborted", n_waves)
        if not self._resident:
            # Fresh source + iterator: the old producer may be dead, mid-
            # fault, or aborted by the watchdog; a cycling stream restarts
            # cleanly at shard 0, which is exactly the next admission
            # boundary.
            self._release_weights()
            self._acquire_weights()
            self.metrics.count("source_restarts")

    def _on_stall(self, idle_s: float, token) -> None:
        """Watchdog thread: non-blocking abort of the wedged source; the
        engine thread's pending queue get raises SourceClosed and the
        recovery path above takes over. ``token`` is the source the firing
        armed period captured — only IT is ever aborted, and only while it
        is still the live source (if recovery already replaced it, the
        stalled-on source is gone and the replacement must not be touched)."""
        if token is None or token is not self._source:
            return
        self.metrics.count("watchdog_stalls")
        token.abort()

    # -- weights -----------------------------------------------------------

    def _mk_source(self, cycle: bool) -> ShardWeightSource:
        return ShardWeightSource(
            self.cfg.model_path,
            self.layer_names,
            self.shards,
            np_dtype_for(self.cfg.dtype),
            device=self.device,
            prefetch_depth=self.cfg.effective_prefetch_depth(),
            tied_embeddings=self.model_cfg.tie_word_embeddings,
            layer_sliding=self.model_cfg.layer_sliding,
            layer_rope=self.model_cfg.layer_rope,
            cycle=cycle,
            retry_policy=self._retry_policy,
            injector=self._injector,
            retry_recorder=self.metrics.retries,
            integrity_recorder=self.metrics.integrity,
            verify_weights=self.cfg.verify_weights,
            host_cache=self._host_cache,
            readahead_threads=self.cfg.readahead_threads,
            residency=self._residency,
        )

    def _acquire_weights(self) -> None:
        if self._resident:
            # One pass places every shard; references kept for the engine's
            # lifetime, so sweeps move zero weight bytes.
            src = self._mk_source(cycle=False)
            try:
                self._kept = list(enumerate(src))
            finally:
                src.close()
        else:
            # Cycling stream: the producer wraps from the last shard back
            # to shard 0, so the prefetch pipeline never cold-starts at a
            # sweep boundary.
            self._source = self._mk_source(cycle=True)
            self._src_iter = iter(self._source)

    def _release_weights(self) -> None:
        self._kept = None
        if self._source is not None:
            self._source.close()
            self._source = None
            self._src_iter = None

    def _sweep_shards(self):
        if self._resident:
            return iter(self._kept)
        return enumerate(islice(self._src_iter, len(self.shards)))

    # -- wave setup --------------------------------------------------------

    def _request_size_tokens(self, req: Request) -> int:
        """Admission-side size estimate: prefix tokens + the LONGEST
        suffix's tokens + the generation budget — the per-row sequence
        the wave will actually allocate (truncated exactly like the
        PromptTokenizer will). Host-side tokenization only; runs on the
        submitter thread, never the sweep loop. Known cost: with the cap
        enabled, an ADMITTED request is tokenized again at wave init —
        one extra host pass per request, accepted because the cap is
        opt-in and reusing raw ids would entangle this estimate with
        PromptTokenizer's bucketing state."""
        pids = self.raw_tokenizer(
            req.prefix, truncation=True, max_length=self.cfg.max_token_len
        )["input_ids"]
        longest = 0
        if req.suffixes:
            sids = self.raw_tokenizer(
                list(req.suffixes), truncation=True,
                max_length=self.cfg.max_token_len,
            )["input_ids"]
            longest = max((len(s) for s in sids), default=0)
        return len(pids) + longest + req.max_new_tokens

    def _prefix_key(self, prefix: str) -> tuple:
        """Coalescing key: the tokenized prefix (truncation-aware), so
        requests merge exactly when their prefix TOKEN streams match.
        One extra host-side prefix tokenization per admitted request —
        the same order of cost as the admission size cap, paid only with
        coalescing on."""
        return tuple(
            self.raw_tokenizer(
                prefix, truncation=True, max_length=self.cfg.max_token_len
            )["input_ids"]
        )

    def _prefix_kv_bytes(self, prefix_tokens: int) -> int:
        """ANALYTIC prefix-KV bytes one prefill materializes for a
        ``prefix_tokens``-long prefix: K + V per layer per kv-head at the
        compute dtype. Pool-OFF fallback only — with the paged pool on,
        ``prefill_kv_bytes_saved`` reads the allocator's actual page
        bookkeeping (``KVPagePool.entry_bytes``, via ``_note_coalesced``)
        so the counter cannot drift from what the pool really shares."""
        mc = self.model_cfg
        itemsize = np.dtype(self.dtype).itemsize
        return int(
            prefix_tokens
            * mc.num_hidden_layers
            * mc.num_key_value_heads
            * (mc.head_dim + mc.v_dim)
            * itemsize
        )

    def _note_coalesced(self, wave, entry, tp, handle) -> None:
        """Bank one coalesced entry's savings from the ALLOCATOR's page
        bookkeeping (entry_bytes sums the entry's actual pages) rather
        than the analytic estimate — called at seal time for freshly
        prefilled entries (pages exist only then) and at admission for
        reuse-path entries (their pages already exist)."""
        saved = (len(entry.requests) - 1) * self._kv_pool.entry_bytes(handle)
        self._sched.note_coalesced(len(entry.requests), saved)
        obs_trace.instant(
            "prefix_coalesce", cat="sched",
            wave_id=wave.wave_id,
            requests=len(entry.requests),
            request_ids=[r.request_id for r in entry.requests],
            prefix_tokens=tp.prefix_len,
            kv_bytes_saved=saved,
        )

    def _release_pool_handles(self, st) -> None:
        """Drop a wave's block-table leases (retire, preempt, abort,
        fatal). Idempotent; pages persist for future same-prefix reuse —
        only the refcounts pinning them drop."""
        if self._kv_pool is None:
            return
        for h in st.pool_handles.values():
            self._kv_pool.release(h)

    def _tokenize_entry(self, entry):
        """One (prefix, merged-suffixes) prompt per wave entry; a
        preemption-resumed request's generated-so-far tokens fold into
        its suffix rows as TOKEN IDS (resume entries are never coalesced,
        serve/sched/coalesce.py), so the resumed prefill recomputes
        exactly the interrupted decode's KV."""
        tp = self.tokenizer(entry.prefix, entry.suffixes)
        r = entry.requests[0]
        if len(entry.requests) == 1 and r.resume_len:
            gen = np.stack(r.resume_tokens, axis=1).astype(np.int32)
            tp = extend_tokenized(
                tp, gen, self.tokenizer.pad_id,
                self.cfg.bucket_multiple, self.cfg.max_token_len,
            )
        return tp

    # -- multi-tenant LoRA adapters (adapters/) ----------------------------

    def _entry_adapter(self, entry) -> str | None:
        """The entry's adapter id (None = base). Coalescing folds the
        adapter into its key (serve/sched/coalesce.py), so an entry's
        members always agree."""
        return getattr(entry.requests[0], "adapter_id", None)

    def _resolve_adapters(self, wave):
        """Resolve every entry's adapter at wave init (host side, before
        tokenization): ``(ok, plans, factors)`` keyed by adapter name.
        An unknown or corrupt adapter fails ONLY its own entry's
        requests — typed (AdapterNotFound / AdapterCorruptError,
        non-retried: the loader already exhausted its re-reads) — and
        the entry drops from the wave; the base and every other tenant
        in the same wave are untouched. ``ok`` False means no entries
        survived (the wave was removed; re-check the queue)."""
        entries = wave.ensure_entries()
        plans: dict[str, Any] = {}
        factors: dict[str, Any] = {}
        keep: list = []
        for e in entries:
            aid = self._entry_adapter(e)
            if aid is not None and aid not in plans:
                try:
                    if self._adapter_store is None:
                        raise AdapterNotFound(
                            f"adapter {aid!r} requested but adapter "
                            "serving is off — start with --adapter_dir"
                        )
                    plan, fac = self._adapter_store.get(aid)
                    if plan.hidden_size != self.model_cfg.hidden_size:
                        raise AdapterCorruptError(
                            f"adapter {aid!r} was built for hidden_size="
                            f"{plan.hidden_size}; this model has "
                            f"{self.model_cfg.hidden_size}"
                        )
                except (AdapterNotFound, ShardLoadError, OSError) as err:
                    # AdapterCorruptError is a ShardLoadError; a stray
                    # filesystem error resolving one tenant's delta must
                    # likewise fail only that tenant, never the wave.
                    for r in e.requests:
                        if not r.status.terminal and r.fail(
                            err, RequestStatus.FAILED
                        ):
                            self.metrics.count("failed")
                    self.metrics.count("adapter_rejects")
                    obs_trace.instant(
                        "adapter_reject", cat="adapter",
                        wave_id=wave.wave_id, adapter=aid,
                        error=type(err).__name__,
                    )
                    obs_events.emit(
                        "adapter_reject", adapter=aid,
                        error=type(err).__name__, detail=str(err)[:200],
                        request_ids=[r.request_id for r in e.requests],
                    )
                    continue
                plans[aid] = plan
                factors[aid] = fac
            keep.append(e)
        if len(keep) != len(entries):
            wave.entries = keep
            wave.requests = [r for e in keep for r in e.requests]
            if not keep:
                self.batcher.waves.remove(wave)
                return False, plans, factors
        return True, plans, factors

    def _shard_decoder_layers(self, layer_idxs) -> list[str]:
        """The shard's decoder layer names in stream order — consumed
        k-at-a-time by the shard's decoder segments to pick which
        adapters' per-layer factors each segment stacks."""
        return [
            self.layer_names[i]
            for i in layer_idxs
            if self.layer_names[i].startswith("model.layers.")
        ]

    def _segment_delta(self, st, shard_pos, di, seg_layers, b, act_dev):
        """The delta pytree one decoder-segment jit call takes for block
        ``b`` — None for a base-only wave (the zero-adapter fast path:
        no stacking, no transfer, identical trace). The [k, G, D, R]
        factor stacks are built and device_put ONCE per (shard,
        segment) and cached on the wave; only then do their bytes count
        against ``fls_adapter_delta_bytes`` — the link charge to be read
        against the base stream's bytes."""
        if st.adapter_scales is None:
            return None
        key = (shard_pos, di)
        ab = st.adapter_ab.get(key)
        if ab is None:
            stacks = [
                adapter_apply.stack_layer(
                    st.adapter_names, st.adapter_factors, lname,
                    self.model_cfg.hidden_size, st.adapter_rank,
                )
                for lname in seg_layers
            ]
            a_np = np.stack([s[0] for s in stacks])
            b_np = np.stack([s[1] for s in stacks])
            ab = {
                "A": jax.device_put(a_np, act_dev),
                "B": jax.device_put(b_np, act_dev),
            }
            st.adapter_ab[key] = ab
            if self._adapter_store is not None:
                self._adapter_store.note_applied(
                    0, int(a_np.nbytes) + int(b_np.nbytes)
                )
        g = st.adapter_gdev.get(b)
        if g is None:
            g = jax.device_put(st.adapter_g[b], act_dev)
            st.adapter_gdev[b] = g
        if st.adapter_scale_dev is None:
            st.adapter_scale_dev = jax.device_put(
                st.adapter_scales, act_dev
            )
        return {
            "A": ab["A"], "B": ab["B"],
            "g": g, "scale": st.adapter_scale_dev,
        }

    # -- sweep-boundary preemption (serve/sched) ---------------------------

    def _maybe_preempt(self) -> None:
        """At a shard-0 boundary: if an interactive request waits with no
        free active-request slot and a purely best-effort wave in flight,
        retire the youngest best-effort wave (the scheduler decides,
        ``SweepScheduler.pick_preempt``) so this boundary's admission can
        seat the interactive work. Never fires mid-sweep."""
        if self._sched is None:
            return
        free = self.serve_cfg.max_active_requests - self.batcher.active_requests
        victim = self._sched.pick_preempt(self.batcher.waves, self.queue, free)
        if victim is not None:
            self._preempt_wave(victim)

    def _preempt_wave(self, wave: Wave) -> None:
        """Retire one in-flight wave at a boundary WITHOUT resolving
        anything: each live request captures its generated-so-far scores
        and token ids as resume state, drops back to QUEUED, and
        re-enqueues at the queue front. Its KV is released; on
        re-admission the resume tokens fold into the suffix ids so the
        continuation is token-identical to an uninterrupted run (the
        exactly-once ``claim()`` machinery guarantees no double
        resolution if a fleet reclaim races this)."""
        st = wave.state
        live: list[Request] = []
        for r in wave.requests:
            if r.status.terminal:
                continue
            if st is not None and wave.steps > 0:
                e_idx, s_off, s_cnt = wave.locate(r)
                b, row = st.loc[e_idx]
                # Steps THIS wave served it (a twice-preempted request's
                # earlier tokens are already in its resume lists).
                done_here = r.tokens_emitted - r.resume_len
                if st.spec is not None:
                    # Speculative wave: capture up to the request's
                    # SLOWEST suffix (tokens_emitted is that watermark).
                    # A suffix that ran ahead on accepted drafts drops
                    # its surplus — verification is greedy-exact, so the
                    # resumed wave re-derives the identical tokens.
                    sc, tk = st.spec[b].request_steps(
                        row, s_off, s_cnt, max(done_here, 0)
                    )
                    r.resume_scores.extend(sc)
                    r.resume_tokens.extend(tk)
                else:
                    for t in range(max(done_here, 0)):
                        r.resume_scores.append(
                            st.scores[b][t][row, s_off : s_off + s_cnt].copy()
                        )
                        r.resume_tokens.append(
                            st.tok_hist[b][t][row, s_off : s_off + s_cnt].copy()
                        )
            if r.first_token_at is not None:
                # The admission deadline guards TIME TO FIRST TOKEN; once
                # the first token is out, expiring the request while it
                # waits to resume would discard served work over a
                # contract it already met.
                r.deadline = None
            r.status = RequestStatus.QUEUED
            live.append(r)
        if st is not None:
            st.kv_store.clear()
            # Release the block-table leases; the PAGES persist, so on
            # re-admission the resumed entries acquire the same sealed
            # prefix and restore their block tables with zero prefix
            # prefill recompute instead of re-running the prefill.
            self._release_pool_handles(st)
        self.batcher.waves.remove(wave)
        self._sched.note_preempted(len(live))
        obs_trace.instant(
            "wave_preempt", cat="sched", wave_id=wave.wave_id,
            requests=len(live), steps=wave.steps,
            request_ids=[r.request_id for r in live],
        )
        obs_events.emit(
            "wave_preempt", wave_id=wave.wave_id, steps=wave.steps,
            request_ids=[r.request_id for r in live],
        )
        self.queue.requeue(live)

    def _init_wave(self, wave: Wave) -> bool:
        """Tokenize/bucket the admitted entries (one per request, or one
        per prefix-coalesced group) and allocate wave state. A bad
        workload (e.g. a longrope regime straddle) fails ONLY this
        wave's requests; the engine keeps serving."""
        # Adapter resolution first (host side): a missing/corrupt
        # adapter fails ONLY its own entry's requests; the survivors
        # proceed as one wave.
        ok, a_plans, a_factors = self._resolve_adapters(wave)
        if not ok:
            return False
        entries = wave.ensure_entries()
        # Speculative waves only where there is decode to amortize: a
        # wave whose whole budget is the prefill pick never drafts.
        spec_wave = self._spec_k > 0 and wave.max_steps > 1
        pool_handles: dict[int, Any] = {}
        try:
            toks = [self._tokenize_entry(e) for e in entries]
            # A speculative pass's fixed-width K+1 window can overshoot
            # the budget by spec_k fed positions (offline precedent).
            check_longrope_regime(
                self.model_cfg, toks,
                extra_len=max(wave.max_steps - 1, 0)
                + (self._spec_k if spec_wave else 0),
            )
            if self._sched is not None and self._kv_pool is None:
                # Pool off: bank the ANALYTIC estimate at admission. With
                # the pool on, savings come from the allocator's actual
                # page bookkeeping instead (_note_coalesced) — at seal
                # time for fresh prefills, below for reuse-path entries.
                for e, tp in zip(entries, toks):
                    if len(e.requests) > 1:
                        saved = (len(e.requests) - 1) * self._prefix_kv_bytes(
                            tp.prefix_len
                        )
                        self._sched.note_coalesced(len(e.requests), saved)
                        obs_trace.instant(
                            "prefix_coalesce", cat="sched",
                            wave_id=wave.wave_id,
                            requests=len(e.requests),
                            request_ids=[r.request_id for r in e.requests],
                            prefix_tokens=tp.prefix_len,
                            kv_bytes_saved=saved,
                        )
            blocks = make_blocks(toks, self.cfg.block_size)
            meta = {
                b: (
                    jnp.asarray(np.stack([toks[i].prefix_ids for i in idxs])),
                    jnp.asarray(np.stack([toks[i].suffix_ids for i in idxs])),
                    jnp.asarray(
                        np.array(
                            [toks[i].prefix_len for i in idxs], np.int32
                        )
                    ),
                    jnp.asarray(np.stack([toks[i].suffix_eos for i in idxs])),
                )
                for b, idxs in enumerate(blocks)
            }
            loc = {
                i: (b, row)
                for b, idxs in enumerate(blocks)
                for row, i in enumerate(idxs)
            }
            # Paged prefix-KV pool: lease each entry's block table (trie
            # path, refcounted until retire/preempt/abort). A block whose
            # EVERY row leases a sealed same-prefix entry skips its
            # prefix prefill entirely — _prefill_shard assembles the
            # pages and runs only the suffix stream, so the recurring
            # prefix prefills once per PROCESS, not once per wave.
            reuse_blocks: set[int] = set()
            if self._kv_pool is not None:
                for i, tp in enumerate(toks):
                    ids = tuple(
                        int(t) for t in tp.prefix_ids[: tp.prefix_len]
                    )
                    pool_handles[i] = self._kv_pool.acquire(
                        ids, int(tp.prefix_len), int(tp.prefix_ids.shape[0]),
                        # Same prefix under a different LoRA adapter is
                        # different KV — the salt forks the trie so
                        # cross-adapter waves never share pages.
                        salt=self._entry_adapter(entries[i]),
                    )
                for b, idxs in enumerate(blocks):
                    if idxs and all(pool_handles[i].reusable for i in idxs):
                        reuse_blocks.add(b)
                for i, (e, tp) in enumerate(zip(entries, toks)):
                    if loc[i][0] in reuse_blocks:
                        self.metrics.count(
                            "prefix_reuse_tokens", int(tp.prefix_len)
                        )
                        if self._sched is not None and len(e.requests) > 1:
                            self._note_coalesced(wave, e, tp, pool_handles[i])
                    else:
                        self.metrics.count(
                            "prefix_prefill_tokens", int(tp.prefix_len)
                        )
            # Generated-KV slots: plain decode fills one slot per sweep; a
            # speculative pass writes K+1 slots at per-suffix offsets
            # capped at max_steps-1, so the last write touches slot
            # max_steps-1+K (the offline gen_slots arithmetic).
            slots = self._sched_core.gen_slots(
                wave.max_steps, self._spec_k, spec_wave
            )
            # Same KV placement rule as the offline path: KV follows the
            # weights onto the chip when they are resident and the wave's
            # KV fits beside them — host-parked KV costs a full round trip
            # per shard per decode step. The fit check is per WAVE; with
            # several concurrent waves the 80% headroom in kv_fits_on_chip
            # absorbs the others (waves are bounded by max_active_requests).
            kv_on_device = self._sched_core.kv_on_device(
                self.model_cfg, self.cfg.dtype, toks, blocks, slots,
                self._resident, device=self.device,
            )
            # Multi-tenant LoRA grouping: ONE wave-level (names, g) so a
            # single [G] scale vector and one stacked factor set serve
            # every block. Base-only waves keep adapter state None.
            a_names: list = []
            a_scales = None
            a_rank = 0
            a_g: dict[int, np.ndarray] = {}
            if a_plans:
                a_names, g_all = adapter_apply.group_rows(
                    [self._entry_adapter(e) for e in entries]
                )
                a_scales = adapter_apply.group_scales(a_names, a_plans)
                a_rank = max(
                    max((r for _, r in a_plans[n].layers), default=1)
                    for n in a_names
                    if n is not None
                )
                a_g = {
                    b: g_all[np.asarray(idxs, np.int64)]
                    for b, idxs in enumerate(blocks)
                }
                obs_trace.instant(
                    "adapter_apply", cat="adapter", wave_id=wave.wave_id,
                    adapters=[n for n in a_names if n is not None],
                    rows=int((g_all != 0).sum()),
                )
            wave.state = _WaveState(
                toks=toks,
                blocks=blocks,
                meta=meta,
                kv_store=KVStore(on_device=kv_on_device),
                scores={b: [] for b in range(len(blocks))},
                tok_hist={b: [] for b in range(len(blocks))},
                loc=loc,
                slots=slots,
                pool_handles=pool_handles,
                reuse_blocks=reuse_blocks,
                adapter_names=a_names,
                adapter_scales=a_scales,
                adapter_factors=a_factors,
                adapter_rank=a_rank,
                adapter_g=a_g,
            )
            return True
        except (
            ValueError,
            KeyError,
            TypeError,
            IndexError,
            MemoryError,
            RuntimeError,
        ) as e:
            # The typed workload-rejection family: tokenizer errors and the
            # longrope straddle raise ValueError, malformed requests
            # KeyError/TypeError/IndexError (an empty suffix tuple indexes
            # an empty token array), an oversized prompt MemoryError —
            # the admission-side size cap (ServeConfig.max_request_tokens)
            # rejects oversized requests typed at submit when configured,
            # but the cap is optional and many concurrent waves can still
            # exhaust the host, so allocation failures here must reject
            # the wave, not shut the engine down — XLA shape/compile
            # problems RuntimeError. Anything OUTSIDE it is an engine bug, not a
            # bad request — it escapes to _run's fatal path so the root
            # cause surfaces instead of masquerading as a per-wave
            # rejection forever.
            if self._kv_pool is not None:
                for h in pool_handles.values():
                    self._kv_pool.release(h)
            for r in wave.requests:
                if not r.status.terminal and r.fail(e, RequestStatus.FAILED):
                    self.metrics.count("failed")
            self.batcher.waves.remove(wave)
            obs_trace.instant(
                "wave_reject", cat="serve",
                wave_id=getattr(wave, "wave_id", -1),
                error=type(e).__name__,
            )
            obs_events.emit(
                "wave_reject", wave_id=getattr(wave, "wave_id", -1),
                error=type(e).__name__,
                request_ids=[r.request_id for r in wave.requests],
            )
            return False

    # -- per-shard compute -------------------------------------------------

    def _act_dev(self):
        return getattr(self.device, "act", self.device)

    def _sweep(self) -> None:
        """One full weight pass: prefill segments for waves at step 0,
        one decode step for everyone else."""
        wd = self._watchdog
        sweep_id = obs_trace.new_sweep_id()
        with obs_trace.sweep_span(
            sweep_id, cat="serve", mode="serve",
            waves=len(self.batcher.waves),
        ):
            for shard_pos, (layer_idxs, segments) in self._sweep_shards():
                if wd is not None:
                    wd.tick()
                # Sweep-progress watermark: position feeds the router's
                # phase scoring, the timestamp its liveness check.
                self._sweep_pos = shard_pos
                self._heartbeat = time.monotonic()
                if self.fleet_hook is not None:
                    # Replica-level chaos (replica_kill raises an engine-
                    # FATAL ReplicaKilled; replica_stall wedges this
                    # thread until the fleet declares the replica dead).
                    self.fleet_hook(shard_pos)
                if self._injector is not None:
                    self._injector.fire(
                        "engine_step", detail=f"shard{shard_pos}"
                    )
                if (
                    self._crash_sweeps
                    and self._sweeps_done >= self._crash_sweeps
                    and (shard_pos > 0 or len(self.shards) == 1)
                ):
                    # Process-death drill (FLS_WAL_CRASH_SWEEPS): SIGKILL
                    # mid-sweep — no cleanup, no flush beyond what the
                    # WAL already handed the kernel. The restart harness
                    # asserts token-identical replay from exactly here.
                    os.kill(os.getpid(), signal.SIGKILL)
                if not layer_idxs:
                    continue
                for wave in self.batcher.waves:
                    if wave.steps == 0:
                        with obs_trace.span(
                            "prefill_shard", cat="serve", sweep_id=sweep_id,
                            shard_idx=shard_pos, wave_id=wave.wave_id,
                        ):
                            self._prefill_shard(
                                wave, shard_pos, layer_idxs, segments
                            )
                    elif wave.state.spec is not None:
                        # Speculative wave: this sweep is one K+1-slot
                        # batch verify pass instead of a 1-token step.
                        with obs_trace.span(
                            "decode_shard", cat="serve", sweep_id=sweep_id,
                            shard_idx=shard_pos, wave_id=wave.wave_id,
                        ):
                            self._spec_decode_shard(
                                wave, shard_pos, layer_idxs, segments
                            )
                    else:
                        with obs_trace.span(
                            "decode_shard", cat="serve", sweep_id=sweep_id,
                            shard_idx=shard_pos, wave_id=wave.wave_id,
                        ):
                            self._decode_shard(
                                wave, shard_pos, layer_idxs, segments
                            )
                if self._source is not None:  # streamed: set and cleared on this thread
                    # Every wave's steps for this shard are enqueued: the
                    # next upload may go out behind them.
                    self._source.dispatched()
            # Back at the boundary: the next shard-0 admission is NOW.
            self._sweep_pos = 0

    def _prefill_shard(self, wave, shard_pos, layer_idxs, segments) -> None:
        st: _WaveState = wave.state
        act_dev = self._act_dev()
        dec_names = (
            self._shard_decoder_layers(layer_idxs)
            if st.adapter_scales is not None
            else ()
        )
        for b in range(len(st.blocks)):
            prefix_ids, suffix_ids, prefix_len, suffix_eos = st.meta[b]
            # Pool-reuse block: every row leases a SEALED same-prefix pool
            # entry — the prefix stream never runs. The suffix stream
            # depends on the prefix only through its post-RoPE (k, v)
            # (llama.prefix_suffix_layer), so feeding the assembled pages
            # to the suffix-only scan is bit-identical, at zero prefix
            # prefill recompute.
            reuse = b in st.reuse_blocks
            total_len = longrope_total_len(
                self.model_cfg, prefix_len, suffix_eos
            )
            if layer_idxs[0] == 0:
                ph, sh = None, None
            else:
                ph, sh = st.kv_store.get(("h", b), act_dev)
            di = 0
            dec_off = 0
            for kind, params in segments:
                if kind == "embed":
                    if reuse:
                        # Suffix embeddings only; the prefix hidden stream
                        # stays dead (None rides the ("h", b) handoff as
                        # an empty pytree leaf).
                        ph, sh = None, llama.embed(
                            params, suffix_ids, self.dtype, self.model_cfg
                        )
                    else:
                        ph, sh = _embed_block(
                            self.model_cfg, self.dtype, params,
                            prefix_ids, suffix_ids,
                        )
                elif kind == "decoders":
                    if st.adapter_scales is not None:
                        k = jax.tree_util.tree_leaves(params)[0].shape[0]
                        delta = self._segment_delta(
                            st, shard_pos, di,
                            dec_names[dec_off:dec_off + k], b, act_dev,
                        )
                        dec_off += k
                    else:
                        delta = None
                    if reuse:
                        rows_k, rows_v = [], []
                        for i in st.blocks[b]:
                            k_np, v_np = self._kv_pool.assemble(
                                st.pool_handles[i], (shard_pos, di)
                            )
                            rows_k.append(k_np)
                            rows_v.append(v_np)
                        kp = jax.device_put(
                            np.stack(rows_k, axis=1), act_dev
                        )
                        vp = jax.device_put(
                            np.stack(rows_v, axis=1), act_dev
                        )
                        sh, kv_s = _suffix_prefill_decoders(
                            self.model_cfg, self._use_pallas, None, params,
                            {"kp": kp, "vp": vp}, sh, prefix_len, total_len,
                            delta=delta,
                        )
                        kv = {
                            "kp": kp, "vp": vp,
                            "ks": kv_s["ks"], "vs": kv_s["vs"],
                        }
                        ph = None
                    else:
                        ph, sh, kv = _prefill_decoders(
                            self.model_cfg, self._use_pallas, None, params,
                            ph, sh, prefix_len, total_len, delta=delta,
                        )
                        if self._kv_pool is not None and st.pool_handles:
                            # Bank this segment's prefix KV into the pool
                            # (per-row pages; chunks another prefix
                            # already contributed dedup in place).
                            k_np, v_np = jax.device_get(
                                (kv["kp"], kv["vp"])
                            )
                            for row, i in enumerate(st.blocks[b]):
                                self._kv_pool.contribute(
                                    st.pool_handles[i], (shard_pos, di),
                                    k_np[:, row], v_np[:, row],
                                )
                    kv = extend_gen_kv(
                        kv, st.slots, self.dtype, device=act_dev
                    )
                    st.kv_store.put(("kv", shard_pos, di, b), kv)
                    di += 1
                elif kind == "norm":
                    sh = _norm_block(
                        self.model_cfg, params, sh, suffix_eos
                    )
                    ph = None
                else:  # head
                    dist = np.asarray(
                        jax.device_get(
                            _head_block(self.model_cfg, params, sh)
                        )
                    )
                    st.scores[b].append(dist)
                    st.tok_hist[b].append(np.argmax(dist, axis=-1))
            if layer_idxs[-1] != self._n_layers - 1:
                st.kv_store.put(("h", b), (ph, sh))

    def _decode_shard(self, wave, shard_pos, layer_idxs, segments) -> None:
        st: _WaveState = wave.state
        act_dev = self._act_dev()
        dec_names = (
            self._shard_decoder_layers(layer_idxs)
            if st.adapter_scales is not None
            else ()
        )
        t = jnp.int32(wave.steps - 1)  # this step's generated-KV slot
        for b in range(len(st.blocks)):
            # Blocks whose every request already resolved sit the sweep out
            # (statuses only change in _post_sweep, so liveness is stable
            # within a sweep): a mixed-budget wave must not keep paying
            # full decode + head + host transfer for finished rows until
            # its slowest request completes. Rows are ENTRIES (possibly
            # prefix-coalesced groups), so the check spans their members.
            if all(
                r.status.terminal
                for i in st.blocks[b]
                for r in wave.entries[i].requests
            ):
                continue
            _, _, prefix_len, suffix_eos = st.meta[b]
            x = (
                None
                if layer_idxs[0] == 0
                else st.kv_store.get(("x", b), act_dev)
            )
            di = 0
            dec_off = 0
            for kind, params in segments:
                if kind == "embed":
                    x = llama.embed(
                        params,
                        jnp.asarray(
                            st.tok_hist[b][-1][..., None], jnp.int32
                        ),
                        self.dtype,
                        self.model_cfg,
                    )
                elif kind == "decoders":
                    if st.adapter_scales is not None:
                        k = jax.tree_util.tree_leaves(params)[0].shape[0]
                        delta = self._segment_delta(
                            st, shard_pos, di,
                            dec_names[dec_off:dec_off + k], b, act_dev,
                        )
                        dec_off += k
                    else:
                        delta = None
                    kv = st.kv_store.get(("kv", shard_pos, di, b), act_dev)
                    x, kv = _decode_decoders(
                        self.model_cfg, self._use_pallas, None, params,
                        kv, x, prefix_len, suffix_eos, t, delta=delta,
                    )
                    st.kv_store.put(("kv", shard_pos, di, b), kv)
                    di += 1
                elif kind == "norm":
                    st.norm_p = params  # applied in the head shard
                else:  # head
                    assert st.norm_p is not None
                    dist = np.asarray(
                        jax.device_get(
                            _decode_norm_head(
                                self.model_cfg,
                                jax.device_put(st.norm_p, act_dev),
                                params,
                                x,
                            )
                        )
                    )
                    st.scores[b].append(dist)
                    st.tok_hist[b].append(np.argmax(dist, axis=-1))
            if layer_idxs[-1] != self._n_layers - 1:
                st.kv_store.put(("x", b), x)

    def _init_spec(self, wave) -> None:
        """Arm a freshly prefilled wave's speculative state: one
        SpecVerifier per block, seeded from the prefill's distributions
        and picks. Per-suffix draft contexts are prefix + suffix + first
        pick — a preemption-resumed request's generated-so-far tokens are
        already folded INTO its suffix ids (``_tokenize_entry``), so
        resume work rides the draft context and is never re-drafted
        stale; a coalesced entry's suffix rows span several requests but
        share the prefix, and each drafts per-suffix over its own row.
        Per-suffix budgets come from the OWNING request (mixed budgets in
        one wave finish early per request, exactly like the plain path)."""
        st: _WaveState = wave.state
        st.spec = {}
        st.spec_classes = {}
        # Resident draft model (when configured) replaces prompt-lookup
        # drafting; verification is draft-agnostic either way, so the
        # choice moves only acceptance, never a token.
        draft_fn = (
            self._draft_model.propose
            if self._draft_model is not None
            else None
        )
        for b, idxs in enumerate(st.blocks):
            bsz = len(idxs)
            s_b = st.toks[idxs[0]].suffix_ids.shape[0]
            budgets = np.ones((bsz, s_b), np.int64)
            active = np.zeros((bsz, s_b), bool)
            # [B][S] owning request's SLO class (None = bucket padding):
            # feeds the per-class fls_spec_* split and, adaptive, the
            # controller's per-row k assignment.
            classes: list[list] = [[None] * s_b for _ in range(bsz)]
            for row, e_idx in enumerate(idxs):
                e = wave.entries[e_idx]
                for (off, cnt), member in zip(e.slices, e.requests):
                    budgets[row, off : off + cnt] = (
                        member.max_new_tokens - member.resume_len
                    )
                    active[row, off : off + cnt] = True
                    for s in range(off, off + cnt):
                        classes[row][s] = member.slo_class
            # Padding rows: budget 1 (frozen immediately; their constant
            # history fill stays minimal).
            d0, t0 = st.scores[b][0], st.tok_hist[b][0]
            st.spec_classes[b] = classes
            st.spec[b] = SpecVerifier(
                self._spec_k,
                draft_fn,
                draft_contexts([st.toks[i] for i in idxs], t0),
                budgets,
                d0,
                t0,
                active=active,
            )

    def _spec_decode_shard(self, wave, shard_pos, layer_idxs, segments) -> None:
        """One shard of a speculative verify pass: embed the per-suffix
        (last accepted + K drafts) windows, run the K+1-token decode scan
        at per-suffix slot offsets, and at the head accept the longest
        matching draft prefix — all inside the SAME weight sweep the
        other waves' prefill/decode segments ride."""
        st: _WaveState = wave.state
        act_dev = self._act_dev()
        dec_names = (
            self._shard_decoder_layers(layer_idxs)
            if st.adapter_scales is not None
            else ()
        )
        for b in range(len(st.blocks)):
            v = st.spec[b]
            # Finished blocks sit the sweep out: every suffix at budget,
            # or every owning request already terminal.
            if v.done or all(
                r.status.terminal
                for i in st.blocks[b]
                for r in wave.entries[i].requests
            ):
                continue
            _, _, prefix_len, suffix_eos = st.meta[b]
            x = (
                None
                if layer_idxs[0] == 0
                else st.kv_store.get(("x", b), act_dev)
            )
            di = 0
            dec_off = 0
            for kind, params in segments:
                if kind == "embed":
                    if self._spec_ctrl is not None:
                        # Adaptive k: the controller assigns this pass's
                        # per-row draft depth (class-priority funding,
                        # 0 everywhere while pressure-backed-off) before
                        # the drafts are fixed.
                        v.set_pass_k(
                            self._spec_ctrl.assign(
                                st.spec_classes[b], v.budgets - v.g
                            )
                        )
                    # Drafts are fixed per pass BEFORE the sweep's
                    # decoders run; base rides wave state to every
                    # decoder segment of this sweep.
                    fed, base = v.begin_pass()
                    st.spec_base[b] = base
                    obs_trace.instant(
                        "spec_draft", cat="spec", wave_id=wave.wave_id,
                        # Suffixes that DRAFTED this pass (begin_pass
                        # skips remaining==1), matching spec_verify's
                        # drafted accounting.
                        block=b, drafted=int((v.budgets - v.g > 1).sum()),
                    )
                    x = llama.embed(
                        params,
                        jnp.asarray(fed, jnp.int32),
                        self.dtype,
                        self.model_cfg,
                    )
                elif kind == "decoders":
                    if st.adapter_scales is not None:
                        k = jax.tree_util.tree_leaves(params)[0].shape[0]
                        delta = self._segment_delta(
                            st, shard_pos, di,
                            dec_names[dec_off:dec_off + k], b, act_dev,
                        )
                        dec_off += k
                    else:
                        delta = None
                    kv = st.kv_store.get(("kv", shard_pos, di, b), act_dev)
                    x, kv = _spec_decoders(
                        self.model_cfg, None, params, kv, x,
                        prefix_len, suffix_eos,
                        jnp.asarray(st.spec_base[b]), delta=delta,
                    )
                    st.kv_store.put(("kv", shard_pos, di, b), kv)
                    di += 1
                elif kind == "norm":
                    st.norm_p = params  # applied in the head shard
                else:  # head
                    assert st.norm_p is not None
                    dist = np.asarray(
                        jax.device_get(
                            _spec_norm_head(
                                self.model_cfg,
                                jax.device_put(st.norm_p, act_dev),
                                params,
                                x,
                            )
                        )
                    )
                    before = (v.drafted, v.accepted, v.rejected)
                    emitted = v.finish_pass(dist)
                    d_draft = v.drafted - before[0]
                    d_acc = v.accepted - before[1]
                    d_rej = v.rejected - before[2]
                    # Per-class split of the pass's draft economy (the
                    # fls_spec_by_class_* family): the per-row drafted/
                    # accepted the verifier just recorded, keyed by each
                    # row's owning request's SLO class. Sums equal the
                    # aggregate deltas exactly (padding rows draft 0).
                    per_cls: dict[str, list[int]] = {}
                    classes = st.spec_classes.get(b)
                    if classes is not None:
                        for r_i in range(v.last_drafted.shape[0]):
                            for s_i in range(v.last_drafted.shape[1]):
                                dk = int(v.last_drafted[r_i, s_i])
                                if dk <= 0:
                                    continue
                                cls = classes[r_i][s_i]
                                acc = per_cls.setdefault(cls, [0, 0])
                                acc[0] += dk
                                acc[1] += int(
                                    v.last_accepted[r_i, s_i]
                                )
                    if per_cls:
                        for cls, (c_d, c_a) in per_cls.items():
                            self.metrics.spec_count(
                                drafted=c_d, accepted=c_a,
                                rejected=c_d - c_a, slo_class=cls,
                            )
                            if self._spec_ctrl is not None:
                                self._spec_ctrl.observe(cls, c_d, c_a)
                    else:
                        self.metrics.spec_count(
                            drafted=d_draft, accepted=d_acc, rejected=d_rej
                        )
                    obs_trace.instant(
                        "spec_verify", cat="spec", wave_id=wave.wave_id,
                        block=b, accepted=int(d_acc), drafted=int(d_draft),
                        emitted=int(emitted.sum()),
                    )
            if layer_idxs[-1] != self._n_layers - 1:
                st.kv_store.put(("x", b), x)

    # -- post-sweep bookkeeping --------------------------------------------

    def _post_sweep(self, sweep_wall_s: float) -> None:
        now = time.monotonic()
        emitted = 0
        for wave in self.batcher.waves:
            prefilled = wave.steps == 0
            wave.steps += 1
            if prefilled:
                self.metrics.count("prefills")
                st0 = wave.state
                if self._kv_pool is not None and st0 is not None:
                    # The wave's prefill just completed: seal each freshly
                    # prefilled entry (every decoder segment contributed),
                    # making it reusable by later same-prefix waves, and
                    # bank coalesced entries' savings from the pool's
                    # actual page bookkeeping.
                    for i, handle in st0.pool_handles.items():
                        if st0.loc[i][0] in st0.reuse_blocks:
                            continue
                        self._kv_pool.seal(handle)
                        e = wave.entries[i]
                        if self._sched is not None and len(e.requests) > 1:
                            self._note_coalesced(
                                wave, e, st0.toks[i], handle
                            )
                if self._spec_k > 0 and wave.max_steps > 1:
                    # Arm the verify passes off the prefill's picks; the
                    # next sweep for this wave is a draft+verify pass.
                    self._init_spec(wave)
            st = wave.state
            if (
                self._adapter_store is not None
                and st is not None
                and st.adapter_scales is not None
            ):
                # Per-sweep charge: how many of this wave's batch rows
                # decoded under an adapter delta this sweep.
                rows = sum(
                    int((g != 0).sum()) for g in st.adapter_g.values()
                )
                if rows:
                    self._adapter_store.note_applied(rows, 0)
            for r in wave.requests:
                if r.status.terminal:
                    continue
                prev_emitted = r.tokens_emitted
                if prefilled and r.first_token_at is None:
                    r.first_token_at = now
                    self.metrics.observe_ttft(now - r.arrival, r.slo_class)
                    obs_trace.instant(
                        "ttft", cat="serve", wave_id=wave.wave_id,
                        request_id=r.request_id,
                        seconds=round(now - r.arrival, 6),
                    )
                if st is not None and st.spec is not None:
                    # Speculative wave: a sweep advances each suffix by
                    # 1..K+1 accepted tokens; the REQUEST's progress is
                    # the slowest of its suffix rows (the result shape is
                    # rectangular per request). An accepted run that
                    # crosses max_new_tokens finishes the request early —
                    # the cap below discards nothing (the verifier stops
                    # emitting at each suffix's own budget).
                    e_idx, s_off, s_cnt = wave.locate(r)
                    b, row = st.loc[e_idx]
                    v = st.spec[b]
                    prog = min(
                        v.emitted(row, s_off + s) for s in range(s_cnt)
                    )
                    new_total = min(
                        r.resume_len + prog, r.max_new_tokens
                    )
                    emitted += max(new_total - r.tokens_emitted, 0)
                    r.tokens_emitted = new_total
                elif r.tokens_emitted < r.max_new_tokens:
                    r.tokens_emitted += 1
                    emitted += 1
                if self._wal is not None and r.tokens_emitted > prev_emitted:
                    # Sweep-boundary progress record: the watermark plus
                    # the token ids this sweep emitted (a DELTA — per-
                    # request WAL cost stays linear in its output). The
                    # ids are forensics/accounting; replay re-derives
                    # them bit-identically (greedy decode).
                    self._wal.progress(
                        r, tok_delta=self._wal_tok_delta(wave, r, prev_emitted)
                    )
                if r.tokens_emitted >= r.max_new_tokens:
                    self._resolve(wave, r)
        self.metrics.count("sweeps")
        self._sweeps_done += 1
        # SLO budgets (obs/slo.py): rate-limited re-evaluation so budget
        # exhaustion journals promptly even when nothing scrapes.
        self._slo.maybe_check()
        if emitted:
            self.metrics.count("tokens_emitted", emitted)
            self.metrics.observe_token_latency(sweep_wall_s)
            obs_trace.instant(
                "token_latency", cat="serve",
                seconds=round(sweep_wall_s, 6), tokens=emitted,
            )
        for w in self.batcher.retire_done():
            if w.state is not None:
                w.state.kv_store.clear()
                self._release_pool_handles(w.state)

    def _wal_tok_delta(self, wave: Wave, r: Request, prev_emitted: int):
        """Token ids this sweep emitted for ``r`` (WAL progress payload):
        ``[step][suffix]`` int lists. Speculative waves keep per-suffix
        ragged histories, so they journal the watermark only (None) —
        replay never needs the ids, it re-derives them greedily."""
        st = wave.state
        if st is None or st.spec is not None:
            return None
        e_idx, s_off, s_cnt = wave.locate(r)
        b, row = st.loc[e_idx]
        hist = st.tok_hist[b]
        lo = prev_emitted - r.resume_len
        hi = r.tokens_emitted - r.resume_len
        if lo < 0 or hi > len(hist):
            return None  # resume bookkeeping edge: watermark only
        return [
            [int(t) for t in hist[step][row, s_off : s_off + s_cnt]]
            for step in range(lo, hi)
        ]

    def _resolve(self, wave: Wave, r: Request) -> None:
        st: _WaveState = wave.state
        e_idx, s_off, s_cnt = wave.locate(r)
        b, row = st.loc[e_idx]
        # Steps served by THIS wave; a preemption-resumed request stitches
        # its pre-preemption steps (resume_scores/resume_tokens) in front,
        # so the caller sees one uninterrupted [n_suffixes, n, vocab]
        # stream regardless of how many boundaries interrupted it.
        rem = r.max_new_tokens - r.resume_len
        if st.spec is not None:
            # Speculative wave: histories are ragged per suffix inside
            # the verifier; re-slice this request's rows step-major.
            sc, tk = st.spec[b].request_steps(row, s_off, s_cnt, rem)
            step_scores = list(r.resume_scores) + sc
            step_tokens = list(r.resume_tokens) + tk
        else:
            step_scores = list(r.resume_scores) + [
                st.scores[b][t][row, s_off : s_off + s_cnt]
                for t in range(rem)
            ]
            step_tokens = list(r.resume_tokens) + [
                st.tok_hist[b][t][row, s_off : s_off + s_cnt]
                for t in range(rem)
            ]
        n = r.max_new_tokens
        scores = np.stack(step_scores, axis=1)
        tokens = np.stack(step_tokens, axis=1)
        updated = (
            r.prefix,
            tuple(
                s + self.raw_tokenizer.decode(tokens[s_i])
                for s_i, s in enumerate(r.suffixes)
            ),
        )
        latency = time.monotonic() - r.arrival
        if r.resolve(scores, updated, tokens):
            self.metrics.count("completed")
            self.metrics.observe_request_latency(latency, r.slo_class)
            obs_trace.instant(
                "request_finish", cat="serve", wave_id=wave.wave_id,
                request_id=r.request_id, tokens=int(n),
            )


__all__ = ["ServeEngine"]
