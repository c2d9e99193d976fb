"""Online serving subsystem: shard-aware continuous batching over the
streaming decode runtime.

Every offline entry point (cli scoring, kv decode) is a batch run
over a fixed prompt set; this package turns the same runtime into a server:

- ``request``  — request/response dataclasses + per-request state machine.
- ``queue``    — thread-safe admission queue: capacity backpressure
  (reject-with-reason), deadline eviction, drain-on-shutdown.
- ``batcher``  — shard-aware continuous batcher: coalesces queued requests
  into waves, admitting new waves only at shard-0 boundaries of the decode
  sweep so mid-stream joins never re-trigger prefill for in-flight
  requests (the Orca iteration-level-scheduling idea mapped onto the
  weight-sweep boundary this design naturally has).
- ``engine``   — the serving loop: drives prefill/decode via the existing
  jitted runtime blocks, supports graceful drain and shutdown, resolves
  per-request futures/callbacks, and feeds utils.metrics.ServingMetrics.
- ``router``   — shard-phase-aware replica ranking: dispatch to the
  replica whose sweep reaches its next shard-0 admission point soonest,
  weighted against normalized queue depth.
- ``fleet``    — N engines behind the router: health-driven draining and
  hard-fail (registry counters + sweep-watermark liveness), exactly-once
  re-dispatch of a dead replica's requests, elastic join/leave, and the
  replica-level chaos sites (replica_kill / replica_stall).
- ``sched``    — the multi-tenant sweep scheduler (docs/scheduling.md):
  SLO classes with strict priority and sweep-boundary preemption of
  best-effort waves, per-tenant deficit-round-robin fairness and
  token-bucket rate limits, and cross-request prefix coalescing (one
  shared prefill for N same-prefix requests).
- ``wal``      — crash-safe serving (docs/recovery.md): the durable
  append-only request ledger (crc-framed segments, fsync policy,
  rotation + terminal-only compaction, torn tails truncated not fatal).
- ``recovery`` — startup replay: re-admit every open WAL request through
  the normal scheduler core, restore checksummed spilled prefix-KV when
  present, outputs token-identical to an uninterrupted run.
"""

from flexible_llm_sharding_tpu.serve.request import (  # noqa: F401
    DeadlineExceeded,
    Overloaded,
    QueueFull,
    Request,
    RequestResult,
    RequestStatus,
    RequestTooLarge,
    RestartPending,
    ServeClosed,
    ServeFuture,
    WaveAborted,
)
from flexible_llm_sharding_tpu.serve.queue import AdmissionQueue  # noqa: F401
from flexible_llm_sharding_tpu.serve.wal import RequestWAL, wal_for  # noqa: F401
from flexible_llm_sharding_tpu.serve import recovery  # noqa: F401
from flexible_llm_sharding_tpu.serve.batcher import ShardAwareBatcher  # noqa: F401
from flexible_llm_sharding_tpu.serve.engine import ServeEngine  # noqa: F401
from flexible_llm_sharding_tpu.serve.router import Router  # noqa: F401
from flexible_llm_sharding_tpu.serve.fleet import (  # noqa: F401
    ReplicaFleet,
    ReplicaKilled,
)
from flexible_llm_sharding_tpu.serve.sched import (  # noqa: F401
    RateLimited,
    SweepScheduler,
    UnknownSLOClass,
)

__all__ = [
    "AdmissionQueue",
    "DeadlineExceeded",
    "Overloaded",
    "QueueFull",
    "RateLimited",
    "ReplicaFleet",
    "ReplicaKilled",
    "Request",
    "RequestResult",
    "RequestStatus",
    "RequestTooLarge",
    "RequestWAL",
    "RestartPending",
    "Router",
    "ServeClosed",
    "ServeEngine",
    "ServeFuture",
    "ShardAwareBatcher",
    "SweepScheduler",
    "UnknownSLOClass",
    "WaveAborted",
    "recovery",
    "wal_for",
]
