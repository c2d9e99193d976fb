"""Unified observability: span tracing, the metrics registry, and the
trace analyzer.

Submodules (imported directly — this package root stays import-light so
hot-path modules can depend on it without cycles):

- ``obs.trace``    bounded-ring span tracer with correlation ids
  (sweep_id / shard_idx / wave_id / request_id), exported as Chrome
  trace-event JSON (Perfetto-loadable) or JSONL. Zero-cost no-op when
  disabled.
- ``obs.registry`` the process metrics registry every subsystem's
  counters register into, with Prometheus text exposition and an
  optional HTTP endpoint (the serve engine's ``--metrics_port``).
- ``obs.report``   the trace analyzer behind ``cli trace-report``:
  link utilization, why the device stood idle between shards, per-phase sweep
  breakdown, TTFT / per-token latency quantiles — plus the
  incident-bundle analyzer behind ``cli incidents``.
- ``obs.events``   the black-box flight recorder's durable append-only
  JSONL event journal (docs/incidents.md): every failure-path site
  writes through it; zero-cost no-op when disabled.
- ``obs.incident`` severity-triggered incident bundles: journal tail +
  metrics snapshot + trace ring + resolved config, debounced and
  disk-budgeted.
- ``obs.slo``      SLO targets + error budgets over the per-class
  latency streams, exported as the ``fls_slo_*`` gauge family.
"""
