#!/usr/bin/env python3
"""Repo entry point for the trace analyzer (same CLI as
``python -m flexible_llm_sharding_tpu.cli trace-report``): link
utilization, why the device stood idle between shards, per-phase sweep
breakdown, and TTFT / per-token latency quantiles from a ``--trace``
recording (Chrome trace-event JSON or JSONL). ``--trace`` also accepts
an incident-bundle directory (obs/incident.py, docs/incidents.md) —
its embedded ``trace.json`` is analyzed; render the full bundle
timeline with ``cli incidents analyze`` instead."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flexible_llm_sharding_tpu.obs.report import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
