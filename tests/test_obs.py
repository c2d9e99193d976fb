"""Unified observability (obs/): the span tracer's ring/drop semantics and
exports, the metrics registry + Prometheus endpoint, the registry-backed
serve stats line (layout pinned — the line CI and operators grep must not
drift), the StepWatchdog's structured stall event, the trace analyzer's
derived numbers, and end-to-end traces from a real streamed run and a
real serve run."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

from flexible_llm_sharding_tpu.config import FrameworkConfig, ServeConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.obs import report as obs_report
from flexible_llm_sharding_tpu.obs import trace as obs_trace
from flexible_llm_sharding_tpu.obs.registry import (
    MetricsRegistry,
    MetricsServer,
)
from flexible_llm_sharding_tpu.obs.trace import Tracer
from flexible_llm_sharding_tpu.utils.checkpoint import save_params
from flexible_llm_sharding_tpu.utils.metrics import (
    ServingMetrics,
    StepWatchdog,
    assemble_serve_stats,
)

from tests.fake_tokenizer import FakeTokenizer

PROMPTS = [
    ("The capital of France", (" is Paris", " is Rome")),
    ("Two plus two equals", (" four", " five")),
]


@pytest.fixture(scope="module")
def model(tiny_cfg, tmp_path_factory):
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    d = tmp_path_factory.mktemp("tiny_model_obs")
    save_params(jax.tree.map(np.asarray, params), str(d), tiny_cfg)
    return str(d)


def _fw(model_dir, **kw):
    base = dict(
        model_path=model_dir,
        layer_num_per_shard=1,
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
    )
    base.update(kw)
    return FrameworkConfig(**base)


@pytest.fixture()
def process_tracer():
    """Enable the process tracer for one test; restore + clear after so
    traces never bleed between tests."""
    t = obs_trace.TRACER
    was = t.enabled
    t.clear()
    t.enable()
    yield t
    t.disable()
    t.clear()
    if was:
        t.enable()


# ---------------------------------------------------------------------------
# Tracer: ring, drops, zero-cost disabled path, exports
# ---------------------------------------------------------------------------

def test_tracer_disabled_records_nothing_and_shares_null_span():
    t = Tracer()
    assert not t.enabled
    s1 = t.span("a")
    s2 = t.span("b")
    # The disabled path allocates nothing: one shared no-op object.
    assert s1 is s2
    with t.span("x", cat="c", k=1):
        pass
    t.instant("y")
    assert len(t) == 0
    assert t.stats()["trace_spans"] == 0


def test_tracer_ring_overflow_drops_oldest_and_counts():
    t = Tracer(capacity=10)
    t.enabled = True  # direct: unit test must not touch the process registry
    for i in range(25):
        t.instant("ev", i=i)
    assert len(t) == 10
    assert t.drops == 15
    assert t.stats()["trace_drops"] == 15
    # Oldest dropped, NEWEST kept: the ring holds the trailing window.
    kept = [s["i"] for s in t.snapshot()]
    assert kept == list(range(15, 25))


def test_tracer_span_timing_and_attrs():
    t = Tracer()
    t.enabled = True
    with t.span("work", cat="test", sweep_id=7, shard_idx=3):
        time.sleep(0.01)
    (rec,) = t.snapshot()
    assert rec["name"] == "work" and rec["cat"] == "test"
    assert rec["sweep_id"] == 7 and rec["shard_idx"] == 3
    assert rec["dur_s"] >= 0.009
    assert rec["tid"] == threading.get_ident()


def test_tracer_exports_chrome_and_jsonl(tmp_path):
    t = Tracer(capacity=100)
    t.enabled = True
    with t.span("s", cat="c", sweep_id=1):
        pass
    t.instant("i", cat="c", request_id="r-1")
    chrome = tmp_path / "t.json"
    jsonl = tmp_path / "t.jsonl"
    t.write(str(chrome))
    t.write(str(jsonl))
    doc = json.loads(chrome.read_text())
    evs = doc["traceEvents"]
    # Perfetto-loadable: complete ("X") spans with us timestamps, instant
    # ("i") events, and the trace_meta drop-count record.
    assert any(e.get("ph") == "X" and e["name"] == "s" for e in evs)
    assert any(e.get("ph") == "i" and e["name"] == "i" for e in evs)
    meta = [e for e in evs if e["name"] == "trace_meta"]
    assert meta and meta[0]["args"]["trace_drops"] == 0
    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    assert {ln["name"] for ln in lines} == {"s", "i", "trace_meta"}
    span = next(ln for ln in lines if ln["name"] == "s")
    assert "dur_s" in span and span["sweep_id"] == 1


def test_jsonl_export_carries_drop_count():
    """Ring overflow must be detectable in BOTH export formats — a
    truncated timeline read as the full run is the silent loss the
    bounded ring promises never happens."""
    t = Tracer(capacity=4)
    t.enabled = True
    for i in range(9):
        t.instant("ev", i=i)
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        p = f"{d}/t.jsonl"
        t.write(p)
        rep = obs_report.analyze(obs_report.load_trace(p))
    assert rep["trace_drops"] == 5


# ---------------------------------------------------------------------------
# StepWatchdog: the stall is a structured span event, not just an exception
# ---------------------------------------------------------------------------

def test_watchdog_abort_emits_structured_span_event(process_tracer):
    fired = threading.Event()
    wd = StepWatchdog(
        "test-sweep", abort_s=0.05, on_stall=lambda idle, tok: fired.set(),
        poll_s=0.01,
    )
    try:
        wd.arm(token="src")
        assert fired.wait(timeout=5.0)
    finally:
        wd.close()
    stalls = [
        s for s in process_tracer.snapshot() if s["name"] == "watchdog_stall"
    ]
    assert stalls, "stall must land in the trace as a structured event"
    ev = stalls[0]
    assert ev["cat"] == "serve"
    assert ev["desc"] == "test-sweep"
    assert ev["idle_s"] >= 0.05
    assert wd.stats() == {"stalls": 1}


# ---------------------------------------------------------------------------
# Metrics registry + Prometheus endpoint
# ---------------------------------------------------------------------------

def test_registry_collect_and_prometheus_text():
    reg = MetricsRegistry()
    reg.register("a", lambda: {"x": 1, "nested": {"y": 2.5}})

    class Src:
        def stats(self):
            return {"z": 3}

    reg.register("b", Src())
    got = reg.collect()
    assert got == {"a": {"x": 1, "nested": {"y": 2.5}}, "b": {"z": 3}}
    text = reg.prometheus_text()
    assert "# TYPE fls_a_x gauge\nfls_a_x 1" in text
    assert "fls_a_nested_y 2.5" in text
    assert "fls_b_z 3" in text
    # Re-registration replaces (last wins); unregister removes.
    reg.register("b", lambda: {"z": 9})
    assert reg.collect()["b"] == {"z": 9}
    reg.unregister("a")
    assert "a" not in reg.collect()


def test_registry_broken_source_reports_error_not_raise():
    reg = MetricsRegistry()

    def broken():
        raise RuntimeError("wedged")

    reg.register("bad", broken)
    assert reg.collect()["bad"] == {"collect_error": 1}
    assert "fls_bad_collect_error 1" in reg.prometheus_text()


def test_metrics_server_scrape():
    reg = MetricsRegistry()
    reg.register("s", lambda: {"up": 1})
    srv = MetricsServer(reg, port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics", timeout=10).read()
        assert b"fls_s_up 1" in text
        js = json.loads(
            urllib.request.urlopen(f"{base}/metrics.json", timeout=10).read()
        )
        assert js == {"s": {"up": 1}}
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=10)
    finally:
        srv.close()
    srv.close()  # idempotent


# ---------------------------------------------------------------------------
# The serve stats line: ONE registry-backed assembly path, layout pinned
# ---------------------------------------------------------------------------

class _FakeCache:
    def stats(self):
        return {"hits": 3, "misses": 1, "hit_rate": 0.75}


class _FakeTier:
    def stats(self):
        return {
            "pinned_bytes": 1024,
            "stream_bytes_saved": 4096,
            "pin_hits": 2,
        }


def test_stats_line_layout_regression():
    """Regression pin for the consolidation: engine.stats() and
    ServingMetrics.snapshot() are ONE registry-backed path, and the
    line's layout — the keys CI greps and operators parse — is exactly
    this."""
    m = ServingMetrics()
    m.count("admitted", 2)
    m.count("completed", 1)
    m.gauge("queue_depth", 5)
    m.observe_ttft(0.25, "interactive")
    m.retries.record("shard_read", retries=1, backoff_s=0.05)
    m.integrity.count("reread_heals")
    m.host_cache = _FakeCache()
    m.residency = _FakeTier()
    line = m.snapshot()
    # Top-level contract: event marker, every known counter (pre-seeded),
    # gauges, latency summaries, and the nested recorder blocks with
    # their top-level convenience keys.
    for key in ServingMetrics.KNOWN_COUNTERS:
        assert key in line, f"counter {key} missing from the stats line"
    assert line["event"] == "serve_stats"
    assert line["admitted"] == 2 and line["completed"] == 1
    assert line["queue_depth"] == 5
    assert set(line["ttft_s"]) == {"count", "mean", "p50", "p95", "p99", "max"}
    assert line["token_latency_s"] == {"count": 0}
    # Per-SLO-class breakdowns (serve/sched): the three classes are
    # pre-seeded so "no samples yet" is scrapeable, and a class-tagged
    # observation lands in its class summary as well as the aggregate.
    for block in ("ttft_by_class", "latency_by_class"):
        assert set(line[block]) == {"best_effort", "interactive", "standard"}
    assert line["ttft_by_class"]["interactive"]["count"] == 1
    assert set(line["ttft_by_class"]["interactive"]) == {
        "count", "mean", "p50", "p95", "p99", "max",
    }
    assert line["ttft_by_class"]["standard"] == {"count": 0}
    assert line["latency_by_class"]["best_effort"] == {"count": 0}
    assert line["io_retries"]["shard_read"]["retries"] == 1
    assert line["integrity"]["reread_heals"] == 1
    assert line["host_cache_hit_rate"] == 0.75
    assert line["host_cache"]["hits"] == 3
    assert line["pinned_bytes"] == 1024
    assert line["stream_bytes_saved"] == 4096
    assert line["residency"]["pin_hits"] == 2
    # Speculative block: the aggregate family plus the per-SLO-class
    # split, all three classes pre-seeded (scrapeable zeros) with the
    # tagged class carrying the deltas.
    m.spec_count(drafted=4, accepted=3, rejected=1, slo_class="interactive")
    line = m.snapshot()
    spec = line["spec"]
    assert spec["drafted_tokens"] == 4 and spec["accepted_tokens"] == 3
    assert set(spec["by_class"]) == {"best_effort", "interactive", "standard"}
    assert spec["by_class"]["interactive"] == {
        "drafted_tokens": 4, "accepted_tokens": 3, "rejected_tokens": 1,
    }
    assert spec["by_class"]["standard"] == {
        "drafted_tokens": 0, "accepted_tokens": 0, "rejected_tokens": 0,
    }
    # The two-level flatten keeps the split on the Prometheus surface.
    text = m.registry.prometheus_text()
    assert "fls_spec_by_class_interactive_accepted_tokens 3" in text
    assert "fls_spec_by_class_standard_drafted_tokens 0" in text
    # The SAME collection renders the line: no second assembly path.
    assert assemble_serve_stats(m.registry.collect()) == line


def test_stats_line_omits_empty_recorder_blocks():
    m = ServingMetrics()
    line = m.snapshot()
    assert "io_retries" not in line  # no retries recorded
    assert "integrity" not in line  # all-zero integrity counters
    assert "host_cache" not in line and "residency" not in line
    # Detaching unregisters: attaching then clearing leaves no stale block.
    m.host_cache = _FakeCache()
    m.host_cache = None
    assert "host_cache" not in m.snapshot()


def test_stats_line_survives_broken_attached_source():
    """A wedged host_cache/residency source degrades to collect_error in
    the registry; the stats line must render around it — inside the serve
    loop a raising snapshot() would be promoted to an engine-fatal error,
    the exact outcome the degradation path exists to prevent."""
    m = ServingMetrics()

    class Broken:
        def stats(self):
            raise RuntimeError("wedged")

    m.host_cache = Broken()
    m.residency = Broken()
    line = m.snapshot()  # must not raise
    assert line["host_cache"] == {"collect_error": 1}
    assert line["residency"] == {"collect_error": 1}
    assert "host_cache_hit_rate" not in line
    assert "pinned_bytes" not in line


def test_metrics_close_retracts_only_own_process_mirrors():
    """A dead engine's process-wide mirrors retract on close(); a newer
    engine's same-name registrations survive (identity-checked), and
    process-level sources (host cache) are never torn down by a detach."""
    from flexible_llm_sharding_tpu.obs.registry import REGISTRY

    a = ServingMetrics()
    b = ServingMetrics()  # newer engine wins the process names
    a.close()
    # b's registrations survive a's teardown; the process collection
    # still carries the serve source.
    assert "serve" in REGISTRY.collect()
    b.close()
    assert "serve" not in REGISTRY.collect()
    # Process-level source registered by its owner is untouched by an
    # engine attaching/detaching a cache (mirror=False path).
    REGISTRY.register("host_cache", lambda: {"hit_rate": 1.0})
    c = ServingMetrics()
    c.host_cache = _FakeCache()
    c.host_cache = None
    c.close()
    assert REGISTRY.collect()["host_cache"] == {"hit_rate": 1.0}
    REGISTRY.unregister("host_cache")


def test_weak_source_releases_dead_instances():
    from flexible_llm_sharding_tpu.obs.registry import weak_source

    class Runner:
        def __init__(self):
            self.stats = {"x": 1}

    r = Runner()
    src = weak_source(r)
    assert src() == {"x": 1}
    del r
    import gc

    gc.collect()
    assert src() == {}  # dead runner vanishes instead of being pinned


def test_serving_metrics_prometheus_has_full_counter_family():
    """Pre-seeded counters make 'zero recoveries' scrapeable (distinct
    from 'recoveries not exported') — the smoke asserts this on a live
    endpoint; this pins it at the unit level."""
    m = ServingMetrics()
    text = m.registry.prometheus_text()
    for key in ("engine_recoveries", "waves_aborted", "source_restarts",
                "watchdog_stalls", "admitted"):
        assert f"fls_serve_{key} 0" in text
    # Per-class latency families pre-seed too (serve/sched): a scrape
    # can tell "no interactive traffic yet" from "not exported".
    for cls in ("interactive", "standard", "best_effort"):
        assert f"fls_serve_ttft_by_class_{cls}_count 0" in text
        assert f"fls_serve_latency_by_class_{cls}_count 0" in text


# ---------------------------------------------------------------------------
# Trace analyzer
# ---------------------------------------------------------------------------

def test_analyzer_derives_utilization_overlap_and_quantiles():
    # Synthetic timeline: 2 produce spans (0.2s each, waits 0.1s total),
    # two shards whose launches, shard-end waits and uploads are known,
    # serve latency instants with known quantiles.
    evs = [
        {"name": "shard_produce", "cat": "stream", "ts_s": 0.0, "dur_s": 0.2},
        {"name": "shard_load", "cat": "stream", "ts_s": 0.0, "dur_s": 0.15},
        {"name": "upload_dispatch", "cat": "stream", "ts_s": 0.15,
         "dur_s": 0.01},
        {"name": "upload", "cat": "stream", "ts_s": 0.15, "dur_s": 0.1,
         "sweep_id": 1, "shard_idx": 0},
        {"name": "shard_produce", "cat": "stream", "ts_s": 0.5, "dur_s": 0.2},
        {"name": "shard_load", "cat": "stream", "ts_s": 0.5, "dur_s": 0.2},
        # Two uploads in flight at once count once (the union).
        {"name": "upload", "cat": "stream", "ts_s": 0.2, "dur_s": 0.1,
         "sweep_id": 1, "shard_idx": 1},
        {"name": "upload", "cat": "stream", "ts_s": 0.7, "dur_s": 0.25,
         "sweep_id": 1, "shard_idx": 2},
        {"name": "source_wait", "cat": "sweep", "ts_s": 0.0, "dur_s": 0.1,
         "sweep_id": 1},
        # Shard 0 launches at 0.22, 0.03 s before its own upload arrives
        # (0.25), and behind shard 1's (enqueued at 0.2, done at 0.3).
        {"name": "compute", "cat": "sweep", "ts_s": 0.2, "dur_s": 0.3,
         "sweep_id": 1, "shard_idx": 0, "launch_s": 0.02},
        {"name": "device_wait", "cat": "sweep", "ts_s": 0.4, "dur_s": 0.1,
         "sweep_id": 1, "shard_idx": 0, "at": "shard_end"},
        # Shard 1 launches 0.05 s after that wait's return, its weights
        # long there, behind shard 2's upload (0.7 -> 0.95), which arrives
        # before its own shard-end wait returns at 0.97.
        {"name": "compute", "cat": "sweep", "ts_s": 0.52, "dur_s": 0.46,
         "sweep_id": 1, "shard_idx": 1, "launch_s": 0.23},
        {"name": "device_wait", "cat": "sweep", "ts_s": 0.8, "dur_s": 0.17,
         "sweep_id": 1, "shard_idx": 1, "at": "shard_end"},
        {"name": "sweep", "cat": "sweep", "ts_s": 0.0, "dur_s": 1.0,
         "sweep_id": 1},
    ] + [
        {"name": "ttft", "cat": "serve", "ts_s": 0.9, "seconds": s}
        for s in (0.1, 0.2, 0.3, 0.4)
    ]
    rep = obs_report.analyze(evs)
    assert rep["wall_s"] == pytest.approx(1.0)
    # Link busy: the union of the upload intervals = [0.15,0.3] +
    # [0.7,0.95]; host builds and the dispatch calls carry nothing.
    assert rep["stream_busy_s"] == pytest.approx(0.4)
    assert rep["link_utilization"] == pytest.approx(0.4)
    # The sweep record's three idle figures, from the export alone.
    idle = rep["idle_between_shards"]
    assert idle["sweeps"] == 1
    assert idle["own_upload_wait_s"] == pytest.approx(0.03)
    assert idle["behind_upload_s"] == pytest.approx(0.05 + 0.2)
    assert idle["drained_s"] == pytest.approx(0.25)
    assert "overlap_efficiency" not in rep
    assert "launched behind another shard's upload 0.250s (25.0%)" in (
        obs_report.format_report(rep)
    )
    assert rep["sweeps"] == 1
    assert rep["sweep_phase_s"]["compute"] == pytest.approx(0.3 + 0.46)
    assert rep["sweep_wall_s"] == pytest.approx(1.0)
    q = rep["ttft_s"]
    assert q["count"] == 4 and q["p50"] == 0.3 and q["max"] == 0.4
    assert "link utilization: 40.0%" in obs_report.format_report(rep)
    # A trace without upload spans (a serve engine's) says so, not "0%".
    bare = obs_report.analyze([e for e in evs if e["name"] != "upload"])
    assert bare["link_utilization"] == 0.0
    assert "link utilization: not timed" in obs_report.format_report(bare)


def test_analyzer_reads_a_lagged_wait_as_the_record_does():
    """Shard 0's shard-end wait, named by its index, lies inside shard 1's
    compute span (its end waited for one shard later): shard 0's last
    launch is its own span's end, before shard 4's upload was enqueued, and
    shard 1 launched before that wait returned: nothing drained, nothing
    queued behind the upload."""
    evs = [
        {"name": "upload_dispatch", "cat": "stream", "ts_s": 1.079, "dur_s": 0.001,
         "sweep_id": 1, "shard_idx": 4},
        {"name": "upload", "cat": "stream", "ts_s": 1.079, "dur_s": 0.011,
         "sweep_id": 1, "shard_idx": 4},
        {"name": "compute", "cat": "sweep", "ts_s": 0.99, "dur_s": 0.06,
         "sweep_id": 1, "shard_idx": 0, "launch_s": 0.01},
        {"name": "compute", "cat": "sweep", "ts_s": 1.06, "dur_s": 0.34,
         "sweep_id": 1, "shard_idx": 1, "launch_s": 0.04},
        {"name": "device_wait", "cat": "sweep", "ts_s": 1.12, "dur_s": 0.18,
         "sweep_id": 1, "shard_idx": 0, "at": "shard_end"},
        {"name": "device_wait", "cat": "sweep", "ts_s": 1.32, "dur_s": 0.08,
         "sweep_id": 1, "shard_idx": 1, "at": "shard_end"},
        {"name": "sweep", "cat": "sweep", "ts_s": 0.9, "dur_s": 0.6, "sweep_id": 1},
    ]
    idle = obs_report.analyze(evs)["idle_between_shards"]
    assert idle == {"sweeps": 1, "drained_s": 0.0, "own_upload_wait_s": 0.0,
                    "behind_upload_s": 0.0}


def test_analyzer_roundtrips_both_export_formats(tmp_path):
    t = Tracer()
    time.sleep(0.05)  # real spans start well after tracer construction
    t.enabled = True
    with t.span("shard_load", cat="stream"):
        time.sleep(0.002)
    t.instant("ttft", cat="serve", seconds=0.5)
    walls = {}
    for suffix in ("chrome.json", "spans.jsonl"):
        p = tmp_path / suffix
        t.write(str(p))
        evs = obs_report.load_trace(str(p))
        rep = obs_report.analyze(evs)
        assert rep["spans_by_name"]["shard_load"]["count"] == 1
        assert rep["ttft_s"]["count"] == 1
        walls[suffix] = rep["wall_s"]
    # The Chrome export's synthetic trace_meta rides at ts=0 (tracer
    # construction); the wall must anchor on the REAL events, so both
    # formats report the same window for the same ring.
    assert walls["chrome.json"] == pytest.approx(
        walls["spans.jsonl"], abs=1e-3
    )
    assert walls["chrome.json"] < 0.05


# ---------------------------------------------------------------------------
# End to end: a traced streamed run and a traced serve run
# ---------------------------------------------------------------------------

def test_executor_run_produces_sweep_timeline(model, process_tracer):
    from flexible_llm_sharding_tpu.runtime.executor import (
        StreamingExecutor, process_sweep_log,
    )

    ex = StreamingExecutor(_fw(model), tokenizer=FakeTokenizer())
    ex(list(PROMPTS))
    spans = process_tracer.snapshot()
    names = {s["name"] for s in spans}
    assert {"sweep", "sweep_head", "sweep_tail", "compute", "dispatch",
            "device_wait", "source_wait", "shard_load", "shard_produce",
            "upload_dispatch", "upload", "act_fetch", "act_store",
            "tokenize"} <= names
    assert "device_put" not in names  # renamed for what it measures
    # Correlation: every compute span carries the pass's sweep_id.
    sweep_ids = {s["sweep_id"] for s in spans if s["name"] == "compute"}
    assert len(sweep_ids) == 1
    rep = obs_report.analyze(spans)
    assert rep["sweeps"] == 1
    assert 0.0 <= rep["link_utilization"] <= 1.0
    # The record's three idle figures again, from the ring's spans (which
    # round to the microsecond).
    last = process_sweep_log()[-1]
    idle = rep["idle_between_shards"]
    for key in ("drained_s", "own_upload_wait_s", "behind_upload_s"):
        assert idle[key] == pytest.approx(last[key], abs=1e-4), key


def test_serve_run_traces_waves_and_exposes_metrics(model, process_tracer):
    from flexible_llm_sharding_tpu.serve import ServeEngine

    engine = ServeEngine(
        _fw(model),
        ServeConfig(
            max_wave_requests=2, default_max_new_tokens=2, metrics_port=0,
        ),
        tokenizer=FakeTokenizer(),
    )
    try:
        reqs = [engine.submit(p, s) for p, s in PROMPTS]
        for r in reqs:
            r.future.result(timeout=300)
        port = engine.metrics_server.port
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ).read().decode()
    finally:
        engine.shutdown(drain=True)
    assert engine.error is None
    # One scrape carries the acceptance set: queue depth, TTFT quantiles,
    # streamed bytes, cache hit rate, retry/heal/recovery counters.
    for series in (
        "fls_serve_queue_depth",
        "fls_serve_ttft_s_p99",
        "fls_stream_streamed_bytes",
        "fls_serve_engine_recoveries",
        "fls_integrity_reread_heals",
        "fls_host_cache_hit_rate",
        "fls_trace_trace_drops",
    ):
        assert series in text, f"{series} missing from the exposition"
    spans = process_tracer.snapshot()
    names = {s["name"] for s in spans}
    assert {"sweep", "prefill_shard", "decode_shard", "wave_admit",
            "ttft", "token_latency", "request_finish"} <= names
    # Wave correlation ids thread through: every prefill/decode span names
    # its wave, every ttft its request.
    assert all(
        "wave_id" in s for s in spans
        if s["name"] in ("prefill_shard", "decode_shard")
    )
    assert all("request_id" in s for s in spans if s["name"] == "ttft")
    rep = obs_report.analyze(spans)
    assert rep["ttft_s"]["count"] == len(PROMPTS)
    assert rep["token_latency_s"]["count"] >= 1
    assert rep["event_counts"]["wave_admit"] >= 1


# ---------------------------------------------------------------------------
# The program's spans ride whatever profiler session is running
# ---------------------------------------------------------------------------

def ONE_CHIP():
    """The suite runs on 8 virtual devices, where run_prompts would take
    its pipeline path: the sweep's spans are the single-executor path's."""
    return jax.devices()[:1]


def _profiled_host_events(tmp_path, body):
    """Run ``body`` inside a jax.profiler session (host spans on, Python
    tracer off, as the benchmark's traced window starts it) and return the
    ``fls.`` events of the trace: {name: [stats dict, ...]}."""
    import glob
    import os

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    out: dict[str, list[dict]] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs_trace.ANNOTATION_PREFIX):
                    out.setdefault(ev.name, []).append(
                        dict(ev.stats, _start=ev.start_ns, _dur=ev.duration_ns)
                    )
    return out


def test_span_with_ring_off_lands_in_profiler_trace(tmp_path):
    t = obs_trace.TRACER
    assert not t.enabled
    t.clear()
    assert not obs_trace.profiler_active()
    # No ring, no session: the shared no-op, as before.
    assert obs_trace.span("a") is obs_trace.span("b", cat="c", k=1)

    def body():
        assert obs_trace.profiler_active()
        with obs_trace.sweep_span(41, mode="offline"):
            with obs_trace.span("probe_span", cat="t", sweep_id=41, shard_idx=3):
                time.sleep(0.005)

    evs = _profiled_host_events(tmp_path, body)
    (probe,) = evs["fls.probe_span"]
    assert probe["sweep_id"] == 41 and probe["shard_idx"] == 3
    assert probe["_dur"] >= 4e6  # ns, on the profiler's clock
    # The sweep is a step annotation: the profiler groups by step_num.
    (sweep,) = evs["fls.sweep"]
    assert sweep["step_num"] == 41 and sweep["sweep_id"] == 41
    assert sweep["_start"] <= probe["_start"]
    assert sweep["_start"] + sweep["_dur"] >= probe["_start"] + probe["_dur"]
    # ...and the ring stayed off and empty: --trace means what it meant.
    assert len(t) == 0 and not t.enabled
    assert not obs_trace.profiler_active()


def test_timed_span_feeds_ring_and_caller_the_same_clock_pair():
    t = obs_trace.TRACER
    assert not t.enabled
    with obs_trace.timed("work", cat="test", sweep_id=7) as sp:
        time.sleep(0.005)
    # Always timed, ring off: the account's reading, nothing recorded.
    assert sp.dur_s >= 0.004 and len(t) == 0
    t.clear()
    t.enable()
    try:
        with obs_trace.timed("work", cat="test", sweep_id=7) as sp:
            time.sleep(0.002)
        with obs_trace.timed("skipped", cat="test") as dropped:
            dropped.drop()
        (rec,) = t.snapshot()
    finally:
        t.disable()
        t.clear()
    assert rec["name"] == "work" and rec["dur_s"] == round(sp.dur_s, 6)
    assert dropped.dur_s >= 0.0  # timing stays readable; the ring skips it


def test_executor_spans_land_in_profiler_trace_without_the_ring(model, tmp_path):
    from flexible_llm_sharding_tpu.runtime import orchestration

    assert not obs_trace.TRACER.enabled
    evs = _profiled_host_events(
        tmp_path / "prof",
        lambda: orchestration.run_prompts(
            _fw(model, prefetch_depth=2), list(PROMPTS), tokenizer=FakeTokenizer(),
            devices=ONE_CHIP(),
        ),
    )
    for name in ("sweep", "sweep_head", "executor_init", "tokenize",
                 "source_wait", "compute", "dispatch", "device_wait",
                 "act_fetch", "act_store", "sweep_tail", "shard_produce",
                 "shard_load", "upload_dispatch", "upload"):
        assert f"fls.{name}" in evs, name
    (sweep,) = evs["fls.sweep"]
    lo, hi = sweep["_start"], sweep["_start"] + sweep["_dur"]
    for name in ("sweep_head", "source_wait", "compute", "sweep_tail"):
        for ev in evs[f"fls.{name}"]:  # the consumer's phases nest in the sweep
            assert lo <= ev["_start"] and ev["_start"] + ev["_dur"] <= hi, name
            assert ev["sweep_id"] == sweep["step_num"]
    assert len(obs_trace.TRACER) == 0


def test_producer_and_consumer_spans_share_the_sweep_id(model, process_tracer):
    from flexible_llm_sharding_tpu.runtime import orchestration

    orchestration.run_prompts(
        _fw(model, prefetch_depth=2), list(PROMPTS), tokenizer=FakeTokenizer(),
        devices=ONE_CHIP(),
    )
    spans = process_tracer.snapshot()
    producer = ("shard_produce", "shard_load", "upload_dispatch", "upload")
    consumer = ("sweep", "sweep_head", "source_wait", "compute", "dispatch",
                "device_wait", "act_fetch", "act_store", "sweep_tail")
    ids = {n: {s.get("sweep_id") for s in spans if s["name"] == n}
           for n in producer + consumer}
    (sweep_id,) = ids["sweep"]
    assert sweep_id > 0
    for n, got in ids.items():
        assert got == {sweep_id}, (n, got)
    # Below a shard the producer's and the consumer's spans name it alike.
    n_shards = len({s["shard_idx"] for s in spans if s["name"] == "compute"})
    for n in ("shard_produce", "shard_load", "upload_dispatch", "upload"):
        assert {s["shard_idx"] for s in spans if s["name"] == n} == set(
            range(n_shards)
        ), n
    # Every upload interval starts at or after its dispatch was called,
    # and ends at or after that call returned.
    dispatch = {s["shard_idx"]: s for s in spans if s["name"] == "upload_dispatch"}
    for up in (s for s in spans if s["name"] == "upload"):
        d = dispatch[up["shard_idx"]]
        assert up["ts_s"] >= d["ts_s"] - 1e-6
        assert up["ts_s"] + up["dur_s"] >= d["ts_s"] + d["dur_s"] - 1e-5
        assert up["bytes"] > 0
    rep = obs_report.analyze(spans)
    assert 0.0 < rep["link_utilization"] <= 1.0


def test_last_sweep_gauges_on_the_stream_source(model):
    from flexible_llm_sharding_tpu.obs.registry import REGISTRY
    from flexible_llm_sharding_tpu.runtime import executor, orchestration

    orchestration.run_prompts(
        _fw(model), list(PROMPTS), tokenizer=FakeTokenizer(), devices=ONE_CHIP()
    )
    last = executor.process_sweep_log()[-1]
    stream = REGISTRY.collect()["stream"]
    for key in ("wall_s", "head_s", "source_wait_s", "upload_busy_s",
                "upload_bytes", "producer_blocked_s"):
        assert stream[f"last_sweep_{key}"] == last[key]
    text = REGISTRY.prometheus_text()
    assert "fls_stream_last_sweep_upload_busy_s" in text
    # Every field an operator reads has its definition on the endpoint, and
    # dispatch_s says what it leaves out.
    for key in last:
        if key not in ("sweep_id", "t_end"):
            assert f"# HELP fls_stream_last_sweep_{key} " in text, key
    (line,) = [
        ln for ln in text.splitlines()
        if ln.startswith("# HELP fls_stream_last_sweep_dispatch_s ")
    ]
    assert "device_wait_s" in line and "\n" not in line


@pytest.mark.parametrize(
    "field",
    ["drained_s", "drained_shards", "own_upload_wait_s", "behind_upload_s",
     "launches_behind_upload", "gc_s", "gc_collections", "slow"],
)
def test_idle_fields_are_gauges_with_their_definition(model, field):
    from flexible_llm_sharding_tpu.obs.registry import REGISTRY
    from flexible_llm_sharding_tpu.runtime import executor, orchestration

    if not executor.process_sweep_log():
        orchestration.run_prompts(
            _fw(model), list(PROMPTS), tokenizer=FakeTokenizer(),
            devices=ONE_CHIP(),
        )
    text = REGISTRY.prometheus_text()
    assert f"# HELP fls_stream_last_sweep_{field} " in text
    assert f"\nfls_stream_last_sweep_{field} " in text
    # The counter of slow sweeps is there from the first scrape on.
    assert "# HELP fls_stream_slow_sweeps " in text
    assert "\nfls_stream_slow_sweeps " in text


def test_slow_sweep_lands_in_the_journal_and_the_ring(process_tracer, tmp_path):
    """The event's path, driven on a record by hand: one journal line, one
    instant on the ring, both naming the phase and the shard."""
    import types

    from flexible_llm_sharding_tpu.obs import events as obs_events
    from flexible_llm_sharding_tpu.runtime import executor

    obs_events.reset_journal()
    obs_events.JOURNAL.configure(str(tmp_path))
    try:
        phases = ("head_s", "source_wait_s", "dispatch_s", "device_wait_s", "tail_s")
        median = dict.fromkeys(phases, 0.1) | {"sweep_id": 7, "wall_s": 0.5}
        rec = dict(median, sweep_id=9, wall_s=3.5, device_wait_s=3.1, gc_s=0.25,
                   slow=1)
        stamps = []
        for k, wait_s in enumerate((0.02, 3.0, 0.08)):
            s = executor.ShardStamps(k, 0.0, 10.0 * k)
            s.t_launch, s.t_wait = 10.0 * k + 0.01, 10.0 * k + 0.02
            s.t_ready = s.t_end = s.t_wait + wait_s
            stamps.append(s)
        n0 = executor.stream_stats()["slow_sweeps"]
        clock = types.SimpleNamespace(shards=stamps, block_rows=())
        executor._keep_slow_sweep(rec, median, clock, source=None, t_end=24.0)
        assert executor.stream_stats()["slow_sweeps"] == n0 + 1
        kept = executor.process_slow_sweeps()[-1]
        assert (kept["worst_phase"], kept["worst_shard"]) == ("device_wait_s", 1)
        assert kept["worst_shard_s"] == pytest.approx(3.0)
        (event,) = [e for e in obs_events.JOURNAL.tail() if e["kind"] == "slow_sweep"]
        assert event["severity"] == "warning" and event["sweep_id"] == 9
        assert event["worst_phase"] == "device_wait_s" and event["worst_shard"] == 1
        assert event["gc_s"] == 0.25 and event["median_wall_s"] == 0.5
        (inst,) = [s for s in process_tracer.snapshot() if s["name"] == "slow_sweep"]
        assert inst["worst_shard"] == 1 and "dur_s" not in inst
    finally:
        obs_events.reset_journal()
