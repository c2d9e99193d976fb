"""``tools/gaps_by_span.py`` on a small trace recorded on the TPU v5e (PR 25:
the tool's own ``--toy`` window of ``moonlight-16b.score-b8``: four traced
batches at the rehearsal's widths, through ``run_prompts``): the program's ``fls.`` spans are in the profiler's trace, on
the clock of the device's ops, inside the benchmark's ``bench.batch.run``."""

import gzip
import importlib.util
import os
import shutil

import pytest

from benchmark import trace_reduce as trd

HERE = os.path.dirname(os.path.abspath(__file__))
PACKED = os.path.join(HERE, "data", "fls_probe.xplane.pb.gz")  # 2.8 MB unpacked
spec = importlib.util.spec_from_file_location(
    "gaps_by_span", os.path.join(os.path.dirname(HERE), "tools", "gaps_by_span.py"))
gaps = importlib.util.module_from_spec(spec)
spec.loader.exec_module(gaps)


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    """The recorded trace unpacked where the profiler would have put it."""
    root = tmp_path_factory.mktemp("fls_probe")
    d = root / "plugins" / "profile" / "recorded"
    d.mkdir(parents=True)
    with gzip.open(PACKED, "rb") as src, open(d / "probe.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(root)


@pytest.fixture(scope="module")
def read(trace_dir):
    planes, spans = trd.read_xplane(trd.find_xplane(trace_dir), gaps.PREFIX)
    return [p for p in planes if p], spans


def test_the_programs_spans_are_on_the_host_plane_of_the_recorded_trace(read):
    planes, spans = read
    assert len(planes) == 1 and len(planes[0]) > 100  # one chip, its ops
    names = {n for n, _, _ in spans}
    assert {"sweep", "sweep_head", "source_wait", "compute", "dispatch", "device_wait",
            "act_fetch", "act_store", "sweep_tail", "shard_produce", "shard_load",
            "upload_dispatch", "upload"} <= names
    # one clock: the traced batch's device ops lie inside its sweep span
    sweeps = [(a, b) for n, a, b in spans if n == "sweep"]
    ops = [(a, b) for _, a, b, mod in planes[0] if mod.startswith("jit__decoder_block")]
    assert sweeps and ops and all(
        any(x - 2e-3 <= a and b <= y + 2e-3 for x, y in sweeps) for a, b in ops)
    # the store's wait for the device is a span of its own, inside the store's
    stores = [(a, b) for n, a, b in spans if n in ("act_store", "act_fetch")]
    waits = [(a, b) for n, a, b in spans if n == "device_wait"]
    assert sum(any(x - 1e-5 <= a and b <= y + 1e-5 for x, y in stores) for a, b in waits) >= 1
    # every upload starts at or after its dispatch was called
    dispatch = sorted(a for n, a, _ in spans if n == "upload_dispatch")
    upload = sorted(a for n, a, _ in spans if n == "upload")
    assert len(upload) == len(dispatch) and all(u >= d - 1e-4 for u, d in zip(upload, dispatch))


def test_idle_seconds_go_to_the_consumers_innermost_span(read):
    planes, spans = read
    rep = gaps.attribute(planes, spans)
    assert rep["idle_s"] > 0
    assert sum(rep["idle_by_consumer_span_s"].values()) == pytest.approx(rep["idle_s"])
    assert rep["idle_under_a_finer_span_pct"] >= 90.0
    labels = {k.split("/")[0] for k in rep["idle_by_consumer_span_s"]}
    assert not labels - set(gaps.CONSUMER) - {"outside"}
    assert "device_wait" not in rep["idle_by_consumer_span_s"]  # always with its parent
    assert all(0.0 <= v <= 100.0 for v in rep["idle_with_pct"].values())
    # every gap counts, not only the longest 50 the committed reduction keeps
    reduced = trd.reduce_planes(planes, spans)
    assert sum(len(gaps.idle_gaps(p)) for p in planes) == reduced["idle_gap_count"]


def test_report_reads_a_trace_directory_and_finds_the_bench_spans(trace_dir):
    rep = gaps.report(trace_dir)
    assert rep["program_spans"] > 20
    assert rep["program_spans_inside_bench_batch_run"] == rep["program_spans"]
    assert 0 < rep["busy_s"] < rep["window_s"]
    gaps.show(rep)  # the human rendering never raises


def test_attribute_on_made_up_planes_and_kernel_names():
    plane = [("%a = f32[] fusion()", 0.0, 1.0, "jit_f(1)"), ("%b = f32[] fusion()", 2.0, 3.0, "jit_f(1)"),
             ("%c = f32[] fusion()", 5.0, 6.0, "jit_f(1)")]
    spans = [("sweep", 0.0, 10.0), ("compute", 0.5, 2.5), ("device_wait", 1.2, 2.2),
             ("upload", 1.0, 1.5), ("producer_blocked", 3.0, 5.0)]
    rep = gaps.attribute([plane], spans)
    assert rep["idle_s"] == pytest.approx(3.0)
    assert rep["idle_by_consumer_span_s"] == {"sweep": pytest.approx(2.0),
                                              "device_wait/compute": pytest.approx(1.0)}
    assert rep["idle_under_a_finer_span_pct"] == pytest.approx(100.0 / 3.0)
    assert rep["idle_with_pct"]["upload"] == pytest.approx(100.0 * 0.5 / 3.0)
    assert rep["idle_with_pct"]["producer_blocked"] == pytest.approx(100.0 * 2.0 / 3.0)
    hlo = ('%closed_call.24 = bf16[4]{0} custom-call(s32[2]{0} %x), custom_call_target="tpu_custom_call", '
           'frontend_attributes={kernel_metadata={\n"kernel":"flash_causal_attention"\n}}')
    ops = gaps.pallas_ops([[(hlo, 0.0, 0.25, "jit__decoder_block(7)")]])
    assert ops == {"jit__decoder_block/pallas:closed_call kernel=flash_causal_attention": 0.25}
