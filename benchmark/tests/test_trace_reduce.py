"""The trace reduction on a small trace recorded on the TPU v5e (PR 24's
probe: three rounds of a 1024x1024 matmul and one Pallas causal-attention
call inside a ``bench.batch.run`` span, a 2 ms sleep inside
``bench.batch.prepare``)."""

import os

import pytest

from benchmark import trace_reduce as trd

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "probe.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    planes, spans = trd.read_xplane(TRACE)
    return planes, spans, trd.reduce_planes(planes, spans)


def test_planes_and_spans_are_found(reduced):
    planes, spans, _ = reduced
    assert len(planes) == 1 and len(planes[0]) == 30
    assert sorted({n for n, _, _ in spans}) == ["batch.prepare", "batch.run"]
    assert all(m.startswith("jit__lambda") for _, _, _, m in planes[0])


def test_busy_is_the_union_and_under_the_window(reduced):
    planes, _, r = reduced
    assert r["chips"] == 1
    assert r["busy_s"] == pytest.approx(7.0106e-05, rel=1e-3)
    assert r["busy_s"] <= sum(b - a for _, a, b, _ in planes[0]) + 1e-12
    assert 0 < r["busy_s"] < r["window_s"] == pytest.approx(6.82e-3, rel=1e-2)


def test_op_time_by_name_marks_the_pallas_kernel(reduced):
    ops = dict(reduced[2]["device_ops"])
    assert ops["jit__lambda/pallas:flash_causal_attention"] == pytest.approx(1.432e-5, rel=1e-2)
    assert max(ops, key=ops.get) == "jit__lambda/fusion"
    assert not any(k.rsplit("/", 1)[-1] in trd.CONTAINERS for k in ops)


def test_gaps_are_labelled_by_the_host_span_that_covers_them(reduced):
    gaps = reduced[2]["idle_gaps"]
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert gaps[0][0].startswith("batch.run|after:jit__lambda/") and gaps[0][1] > 2e-3
    assert any(g[0].startswith("batch.prepare|") for g in gaps)


def test_op_label_and_merge():
    hlo = ('%closed_call.24 = bf16[4,16,64,128]{3,2,1,0} custom-call(s32[2]{0} %fusion.247), '
           'custom_call_target="tpu_custom_call"')
    assert trd.op_label(hlo, "jit__decoder_block(123)") == "jit__decoder_block/pallas:closed_call"
    assert trd.op_label("%fusion.12 = bf16[8]{0} fusion(...)") == "fusion"
    assert trd.merge_intervals([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trd.reduce_planes([[]], []) is None
