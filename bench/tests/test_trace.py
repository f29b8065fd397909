"""The trace reduction, on a small trace recorded on a TPU v5e
(data/traces/probe.xplane.pb): two steps of a 2048x2048 matmul and a
paged decode kernel under the benchmark's own spans, with a 5 ms host
sleep labelled `bench.sample` after each."""

from pathlib import Path

import pytest

from bench import tracing

TRACE = Path(__file__).resolve().parent / "data" / "traces" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return tracing.reduce(str(TRACE))


def test_window_and_busy(reduced):
    assert reduced["window_s"] == pytest.approx(0.013407699)
    assert reduced["busy_s"] == pytest.approx(0.000204018)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_kernel_found_by_name(reduced):
    k = reduced["kernels"]["xfa_decode_attention_paged"]
    assert k["count"] == 2
    assert k["seconds"] == pytest.approx(0.000200102)


def test_modules_and_ops(reduced):
    assert reduced["modules"]["jit__lambda"]["count"] == 4
    names = [n for n, _ in reduced["device_ops"]]
    assert names[:2] == ["xfa_decode_attention_paged",
                         "convolution_tanh_fusion"]


def test_idle_gaps_are_labelled_by_host_span(reduced):
    assert reduced["idle_gaps"][0][0] == "bench.sample"
    assert reduced["idle_gaps"][0][1] == pytest.approx(0.007100233)
    idle = sum(reduced["idle_by_span"].values())
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_op_names():
    assert tracing.op_name("%fusion.12 = bf16[2]{0} fusion(...)") == \
        "fusion.12"
    assert tracing.op_kind("fusion.12") == "fusion"
    assert tracing.op_kind("copy-start") == "copy-start"
    assert tracing.op_kind("xfa_decode_attention_paged.1") == \
        "xfa_decode_attention_paged"


def test_no_window_span_reads_nothing(tmp_path):
    assert tracing.find_xplane(str(tmp_path)) is None
