"""The readers of the program's serve spans and counters:
`prefill_residence_p90_ms` from the request records, and
`decode_stall_p95_ms` and `decode_live_pages_pct` from the process
tracer's fold, on synthetic runs; then all three through a traced run
of the tiny chat cell on the CPU."""

import pytest

from bench.run import Run
from bench.spec import reader
from bench.tests import _tiny
from repro.core import tracer as xfa
from repro.core.histogram import hist_of, percentile_ns

SERVE_METRICS = ("prefill_residence_p90_ms", "decode_stall_p95_ms",
                 "decode_live_pages_pct")


@pytest.fixture
def fold():
    xfa.reset()
    yield
    xfa.reset()


def req(admitted, first, in_window=True):
    return {"due": 0.0, "admitted": admitted, "in_window": in_window,
            "times": [] if first is None else [first, first + 0.05]}


def test_prefill_residence_is_nearest_rank_p90_over_the_window():
    # residences 10, 20, ..., 100 ms in the window; p90 is the 9th
    reqs = [req(1.0, 1.0 + 0.01 * k) for k in range(1, 11)]
    reqs += [req(1.0, 9.0, in_window=False),      # outside the window
             req(None, None), req(2.0, None)]     # never admitted / answered
    got = reader("prefill_residence_p90_ms")(Run(requests=reqs))
    assert got == pytest.approx(90.0)


def test_prefill_residence_reads_nothing_without_first_tokens():
    assert reader("prefill_residence_p90_ms")(
        Run(requests=[req(1.0, None)])) is None


def test_decode_stall_is_the_fold_histograms_p95(fold):
    ms = [5, 5, 5, 120, 200]
    rows = [40, 40, 30, 8, 2]
    for d, n in zip(ms, rows):
        xfa.record_duration("serve", "decode_stall", d * 1e6, n=n)
    # the same edge from another caller merges in
    with xfa.scope("serve", "decode_tick"):
        xfa.record_duration("serve", "decode_stall", 150e6, n=5)
    samples = [int(d * 1e6) for d, n in zip(ms + [150], rows + [5])
               for _ in range(n)]
    want = percentile_ns(hist_of(samples), 0.95) * 1e-6
    assert reader("decode_stall_p95_ms")(Run()) == pytest.approx(want)
    assert 120 <= want <= 200


def test_live_pages_is_held_over_addressed(fold):
    for held in (10, 30, 20):
        xfa.record_gauge("serve", "decode_pages", held)
        xfa.record_gauge("serve", "decode_page_slots", 64)
    assert reader("decode_live_pages_pct")(Run()) == pytest.approx(
        100.0 * 60 / 192)


@pytest.mark.parametrize("metric", ["decode_stall_p95_ms",
                                    "decode_live_pages_pct"])
def test_program_without_the_counter_reads_nothing(fold, metric):
    xfa.record_duration("serve", "ttft", 1e6)
    assert reader(metric)(Run()) is None


def test_traced_tiny_chat_reports_the_serve_metrics(fold):
    spec = _tiny.spec()
    spec.data["per_layer"] += [
        {"name": m, "unit": "ms", "better": "lower", "source": "program_span",
         "layer": "test", "moves": "itl_p95_ms", "workloads": ["tiny.chat"]}
        for m in SERVE_METRICS]
    from bench import run
    r = run.run_cell(spec, "tiny.chat", 2 ** 31 + 29, 2.0, True,
                     require_chip=False)
    got = {m: r["metrics"][m]["value"] for m in SERVE_METRICS}
    assert got["prefill_residence_p90_ms"] > 0
    assert got["decode_stall_p95_ms"] > 0
    # tiny: 4 slots x 16 pages a row; a decoding row holds at least one
    assert 100.0 / 64 <= got["decode_live_pages_pct"] <= 100.0
