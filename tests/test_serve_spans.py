"""The XFA -> profiler bridge and the serving engine's tick spans.

`xfa.profiler_spans(True)` makes every bracketed XFA boundary (@api,
@wait, wrap, scope; timed, counting-only and sampled-out) also open a
`jax.profiler.TraceAnnotation` named `xfa.<component>.<api>`; off, no
annotation is built.  The engine splits each tick into scope edges
(plan, prefill_inputs, prefill_sync, first_token, decode_inputs, sample,
emit) and folds three counters at the work: `decode_stall` once per row
decoding at a step's start, `prefill_residence` once per first token,
and the `decode_pages` gauge (with `decode_page_slots`, the slots the
paged kernel's grid addresses) once per paged decode call.
"""

import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.core import tracer as xfa
from repro.core.shadow import KIND_WAIT
from repro.core.tracer import Tracer
from repro.profile import tracer_folded
from repro.serving import ServingEngine
from test_serving_engine import build, mixed_prompts

TICK_EDGES = ("plan", "admit", "prefill_inputs", "prefill_sync",
              "first_token", "decode_tick", "decode_inputs", "sample",
              "emit", "decode_stall", "prefill_residence")


class Log:
    """A stand-in for TraceAnnotation that logs enters and exits."""

    def __init__(self):
        self.events = []
        log = self

        class Ann:
            def __init__(self, name):
                self.name = name
                log.events.append(("new", name))

            def __enter__(self):
                log.events.append(("enter", self.name))

            def __exit__(self, *exc):
                log.events.append(("exit", self.name))

        self.cls = Ann


@pytest.fixture
def quiet_tracer():
    """The process tracer, folded from zero, with the bridge off after."""
    xfa.reset()
    yield xfa.TRACER
    xfa.profiler_spans(False)
    xfa.reset()


# -- the bridge on a private tracer ----------------------------------------
def _boundary(t: Tracer, how: str):
    """A callable that enters component `c` as api `f` the `how` way."""
    if how == "api":
        return t.api("c", "f")(lambda: None)
    if how == "wait":
        return t.wait("c", "f")(lambda: None)
    if how == "wrap":
        return t.wrap(lambda: None, "c", "f")

    def scoped():
        with t.scope("c", "f", kind=KIND_WAIT):
            pass
    return scoped


@pytest.mark.parametrize("how", ["api", "wait", "wrap", "scope"])
def test_bridge_annotates_each_boundary_kind(how):
    t, log = Tracer(), Log()
    t.annotation = log.cls
    outer = t.api("o", "g")(_boundary(t, how))
    outer()
    assert [e for e in log.events if e[0] != "new"] == [
        ("enter", "xfa.o.g"), ("enter", "xfa.c.f"),
        ("exit", "xfa.c.f"), ("exit", "xfa.o.g")]
    # the fold is unchanged by the bridge
    assert {i.key[1:] for i in t.tables.registry.infos()} == {("o", "g"),
                                                              ("c", "f")}


@pytest.mark.parametrize("mode", ["counting_only", "sampled_out"])
def test_bridge_annotates_untimed_frames(mode):
    t, log = Tracer(), Log()
    t.annotation = log.cls
    if mode == "counting_only":
        t.timing = False
    else:
        class Never:
            def observe(self, slot):
                return 0
        t.sampler = Never()
    t.api("c", "f")(lambda: None)()
    assert ("enter", "xfa.c.f") in log.events
    assert ("exit", "xfa.c.f") in log.events


def test_bridge_closes_the_span_on_an_exception():
    t, log = Tracer(), Log()
    t.annotation = log.cls

    @t.api("c", "boom")
    def boom():
        raise ValueError

    with pytest.raises(ValueError):
        boom()
    with pytest.raises(KeyError):
        with t.scope("c", "s"):
            raise KeyError
    assert [e for e in log.events if e[0] != "new"] == [
        ("enter", "xfa.c.boom"), ("exit", "xfa.c.boom"),
        ("enter", "xfa.c.s"), ("exit", "xfa.c.s")]
    assert t.stack_depth() == 0


def test_bridge_off_and_disabled_build_nothing():
    t, log = Tracer(), Log()
    f = t.api("c", "f")(lambda: None)
    f()                                   # bridge off
    t.annotation = log.cls
    t.enabled = False
    f()                                   # tracer off
    with t.scope("c", "s"):
        pass
    assert log.events == []


def test_profiler_spans_switch(quiet_tracer):
    xfa.profiler_spans(True)
    assert quiet_tracer.annotation is jax.profiler.TraceAnnotation
    xfa.profiler_spans(False)
    assert quiet_tracer.annotation is None


# -- the engine's tick spans and counters -----------------------------------
def serve_edges():
    out = {}
    for k, e in tracer_folded().edges.items():
        if k[1] == "serve":
            out[k[2]] = out[k[2]].merge(e) if k[2] in out else e
    return out


def paged_engine(model, params, max_batch=3):
    return ServingEngine(model, params, ServeConfig(
        max_batch=max_batch, max_seq_len=64, eos_token=-1, prefill_chunk=8,
        min_chunk_bucket=4, page_size=8, max_cache_pages=40))


def drive(engine, prompts, max_new):
    """Submit two requests, step, submit the rest, step until drained;
    return the handles, the rows decoding at each step's start, and the
    pages the decoding rows held at each decode call."""
    decoding, pages = [], []
    dec = engine._decode

    def counted(params, tokens, table, cache, pos, *bt):
        if bt:
            rows = engine.scheduler.decoding()
            pages.append(int(np.count_nonzero(np.asarray(bt[0])[rows])))
        return dec(params, tokens, table, cache, pos, *bt)

    engine._decode = counted
    reqs = [engine.submit(p, n) for p, n in zip(prompts[:2], max_new)]
    for k in range(200):
        if k == 2:
            reqs += [engine.submit(p, n)
                     for p, n in zip(prompts[2:], max_new[2:])]
        if k > 2 and not engine.scheduler.has_work():
            break
        decoding.append(len(engine.scheduler.decoding()))
        engine.step()
    return reqs, decoding, pages


def test_every_tick_edge_is_folded_and_counts_its_events(quiet_tracer):
    cfg, model, params = build("tinyllama_1_1b")
    engine = paged_engine(model, params)
    reqs, decoding, pages = drive(engine, mixed_prompts(cfg), [5, 7, 4, 6])
    assert all(r.done for r in reqs)
    e = serve_edges()
    for api in TICK_EDGES + ("decode_pages", "decode_page_slots"):
        assert api in e and e[api].count > 0, api
    assert "prefill_request" not in e
    assert e["prefill_sync"].kind == e["sample"].kind == KIND_WAIT
    # one stall per row decoding at a step's start, on ticks with any
    assert e["decode_stall"].count == sum(decoding) > 0
    assert int(e["decode_stall"].hist.sum()) == sum(decoding)
    # one residence per first token
    assert e["prefill_residence"].count == sum(
        r.first_token_at is not None for r in reqs) == len(reqs)
    assert e["first_token"].count == len(reqs)
    # the gauge: pages held by the decode call's rows, per call
    assert e["decode_pages"].count == len(pages)
    assert e["decode_pages"].mean_ns == pytest.approx(np.mean(pages))
    assert e["decode_page_slots"].mean_ns == 3 * engine._n_blocks
    assert e["decode_tick"].child_ns >= (e["decode_inputs"].total_ns
                                         + e["sample"].total_ns)


def test_residence_is_admission_to_first_token(quiet_tracer):
    cfg, model, params = build("tinyllama_1_1b")
    engine = paged_engine(model, params)
    reqs, _, _ = drive(engine, mixed_prompts(cfg), [3, 3, 3, 3])
    e = serve_edges()["prefill_residence"]
    want = sum(int((r.first_token_at - r.admitted_at) * 1e9) for r in reqs)
    assert e.total_ns == pytest.approx(want, rel=1e-6, abs=len(reqs))


def test_contiguous_engine_folds_the_tick_edges_without_pages(quiet_tracer):
    cfg, model, params = build("tinyllama_1_1b")
    engine = ServingEngine(model, params, ServeConfig(
        max_batch=3, max_seq_len=64, eos_token=-1, prefill_chunk=8,
        min_chunk_bucket=4))
    reqs, decoding, pages = drive(engine, mixed_prompts(cfg), [4, 4, 4, 4])
    e = serve_edges()
    for api in TICK_EDGES:
        assert api in e, api
    assert "decode_pages" not in e and pages == []
    assert e["decode_stall"].count == sum(decoding)


def test_bridge_off_builds_no_annotation(quiet_tracer, monkeypatch):
    log = Log()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", log.cls)
    cfg, model, params = build("tinyllama_1_1b")
    engine = paged_engine(model, params)
    engine.submit(mixed_prompts(cfg)[0], 3)
    engine.step()
    engine.step()
    assert log.events == []
    # the same steps with the bridge on do build them
    xfa.profiler_spans(True)
    engine.step()
    assert ("enter", "xfa.serve.decode_tick") in log.events


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
    return out


def test_bridge_puts_serve_spans_in_a_profiler_trace(quiet_tracer, tmp_path):
    cfg, model, params = build("tinyllama_1_1b")
    engine = paged_engine(model, params)
    prompts = mixed_prompts(cfg)
    engine.submit(prompts[0], 6)
    engine.step()                         # compile outside the trace
    engine.submit(prompts[1][:6], 6)
    xfa.profiler_spans(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.step"):
            engine.step()                 # prefill of one, decode of both
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    step = next(e for e in ev if e[0] == "test.step")
    spans = {}
    for name, s, t in ev:
        if name.startswith("xfa.serve."):
            assert step[1] <= s <= t <= step[2], name
            spans.setdefault(name[len("xfa.serve."):], (s, t))
    assert set(spans) >= {"plan", "admit", "prefill_inputs",
                          "prefill_sync", "first_token", "decode_tick",
                          "decode_inputs", "sample", "emit"}
    tick = spans["decode_tick"]
    for child in ("decode_inputs", "sample", "emit"):
        assert tick[0] <= spans[child][0] <= spans[child][1] <= tick[1]
    assert spans["plan"][0] <= spans["admit"][0] <= spans["plan"][1]
    # in the order the tick runs them
    order = ["plan", "prefill_inputs", "prefill_sync", "first_token",
             "decode_inputs", "sample", "emit"]
    starts = [spans[k][0] for k in order]
    assert starts == sorted(starts)
