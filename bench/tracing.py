"""The traced window of a `--trace 1` run, and the reduction of its
profiler trace to busy time, kernel time, step periods and idle gaps.

Host spans are the benchmark's own `jax.profiler.TraceAnnotation`s
around its calls into each layer (named `bench.*`); the device side is
whatever the profiler records on the TPU planes.  Only the process that
holds the chip can trace it, so the trace is taken in the run itself,
around a few seconds of whole steps in the middle of the window.
"""

from __future__ import annotations

import contextlib
import glob
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from bench import stats

WINDOW = "bench.traced_window"
#: device lines whose events are the operations that ran, by preference
OP_LINES = ("XLA Ops",)


class Recorder:
    """Starts and stops the trace, labels host work with spans, and
    records the shapes of the program calls made while it is on."""

    def __init__(self, traced: bool, trace_at: float = 0.0,
                 trace_s: float = 0.0, trace_dir: str = "") -> None:
        self.traced = traced
        self.trace_at, self.trace_s, self.trace_dir = (trace_at, trace_s,
                                                       trace_dir)
        self.active = False
        self.done = False
        self.calls: List[dict] = []
        self.steps: List[Tuple[float, float]] = []
        self.window: Optional[Tuple[float, float]] = None
        self._ann = None

    # -- window control -----------------------------------------------------
    def tick(self, elapsed: float) -> None:
        """Called before each step with the seconds since the window
        opened: starts the trace at trace_at, stops it trace_s later."""
        if not self.traced or self.done:
            return
        if not self.active and elapsed >= self.trace_at:
            self._start()
        elif self.active and elapsed >= self.trace_at + self.trace_s:
            self.stop()

    def _start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()
        self._t = time.monotonic()
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        self._ann.__exit__(None, None, None)
        self.window = (self._t, time.monotonic())
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def span(self, name: str):
        return (jax.profiler.TraceAnnotation(name) if self.active
                else contextlib.nullcontext())

    def step(self, fn: Callable, *args, label: str = "bench.engine_step"):
        if not self.active:
            return fn(*args)
        t = time.monotonic()
        with jax.profiler.TraceAnnotation(label):
            out = fn(*args)
        self.steps.append((t, time.monotonic()))
        return out

    # -- what the program is asked to do while traced -------------------------
    def wrap_engine(self, engine) -> None:
        """Record the rows of every prefill and decode call, and label
        the calls, while the trace is on."""
        chunk, decode, sampler = engine._chunk, engine._decode, engine.sampler
        sched = engine.scheduler

        def chunk_call(params, tokens, table, cache, pos, bt, valid):
            if not self.active:
                return chunk(params, tokens, table, cache, pos, bt, valid)
            p, v = np.asarray(pos), np.asarray(valid)
            self.calls.append({"kind": "chunk", "width": int(tokens.shape[1]),
                               "rows": [(int(a), int(b))
                                        for a, b in zip(p, v) if b > 0]})
            with jax.profiler.TraceAnnotation("bench.prefill_call"):
                return chunk(params, tokens, table, cache, pos, bt, valid)

        def decode_call(params, tokens, table, cache, pos, bt):
            if not self.active:
                return decode(params, tokens, table, cache, pos, bt)
            self.calls.append({"kind": "decode", "rows": [
                (sched.slots[i].pos, 1) for i in sched.decoding()]})
            with jax.profiler.TraceAnnotation("bench.decode_call"):
                return decode(params, tokens, table, cache, pos, bt)

        class Sampler:
            def __call__(s, logits, step):
                with self.span("bench.sample"):
                    return sampler(logits, step)

            def sample_one(s, row, sp, step):
                with self.span("bench.sample_first"):
                    return sampler.sample_one(row, sp, step)

            def __getattr__(s, name):
                return getattr(sampler, name)

        engine._chunk, engine._decode = chunk_call, decode_call
        engine.sampler = Sampler()


# -- reduction -----------------------------------------------------------------
def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event: the profiler names
    an op event by its whole HLO text, `%name.N = type op(...)`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """An op's name without its numeric suffix (`fusion.12` -> `fusion`)."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def device_ops(pd) -> Dict[str, List[Tuple[str, float, float]]]:
    """{device plane name: [(op name, start_ns, end_ns)]} from the TPU
    planes' op lines."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        evs = []
        for line in plane.lines:
            if line.name in OP_LINES:
                evs += _events(line)
        if evs:
            out[plane.name] = evs
    return out


def host_spans(pd, prefix: str = "bench.") -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [e for e in _events(line) if e[0].startswith(prefix)]
    return out


def reduce(xplane: str) -> dict:
    """Busy and idle time over the traced window (the `bench.traced_window`
    span), time and count of each kind of op (kernels by their names)
    and of each program (module), the ops that took most time, and the longest idle gaps
    labelled by the innermost benchmark span the host was in at the
    gap's middle.

    The trace holds only the traced steps' device work (each step ends
    with the host reading its tokens or loss), so op, kernel and module
    times are summed over the whole trace; busy time is clipped to the
    host's window.  The device clock lands about a millisecond off the
    host's, which moves the window's edges by that much."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane)
    spans = host_spans(pd)
    win = [s for s in spans if s[0] == WINDOW]
    if not win:
        return {}
    lo, hi = win[0][1], win[0][2]
    planes = device_ops(pd)
    if not planes:
        return {}
    busy, ops, gaps_all = [], {}, []
    mods: Dict[str, List[float]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for name, s, e in _events(line):
                m = mods.setdefault(name.split("(")[0], [0, 0.0])
                m[0] += 1
                m[1] += (e - s) * 1e-9
    inner = [s for s in spans if s[0] != WINDOW]
    for evs in planes.values():
        iv = [(s, e) for _, s, e in evs if e > lo and s < hi]
        busy.append(stats.union_length(iv, lo, hi))
        for full, s, e in evs:
            d = (e - s) * 1e-9
            o = ops.setdefault(op_kind(op_name(full)), [0, 0.0])
            o[0] += 1
            o[1] += d
        for g0, g1 in stats.gaps(iv, lo, hi):
            mid = 0.5 * (g0 + g1)
            cover = [s for s in inner if s[1] <= mid <= s[2]]
            label = (min(cover, key=lambda s: s[2] - s[1])[0] if cover
                     else "host (no benchmark span)")
            gaps_all.append((label, (g1 - g0) * 1e-9))
    n = len(planes)
    by_label: Dict[str, float] = {}
    for label, d in gaps_all:
        by_label[label] = by_label.get(label, 0.0) + d
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) * 1e-9 / n,
        "modules": {k: {"count": c / n, "seconds": t / n}
                    for k, (c, t) in mods.items()},
        "kernels": {k: {"count": c / n, "seconds": t / n}
                    for k, (c, t) in ops.items()},
        "device_ops": sorted(([k, t / n] for k, (_, t) in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(([lbl, d] for lbl, d in gaps_all),
                            key=lambda kv: -kv[1])[:10],
        "idle_by_span": {k: v / n for k, v in by_label.items()},
    }
