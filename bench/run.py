"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine holding the chips the cell
asks for.  The cell, its configuration, traffic mix, correctness limits
and metrics are all found by name from BENCHMARK.json (see
bench/spec.py).  The run makes its weights and traffic from --seed,
compiles and warms every program the cell uses (set-up), measures for
--seconds, checks what the timed path produced against the plain
float32 reference, and prints one JSON object:

    {"correct", "attempted", "failed", "metrics", "device"
     [, "breakdown"], "checked"}

With --trace 0 the metrics are the cell's end-to-end ones; with
--trace 1 its per-layer ones, from a profiler trace of a few seconds
in the middle of the window.  It exits non-zero, printing no result,
when JAX finds no TPU, fewer chips than the cell asks for, or a device
kind missing from bench/peaks.py.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: the persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_compile_cache"
#: seconds of profiler trace in a --trace 1 run
TRACE_S = 5.0


class Run:
    """Everything a metric reader may read about one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def configure_jax() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # the cache is the checkout's own: no eviction (whose bookkeeping
    # breaks writes where the environment sets a maximum size)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts program compilations (or loads from the persistent cache)
    and persistent-cache hits, so set-up and window can be told apart."""

    def __init__(self) -> None:
        import jax
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _ev(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    from bench.peaks import PEAKS
    devs = jax.devices()
    d = devs[0]
    if require_chip:
        if d.platform != "tpu":
            raise SystemExit(f"no TPU: JAX runs on {d.platform}")
        if len(devs) < chips:
            raise SystemExit(f"{len(devs)} chips, the cell needs {chips}")
        if d.device_kind not in PEAKS:
            raise SystemExit(f"device_kind {d.device_kind!r} is not in "
                             f"bench/peaks.py")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


# -- serving ---------------------------------------------------------------
def run_serve(spec, cell, cfg, mix, seed, seconds, traced, trace_dir,
              counter, model=None, control=False) -> dict:
    import jax
    import numpy as np
    from bench import drive_serve, traffic
    from bench.tracing import Recorder
    vocab = cfg["program"]["vocab"]
    engine = drive_serve.build(cfg, seed, model)
    drive_serve.warm(engine)
    rec = Recorder(traced, max(0.0, seconds / 2 - TRACE_S / 2),
                   min(TRACE_S, seconds), trace_dir)
    rec.wrap_engine(engine)
    reqs = traffic.open_loop(mix, seed, seconds, vocab)
    setup_s = time.monotonic() - T_START
    compiles0 = counter.compiles
    jax.config.update("jax_log_compiles", True)
    out = drive_serve.open_loop(engine, reqs, seconds, mix["drain_s"], rec,
                                mix["check_requests"],
                                mix["check_min_tokens"])
    jax.config.update("jax_log_compiles", False)
    in_window_compiles = counter.compiles - compiles0
    mem = memory_peak()
    served = out["served"]
    errors = [s for s in served if s.handle is not None
              and s.handle.error is not None]
    finished = {s.req.index: np.asarray(s.handle.output, np.int32)
                for s in served if s.handle is not None and s.handle.done
                and s.handle.error is None}
    requests = []
    for s in served:
        h = s.handle
        requests.append({
            "due": s.due_abs, "submit": s.submit,
            "admitted": getattr(h, "admitted_at", None),
            "times": list(s.times), "in_window": s.req.in_window,
            "prompt": len(s.req.prompt), "max_new": s.req.max_new,
            "done": s.req.index in finished})
    engine.stop()
    del engine
    gc.collect()
    # the reference check, once the program's state is freed
    done = [s.req for s in served if s.req.index in finished]
    sample = traffic.check_sample(done, finished, seed,
                                  mix["check_requests"],
                                  mix["check_min_tokens"])
    limits = spec.check(cell["name"])
    checked = {}
    ok = bool(sample) and not errors
    if sample:
        from bench.references import load as load_reference
        ref = load_reference(cfg["reference"])
        got = ref.serve_gaps(cfg["program"], seed,
                             [(r.prompt, finished[r.index]) for r in sample],
                             control=control)
        checked["reference"] = got
        # the control: the fp8 reference's first tokens in the program's
        # place, judged by the same comparison
        gap = got["control_mean_gap" if control else "mean_gap"]
        checked["mean_gap"] = {"value": gap, "limit": limits["mean_gap"]}
        ok = ok and gap <= limits["mean_gap"]
    win = [r for r in requests if r["in_window"]]
    attempted = len(win)
    failed = sum(1 for r in win if not r["times"])
    return dict(ok=ok, attempted=attempted, failed=failed, setup_s=setup_s,
                memory_peak_bytes=mem, checked=checked, rec=rec,
                in_window_compiles=in_window_compiles,
                run=dict(requests=requests, t0=out["t0"],
                         t_end=out["t_end"], calls=rec.calls,
                         steps=rec.steps, trace_window=rec.window))


# -- training --------------------------------------------------------------
def run_train(spec, cell, cfg, mix, seed, seconds, traced, trace_dir,
              counter, model=None, control=False) -> dict:
    import jax
    from bench.drive_train import TrainCell
    from bench.references import load as load_reference
    from bench.tracing import Recorder
    tc = TrainCell(cfg, mix, seed)
    first = tc.first_steps()
    rec = Recorder(traced, max(0.0, seconds / 2 - TRACE_S / 2),
                   min(TRACE_S, seconds), trace_dir)
    setup_s = time.monotonic() - T_START
    compiles0 = counter.compiles
    jax.config.update("jax_log_compiles", True)
    out = tc.window(seconds, rec)
    jax.config.update("jax_log_compiles", False)
    in_window_compiles = counter.compiles - compiles0
    mem = memory_peak()
    tc.close()
    del tc
    gc.collect()
    ref_mod = load_reference(cfg["reference"])
    ref = ref_mod.train_steps(cfg["program"], cfg["train"], seed,
                              first["batches"])
    limits = spec.check(cell["name"])
    checked = compare_train(first, ref, limits)
    if control:
        # the control: the fp8 reference in the program's place, judged
        # by the same comparison; the program's own numbers kept beside
        fp8 = ref_mod.train_steps(cfg["program"], cfg["train"], seed,
                                  first["batches"], quant="fp8")
        program = checked
        checked = compare_train(fp8, ref, limits)
        checked["program"] = program
        checked["first"], checked["control"] = first, fp8
        checked["reference"] = ref
    ok = out["finite"] and all(v["value"] <= v["limit"]
                               for v in checked.values() if "limit" in v)
    return dict(ok=ok, attempted=out["steps"], failed=0 if out["finite"]
                else out["steps"], setup_s=setup_s, memory_peak_bytes=mem,
                checked=checked, rec=rec,
                in_window_compiles=in_window_compiles,
                run=dict(t0=out["t0"], t_end=out["t_end"],
                         steps_done=out["steps"],
                         tokens_per_step=mix["batch"] * mix["seq_len"],
                         seq_len=mix["seq_len"], batch=mix["batch"],
                         steps=rec.steps, trace_window=rec.window))


def leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf: |program norm - reference norm| over the larger of
    the reference's norm of that leaf and its median leaf norm."""
    keys = [k for k in ref if keep is None or k in keep]
    med = sorted(ref[k] for k in keys)[len(keys) // 2]
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def loss_gap(first: dict, ref: dict) -> float:
    """Worst step's |program loss - reference loss| / reference loss."""
    return max(abs(a - b) / abs(b) for a, b in zip(first["losses"],
                                                   ref["losses"]))


def compare_train(first: dict, ref: dict, limits: dict) -> dict:
    """The training numbers compared, each beside its limit: the first
    gradient and the change after the checked steps, by the worst leaf.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of the change.  The
    loss is not compared: neither the fp8 control nor a fault reads far
    enough above the program's own bfloat16-logit gap (PERF.md)."""
    g = ref["grad"]
    med = sorted(g.values())[len(g) // 2]
    moving = {k for k, v in g.items() if v >= 1e-3 * med}
    return {
        "grad_gap": {"value": leaf_gap(first["grad"], g),
                     "limit": limits["grad_gap"]},
        "change_gap": {"value": leaf_gap(first["change"], ref["change"],
                                         moving),
                       "limit": limits["change_gap"]},
    }


# -- the run ----------------------------------------------------------------
def run_cell(spec, name: str, seed: int, seconds: float, traced: bool,
             require_chip: bool = True, control: bool = False) -> dict:
    from bench import flops
    from bench.peaks import PEAKS, peaks_for
    from bench.spec import reader
    from bench.tracing import find_xplane, reduce
    cell = spec.cell(name)
    device = device_info(cell["chips"], require_chip)
    configure_jax()
    counter = CompileCounter()
    cfg = spec.config(cell["config"])
    mix = spec.mix(cell["traffic"])
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    kind = "train" if mix["kind"] == "train" else "serve"
    fn = run_train if kind == "train" else run_serve
    r = fn(spec, cell, cfg, mix, seed, seconds, traced, trace_dir, counter,
           control=control)
    device["memory_peak_bytes"] = r["memory_peak_bytes"]
    trace = None
    if traced:
        xp = find_xplane(trace_dir)
        trace = reduce(xp) if xp else {}
        if trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
    shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(kind=kind, seconds=seconds,
              setup_s=r["setup_s"], dims=flops.Dims.of(cfg["program"]),
              peaks=PEAKS.get(device["kind"]) if require_chip
              else peaks_for("TPU v5 lite"), trace=trace, **r["run"])
    metrics = {}
    for m in spec.metrics(name, traced):
        v = reader(m["name"])(run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": bool(r["ok"]), "attempted": r["attempted"],
              "failed": r["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    print(f"set-up {r['setup_s']:.3f} s; compiles or cache loads: "
          f"{counter.compiles} in all, {r['in_window_compiles']} inside the "
          f"window; persistent cache hits {counter.hits}", file=sys.stderr)
    result["checked"] = {k: v for k, v in r["checked"].items()
                         if "limit" in v}
    if "tokens" in r["checked"].get("reference", {}):
        result["checked"]["tokens_checked"] = \
            r["checked"]["reference"]["tokens"]
    for k, v in result["checked"].items():
        if isinstance(v, dict):
            print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
                  file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the fp8 control in the program's place in "
                    "the comparison (must come out not correct); never "
                    "part of a benchmark run")
    args = ap.parse_args(argv)
    from bench.spec import Spec
    spec = Spec.load()
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), control=bool(args.control))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
