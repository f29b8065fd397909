"""Find a serving cell's knee once, by a sweep of fixed open-loop rates
on the chip.  Not part of a benchmark run.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 2,3,4,6

One process builds the engine once per rate (weights from the seed are
made once), offers the cell's traffic at each rate for --seconds, and
prints one JSON line per rate: time to first token and inter-token gap
(median and tail), requests due and answered, and the queue left at the
end of the window.  The knee is the highest rate whose queue does not
grow through the window; the cell's mix file then takes about 0.8 of it.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench import drive_serve, stats, traffic
    from bench.run import configure_jax, device_info
    from bench.spec import Spec
    from bench.tracing import Recorder
    from repro.configs.base import ServeConfig
    from repro.serving.engine import ServingEngine
    spec = Spec.load()
    cell = spec.cell(args.workload)
    device = device_info(cell["chips"], True)
    configure_jax()
    cfg, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    engine = drive_serve.build(cfg, args.seed)
    model, params = engine.model, engine.params
    for rate in [float(r) for r in args.rates.split(",")]:
        del engine
        gc.collect()
        engine = ServingEngine(model, params, ServeConfig(**cfg["serve"]))
        drive_serve.warm(engine)
        m = dict(mix, rate_per_s=rate, drain_s=min(mix["drain_s"], 30))
        reqs = traffic.open_loop(m, args.seed, args.seconds,
                                 cfg["program"]["vocab"])
        t = time.monotonic()
        out = drive_serve.open_loop(engine, reqs, args.seconds,
                                    m["drain_s"], Recorder(False))
        win = [s for s in out["served"] if s.req.in_window]
        ttft = [(s.times[0] - s.due_abs) * 1e3 if s.times else float("inf")
                for s in win]
        gaps = [(b - a) * 1e3 for s in win
                for a, b in zip(s.times, s.times[1:])]
        half = args.seconds / 2
        wait = lambda early: stats.percentile(
            [s.handle.admitted_at - s.due_abs for s in win
             if s.handle.admitted_at is not None
             and (s.req.due < half) == early], 50)
        row = {"rate": rate, "due": len(win),
               "queue_wait_p50_s_first_half": wait(True),
               "queue_wait_p50_s_second_half": wait(False),
               "answered": sum(1 for s in win if s.times),
               "ttft_p50_ms": stats.percentile(ttft, 50),
               "ttft_p90_ms": stats.percentile(ttft, 90),
               "itl_p50_ms": stats.percentile(gaps, 50),
               "itl_p95_ms": stats.percentile(gaps, 95),
               "waiting_at_close": len(engine.scheduler.waiting),
               "active_at_close": len(engine.scheduler.active()),
               "ran_s": time.monotonic() - t,
               "device": device["kind"]}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
