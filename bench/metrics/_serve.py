"""Shared arithmetic of the serving readers (not a metric itself)."""

from __future__ import annotations

import math

from bench import flops


def in_window(run):
    return [r for r in run.requests if r["in_window"]]


def ttfts_ms(run):
    """From each in-window request's due time to its first token; a
    request that never answered counts as infinitely late."""
    return [(r["times"][0] - r["due"]) * 1e3 if r["times"] else math.inf
            for r in in_window(run)]


def token_gaps_ms(run):
    return [(b - a) * 1e3 for r in in_window(run)
            for a, b in zip(r["times"], r["times"][1:])]


def calls(run, kind):
    return [c for c in (run.calls or []) if c["kind"] == kind]


def traced_flops(run) -> float:
    return sum(flops.serve_step_flops(run.dims, c["rows"])
               for c in (run.calls or []))


def module_seconds(run, name: str):
    """(count, device seconds) of the program whose module name holds
    `name`, over the traced window."""
    mods = (run.trace or {}).get("modules", {})
    hits = [v for k, v in mods.items() if name in k]
    if not hits:
        return 0, 0.0
    return (sum(v["count"] for v in hits), sum(v["seconds"] for v in hits))


def roofline(run, kernel: str, kind: str, cost) -> float:
    """Least time the calls' work allows over the kernel's traced time,
    in percent; None where the trace holds no such kernel."""
    k = (run.trace or {}).get("kernels", {}).get(kernel)
    cs = calls(run, kind)
    if not k or not k["seconds"] or not cs:
        return None
    least = sum(run.dims.layers * flops.min_time(cost(run.dims, c["rows"]),
                                                 run.peaks)[0] for c in cs)
    return 100.0 * least / k["seconds"]


def idle_pct(run):
    t = run.trace
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def mfu(run):
    t = run.trace
    if not t or not t.get("window_s") or not run.calls:
        return None
    return 100.0 * traced_flops(run) / (t["window_s"]
                                        * run.peaks["bf16_flops"])
