"""Serving launcher: continuous-batching engine over a model checkpoint.

Closed-loop (default): submit --requests up front, drain synchronously —
a throughput benchmark.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_1_1b \
        --smoke --requests 8 [--ckpt artifacts/train]

Open-loop: Poisson arrivals at --rate req/s against the engine running
on its background thread — the latency-under-load benchmark (queue wait
and TTFT are only meaningful here).

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_1_1b \
        --smoke --mode open --rate 4 --requests 32

Exits non-zero when the engine failed, or when any request errored or
did not finish.
"""

from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import numpy as np

from repro.ckpt.manager import CheckpointManager
from repro.configs import get_config, get_smoke
from repro.configs.base import ServeConfig
from repro.models import build_model
from repro.serving import ServingEngine, latency_stats, run_workload


def summarize(done, wall_s: float) -> str:
    s = latency_stats(done, wall_s)
    lines = [f"served {s['requests']:.0f} requests / {s['tokens']:.0f} "
             f"tokens in {s['wall_s']:.2f}s "
             f"({s['throughput_tok_s']:.1f} tok/s)"]
    if "ttft_mean_s" in s:
        lines.append(f"ttft       mean {s['ttft_mean_s'] * 1e3:.1f}ms  "
                     f"p50 {s['ttft_p50_s'] * 1e3:.1f}ms  "
                     f"p95 {s['ttft_p95_s'] * 1e3:.1f}ms")
    if "queue_wait_mean_s" in s:
        lines.append(f"queue_wait mean {s['queue_wait_mean_s'] * 1e3:.1f}ms  "
                     f"p95 {s['queue_wait_p95_s'] * 1e3:.1f}ms")
    if s["truncated"]:
        lines.append(f"truncated prompts: {s['truncated']:.0f}")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--min-prompt", type=int, default=4,
                    help="shortest prompt drawn, tokens")
    ap.add_argument("--max-prompt", type=int, default=0,
                    help="prompts are drawn below this length (0: "
                         "max_seq // 4)")
    ap.add_argument("--ckpt", default="")
    # -- workload ------------------------------------------------------------
    ap.add_argument("--mode", choices=("closed", "open"), default="closed",
                    help="closed: submit all then drain (throughput); open: "
                         "Poisson arrivals on a live engine (latency)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="open-loop mean arrival rate, requests/s")
    # -- scheduler -----------------------------------------------------------
    ap.add_argument("--prefill-chunk", type=int, default=512,
                    help="tokens per in-model prefill chunk: the admission "
                         "chunk and every continuation chunk of a longer "
                         "prompt run one positioned forward_chunk each")
    ap.add_argument("--tail-chunk", type=int, default=0,
                    help="continuation-chunk width (0: same as "
                         "--prefill-chunk; 1 reproduces the legacy "
                         "one-token-per-tick tail feed)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="per-tick prefill token budget across admission "
                         "and continuation chunks (0: unbounded)")
    ap.add_argument("--no-bucket-chunks", action="store_true",
                    help="disable power-of-two chunk-width bucketing "
                         "(every distinct prompt length compiles its own "
                         "prefill program)")
    ap.add_argument("--min-chunk-bucket", type=int, default=8,
                    help="smallest power-of-two chunk bucket")
    ap.add_argument("--prefill-batch", type=int, default=8,
                    help="max slots whose same-width prefill chunks batch "
                         "into ONE forward_chunk call per tick (capped at "
                         "--max-batch; 1 reproduces per-slot batch=1 "
                         "prefill)")
    # -- paged KV-cache pool -------------------------------------------------
    ap.add_argument("--max-cache-pages", type=int, default=0,
                    help="swap the contiguous [max_batch, max_seq] cache "
                         "for a paged arena of this many pages (0: off); "
                         "admission is then gated by free pages, not slot "
                         "count — page 0 is reserved scratch.  Transformer/"
                         "MLA families only; recurrent families keep their "
                         "dense O(1)-per-slot state")
    ap.add_argument("--page-size", type=int, default=64,
                    help="cache rows per page of the paged pool")
    # -- sampling ------------------------------------------------------------
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--sample-seed", type=int, default=0)
    # -- profiling -----------------------------------------------------------
    ap.add_argument("--profile-dir", default="",
                    help="write this replica's XFA profile shard here "
                         "(reduce with: python -m repro.profile report DIR)")
    ap.add_argument("--profile-interval", type=int, default=256,
                    help="decode ticks between shard refreshes")
    ap.add_argument("--profile-label", default="serve",
                    help="shard label; give replicas sharing a host "
                         "distinct labels (serve-0, serve-1, ...)")
    ap.add_argument("--profile-keep-last", type=int, default=8,
                    help="snapshots kept per shard ring (0: unbounded)")
    ap.add_argument("--profile-max-age-s", type=float, default=0.0,
                    help="delete ring snapshots older than this (0: never)")
    ap.add_argument("--profile-max-bytes", type=int, default=0,
                    help="per-run-dir snapshot byte budget (0: unbounded)")
    from repro.profile import kv_pair
    ap.add_argument("--profile-meta", action="append", default=[],
                    type=kv_pair, metavar="KEY=VALUE",
                    help="extra run-manifest metadata (repeatable)")
    ap.add_argument("--xfa-collector", default="", metavar="HOST:PORT",
                    help="stream snapshot-ring deltas to a fleet collector "
                         "(python -m repro.profile collect); failures "
                         "degrade to the local ring, never stall serving")
    ap.add_argument("--xfa-host-label", default="",
                    help="override this replica's host label in shard "
                         "names and manifests (default: hostname)")
    ap.add_argument("--xfa-budget-pct", type=float, default=0.0,
                    help="host-tracer overhead budget as a percent of wall "
                         "time (0: governor off, every boundary fully "
                         "timed); hot edges back off to 1-in-k timing "
                         "with unbiased scale-up, counting stays exact")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.xfa_host_label:
        from repro.profile import set_host_label
        set_host_label(args.xfa_host_label)
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg, impl="auto")
    if args.ckpt:
        like = jax.eval_shape(model.init, jax.random.key(0))
        mgr = CheckpointManager(args.ckpt)
        # restore params out of a full train state checkpoint
        import jax.numpy as jnp
        tree, _ = mgr.restore({"params": jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), like)})
        params = tree["params"]
    else:
        params = model.init(jax.random.key(0))

    engine = ServingEngine(model, params, ServeConfig(
        max_batch=args.max_batch, max_seq_len=args.max_seq,
        prefill_chunk=args.prefill_chunk,
        tail_chunk=args.tail_chunk,
        prefill_budget_tokens=args.prefill_budget,
        bucket_chunks=not args.no_bucket_chunks,
        min_chunk_bucket=args.min_chunk_bucket,
        prefill_batch=args.prefill_batch,
        page_size=args.page_size,
        max_cache_pages=args.max_cache_pages,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        sample_seed=args.sample_seed,
        profile_dir=args.profile_dir,
        profile_interval_ticks=args.profile_interval,
        profile_label=args.profile_label,
        profile_keep_last=args.profile_keep_last,
        profile_max_age_s=args.profile_max_age_s,
        profile_max_bytes=args.profile_max_bytes,
        profile_meta=tuple(args.profile_meta),
        xfa_collector=args.xfa_collector,
        xfa_overhead_budget=args.xfa_budget_pct / 100.0))
    # sampling knobs ride in ServeConfig: submit() defaults to them
    rng = np.random.default_rng(0)
    hi = args.max_prompt or args.max_seq // 4
    prompts = [rng.integers(0, cfg.vocab,
                            int(rng.integers(args.min_prompt, hi)))
               for _ in range(args.requests)]
    t0 = time.monotonic()
    done = run_workload(engine, prompts, args.max_new, mode=args.mode,
                        rate=args.rate, rng=rng)
    print(summarize(done, time.monotonic() - t0))
    errored = [r.uid for r in done if r.error is not None or not r.done]
    if engine.error is not None or errored or len(done) < len(prompts):
        print(f"FAILED: engine error {engine.error!r}; errored requests "
              f"{errored}; {len(done)} of {len(prompts)} finished")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
