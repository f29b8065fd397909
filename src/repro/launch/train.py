"""Training launcher: the production entry point.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1_1b \
        --smoke --steps 50 [--mesh 4x2] [--resume]

On a real pod: omit --smoke, pass --mesh 16x16 (the process count must
match); this box runs the same code path on the smoke configs.  --layers
keeps a config's published widths and cuts its depth to what one chip
holds.  Exits non-zero when the final loss is not finite.
"""

from __future__ import annotations

import argparse
import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.ckpt.manager import CheckpointManager
from repro.configs import get_config, get_smoke
from repro.configs.base import TrainConfig
from repro.core.session import XFASession
from repro.data.pipeline import SyntheticLMData
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.parallel.axes import runtime_mesh
from repro.runtime.trainer import Trainer


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the config to this many layers, widths "
                         "unchanged (0: the config's own depth)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="", help="e.g. 16x16 or 2x16x16")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="artifacts/train")
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--profile-dir", default="",
                    help="register the run + write per-process XFA profile "
                         "snapshot rings here (reduce with: python -m "
                         "repro.profile report DIR; browse runs with: "
                         "python -m repro.profile query ROOT)")
    ap.add_argument("--profile-interval", type=int, default=0,
                    help="steps between snapshot-ring refreshes "
                         "(0: only at end)")
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
                         "degrade to the local ring, never kill the run")
    ap.add_argument("--xfa-host-label", default="",
                    help="override this process's host label in shard "
                         "names and manifests (default: hostname; tests "
                         "and multi-process-per-host fleets set it)")
    ap.add_argument("--xfa-budget-pct", type=float, default=0.0,
                    help="host-tracer overhead budget as a percent of wall "
                         "time (0: governor off, every boundary fully "
                         "timed); hot edges back off to 1-in-k timing "
                         "with unbiased scale-up, counting stays exact")
    return ap.parse_args(argv)


def train(args: argparse.Namespace) -> Tuple[Trainer, Dict[str, Any],
                                               Dict[str, float]]:
    """Build and run the trainer; returns (trainer, final state, metrics
    of the last step)."""
    if args.xfa_host_label:
        from repro.profile import set_host_label
        set_host_label(args.xfa_host_label)
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        axes = {2: ("data", "model"), 3: ("pod", "data", "model")}[len(shape)]
        mesh = make_mesh(shape, axes)

    model = build_model(cfg, impl="auto")
    tcfg = TrainConfig(total_steps=args.steps, learning_rate=args.lr,
                       warmup_steps=max(args.steps // 10, 1),
                       microbatches=args.microbatches,
                       ckpt_interval=args.ckpt_interval,
                       xfa_overhead_budget=args.xfa_budget_pct / 100.0)
    from repro.profile import RetentionPolicy
    trainer = Trainer(model, tcfg,
                      CheckpointManager(args.ckpt_dir, async_save=True),
                      session=XFASession(device_spec=model.fold_spec),
                      profile_dir=args.profile_dir or None,
                      profile_interval=args.profile_interval,
                      profile_retention=RetentionPolicy(
                          keep_last=args.profile_keep_last,
                          max_age_s=args.profile_max_age_s,
                          max_bytes=args.profile_max_bytes),
                      profile_meta=dict(args.profile_meta),
                      xfa_collector=args.xfa_collector)
    data = SyntheticLMData(cfg, args.batch, args.seq)
    with runtime_mesh(mesh):
        state, metrics = trainer.run(jax.random.key(0), data, args.steps,
                                     resume=args.resume)
    return trainer, state, metrics


def main(argv: Optional[List[str]] = None) -> int:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    trainer, _, metrics = train(parse_args(argv))
    print(f"done: {metrics}")
    print(trainer.session.report().render(components=("app",)))
    loss = metrics.get("loss")          # None: no step left to run
    if loss is not None and not math.isfinite(loss):
        print(f"FAILED: final loss {loss} is not finite")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
