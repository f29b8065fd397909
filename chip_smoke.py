#!/usr/bin/env python3
"""Chip smoke test: drive the serving and training entry points once on a
TPU at the published TinyLlama-1.1B widths (22 layers, d_model 2048,
32 q / 4 kv heads, head_dim 64, d_ff 5632, vocab 32000), with random
weights from a fixed seed, and check the results against the plain-jnp
reference path (`impl="ref"`).

    python chip_smoke.py [--out DIR]     # one chip: device, train, serve
    python chip_smoke.py --four-chips    # 2x2 (data, model) mesh trainer
                                         # against the same step on one chip

Run it from the repository root; it imports `src/repro` beside it.  It
runs in one process (a chip belongs to one process at a time) and calls
the launchers' own `main`.  Every phase prints what it found.  Only when
all phases pass is the last line of standard output

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

It exits non-zero, with no such line, when JAX finds no TPU (there is no
CPU fallback) or any phase fails.  The compile cache is
`launch/compile_cache.py`'s: a second run on the same tree compiles less.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "tinyllama_1_1b"
SEED = 0

# -- serving: the launcher's own flags ----------------------------------------
MAX_BATCH, MAX_SEQ, MAX_NEW, PAGE_SIZE = 8, 2048, 64, 64
PROMPT_LO, PROMPT_HI = 128, 1536
SERVE_ARGS = ["--arch", ARCH, "--max-batch", str(MAX_BATCH),
              "--max-seq", str(MAX_SEQ), "--max-new", str(MAX_NEW),
              "--min-prompt", str(PROMPT_LO),
              "--max-prompt", str(PROMPT_HI + 1),
              # compiled chunk widths: 128, 256, 512
              "--prefill-chunk", "512", "--min-chunk-bucket", "128",
              "--page-size", str(PAGE_SIZE)]
N_CLOSED, N_OPEN, OPEN_RATE = 16, 8, 2.0
#: every request's worst case (prompt + max_new - 1 rows) at once, plus
#: the reserved scratch page
PAGES = N_CLOSED * -(-(PROMPT_HI + MAX_NEW - 1) // PAGE_SIZE) + 1
CHECK_ROWS, CHECK_CHUNK = 4, 512

# -- training -----------------------------------------------------------------
#: 16 B/param of train state (bf16 param, f32 master, m, v, bf16 grad)
#: x 1.1e9 params does not fit 16 GiB, so depth is cut and widths kept.
#: Compiling the step for a described v5e gives, at batch 8 x 1024:
#: 8 layers -> 6.30 GiB of arguments + 6.56 GiB of temporaries.
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 8, 1024, 5

# -- tolerances (bf16 parameters and activations on both paths) ---------------
#: first-chunk logits, Pallas vs reference: ||a - b||_2 / ||b||_2
LOGITS_RTOL = 2e-2
#: step-0 loss and gradient global norm, Pallas vs reference: |a-b| / |b|
LOSS_RTOL, GNORM_RTOL = 2e-3, 2e-2
#: loss and gradient norm after the same steps, 2x2 mesh vs one device
MESH_LOSS_RTOL, MESH_GNORM_RTOL = 5e-3, 5e-2

_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    # the backend compile, or its load from the persistent cache on a hit
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------- phases ----
def phase_serve_runs(out: Path) -> str:
    """The serve launcher, closed loop on the contiguous and the paged
    cache, then open loop; each run's exit code covers every request."""
    from repro.launch import serve
    prof = out / "profile"
    runs = {
        "contiguous": ["--requests", str(N_CLOSED)],
        "paged": ["--requests", str(N_CLOSED),
                  "--max-cache-pages", str(PAGES)],
        "open": ["--requests", str(N_OPEN), "--mode", "open",
                 "--rate", str(OPEN_RATE)],
    }
    for label, extra in runs.items():
        print(f"-- serve {label}: {' '.join(extra)}", flush=True)
        rc = serve.main(SERVE_ARGS + extra + [
            "--profile-dir", str(prof), "--profile-label", f"serve-{label}"])
        _check(rc == 0, f"serve {label} exited {rc}")
    from repro.profile.__main__ import main as profile_main
    report = out / "profile_report.txt"
    with open(report, "w") as f, contextlib.redirect_stdout(f):
        rc = profile_main(["report", str(prof)])
    lines = report.read_text().splitlines()
    _check(rc == 0 and lines, f"profile report exited {rc}")
    return (f"3 runs, every request finished; profile report of {prof}: "
            f"{len(lines)} lines -> {report}")


def phase_serve_check() -> str:
    """First-chunk logits on the Pallas path against impl='ref' on the same
    parameters, and Mosaic kernels in the chunk and decode programs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(ARCH)
    fast, slow = build_model(cfg, impl="auto"), build_model(cfg, impl="ref")
    params = fast.init(jax.random.key(SEED))
    table = fast.table()
    rng = np.random.default_rng(SEED)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (CHECK_ROWS, CHECK_CHUNK)),
                         jnp.int32)
    pos = jnp.zeros((CHECK_ROWS,), jnp.int32)
    valid = jnp.full((CHECK_ROWS,), CHECK_CHUNK, jnp.int32)
    cache = fast.init_cache(CHECK_ROWS, MAX_SEQ)
    logits, kernels = {}, []
    for name, model in (("pallas", fast), ("ref", slow)):
        prog = jax.jit(model.forward_chunk).lower(
            params, tokens, table, cache, pos, valid).compile()
        if name == "pallas":
            kernels.append(("chunk", prog.as_text()))
        logits[name] = np.asarray(prog(params, tokens, table, cache, pos,
                                       valid)[0], np.float32)
    a, b = logits["pallas"], logits["ref"]
    _check(a.shape == (CHECK_ROWS, cfg.vocab) and np.isfinite(a).all(),
           f"logits {a.shape}, finite={np.isfinite(a).all()}")
    err = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    agree = float(np.mean(a.argmax(-1) == b.argmax(-1)))

    # the decode tick and the paged chunk and decode programs
    pool = fast.init_cache(MAX_BATCH, MAX_SEQ)
    one = jnp.zeros((MAX_BATCH, 1), jnp.int32)
    kernels.append(("decode", jax.jit(fast.forward_chunk).lower(
        params, one, table, pool, jnp.zeros((MAX_BATCH,), jnp.int32)
    ).compile().as_text()))
    arena = fast.init_paged_cache(PAGES, PAGE_SIZE)
    bt = jnp.zeros((MAX_BATCH, MAX_SEQ // PAGE_SIZE), jnp.int32)
    for name, toks in (("paged chunk", tokens), ("paged decode", one)):
        n = toks.shape[0]
        kernels.append((name, jax.jit(fast.forward_chunk_paged).lower(
            params, toks, table, arena, jnp.zeros((n,), jnp.int32), bt[:n]
        ).compile().as_text()))
    missing = [n for n, text in kernels if "tpu_custom_call" not in text]
    _check(not missing, f"no tpu_custom_call in {missing}")
    _check(err <= LOGITS_RTOL,
           f"logits rel-L2 {err:.3e} > {LOGITS_RTOL:.0e}")
    return (f"logits [{CHECK_ROWS}, {cfg.vocab}] rel-L2 vs ref {err:.3e} "
            f"(limit {LOGITS_RTOL:.0e}), argmax agreement {agree:.2f}; "
            f"tpu_custom_call in {[n for n, _ in kernels]} programs")


def _train_args(out: Path, steps: int, *extra: str):
    return ["--arch", ARCH, "--layers", str(TRAIN_LAYERS),
            "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
            "--steps", str(steps), "--ckpt-dir", str(out / "ckpt"),
            "--ckpt-interval", "0", *extra]


def phase_train_run(out: Path) -> str:
    from repro.configs import get_config
    from repro.launch import train
    print(f"-- train: depth cut {get_config(ARCH).n_layers} -> {TRAIN_LAYERS} "
          f"layers at full width (one chip's memory), batch {TRAIN_BATCH} "
          f"x {TRAIN_SEQ}, {TRAIN_STEPS} steps", flush=True)
    rc = train.main(_train_args(out, TRAIN_STEPS))
    _check(rc == 0, f"train exited {rc} (non-finite loss)")
    return f"{TRAIN_STEPS} Trainer steps, final loss finite"


def phase_train_check() -> str:
    """Step-0 loss and gradient norm on the Pallas path (the trainer's
    loss) against impl='ref' on the same parameters and batch."""
    import jax
    from repro.configs import get_config
    from repro.data.pipeline import SyntheticLMData
    from repro.models import build_model
    from repro.optim.adamw import global_norm

    cfg = dataclasses.replace(get_config(ARCH), n_layers=TRAIN_LAYERS)
    fast = build_model(cfg, impl="auto")
    # full remat only bounds the reference's memory: same math
    slow = build_model(dataclasses.replace(cfg, remat="full"), impl="ref")
    params = fast.init(jax.random.key(SEED))     # the trainer's step-0 params
    batch = SyntheticLMData(cfg, TRAIN_BATCH, TRAIN_SEQ).generate(0)
    got = {}
    for name, model in (("pallas", fast), ("ref", slow)):
        (_, (metrics, _)), grads = jax.jit(jax.value_and_grad(
            model.loss_fn, has_aux=True))(params, batch, model.table())
        got[name] = (float(metrics["loss"]), float(global_norm(grads)))
        del grads
    (lp, gp), (lr, gr) = got["pallas"], got["ref"]
    _check(math.isfinite(lp) and math.isfinite(gp), f"pallas {lp}, {gp}")
    _check(_rel(lp, lr) <= LOSS_RTOL,
           f"loss {lp} vs ref {lr}: rel {_rel(lp, lr):.3e}")
    _check(_rel(gp, gr) <= GNORM_RTOL,
           f"grad norm {gp} vs ref {gr}: rel {_rel(gp, gr):.3e}")
    return (f"step-0 loss {lp:.6f} vs ref {lr:.6f} (rel {_rel(lp, lr):.2e}, "
            f"limit {LOSS_RTOL:.0e}); grad norm {gp:.6f} vs ref {gr:.6f} "
            f"(rel {_rel(gp, gr):.2e}, limit {GNORM_RTOL:.0e})")


def phase_four_chips(out: Path) -> str:
    """The trainer on a 2x2 (data, model) mesh, as `train.py --mesh 2x2`
    runs it, against the same steps on one device."""
    import jax
    from repro.launch import train
    _check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    steps = 2
    _, state, m_mesh = train.train(train.parse_args(
        _train_args(out, steps, "--mesh", "2x2")))
    total, per_dev = {}, {}
    for part in ("params", "opt"):
        leaves = [x for x in jax.tree.leaves(state[part]) if x.ndim]
        total[part] = sum(x.nbytes for x in leaves)
        per_dev[part] = {}
        for x in leaves:
            for shard in x.addressable_shards:
                d = shard.device.id
                per_dev[part][d] = per_dev[part].get(d, 0) + shard.data.nbytes
        print(f"-- {part}: {total[part] / 2**30:.3f} GiB in all; per device "
              + ", ".join(f"{d}: {b / 2**30:.3f} GiB"
                          for d, b in sorted(per_dev[part].items())),
              flush=True)
        _check(len(per_dev[part]) == 4, f"{part} on {sorted(per_dev[part])}")
        _check(max(per_dev[part].values()) < total[part],
               f"{part} replicated whole on a device, not sharded")
    del state
    gc.collect()
    _, state, m_one = train.train(train.parse_args(_train_args(out, steps)))
    del state
    for key, tol in (("loss", MESH_LOSS_RTOL), ("grad_norm", MESH_GNORM_RTOL)):
        a, b = m_mesh[key], m_one[key]
        _check(math.isfinite(a) and _rel(a, b) <= tol,
               f"{key} mesh {a} vs one device {b}: rel {_rel(a, b):.3e}")
    return (f"after {steps} steps: loss mesh {m_mesh['loss']:.6f} vs one "
            f"device {m_one['loss']:.6f} (rel "
            f"{_rel(m_mesh['loss'], m_one['loss']):.2e}, limit "
            f"{MESH_LOSS_RTOL:.0e}); grad norm {m_mesh['grad_norm']:.6f} vs "
            f"{m_one['grad_norm']:.6f}; params and opt state on 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh trainer and its one-device "
                         "comparison")
    ap.add_argument("--out", default=str(ROOT / "artifacts" / "chip_smoke"),
                    help="directory for the profile shard, its report and "
                         "checkpoints")
    args = ap.parse_args(argv)

    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"FAILED: the repro package is not beside this script "
              f"({ROOT / 'src'}): {e}")
        return 2
    import jax
    cache_dir = enable_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"FAILED: no TPU found (JAX backend {jax.default_backend()!r},"
              f" devices {jax.devices()}); this test has no CPU fallback")
        return 1
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"[device] {device}; compile cache {cache_dir}", flush=True)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    if args.four_chips:
        phases = [("four-chips", lambda: phase_four_chips(out))]
    else:
        # training first: its state needs most of the chip's memory
        phases = [("train", lambda: phase_train_run(out)),
                  ("train-check", phase_train_check),
                  ("serve", lambda: phase_serve_runs(out)),
                  ("serve-check", phase_serve_check)]
    failed = []
    t_all = time.monotonic()
    for name, fn in phases:
        t0, c0 = time.monotonic(), _COMPILE_S[0]
        try:
            info, ok = fn(), True
        except Exception:           # noqa: BLE001 — report, fail the run
            traceback.print_exc()
            info, ok = "", False
            failed.append(name)
        gc.collect()
        mem = devs[0].memory_stats() or {}
        print(f"[{name}] {'PASS' if ok else 'FAIL'} in "
              f"{time.monotonic() - t0:.1f} s (compile "
              f"{_COMPILE_S[0] - c0:.1f} s; device memory in use "
              f"{mem.get('bytes_in_use', 0) / 2**30:.2f} GiB, peak "
              f"{mem.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB) {info}",
              flush=True)
    print(f"[total] {time.monotonic() - t_all:.1f} s, compile "
          f"{_COMPILE_S[0]:.1f} s (cache {cache_dir}); failed: {failed}",
          flush=True)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
