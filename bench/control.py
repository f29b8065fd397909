"""Readings that the correctness limits are set from.  Not part of a
benchmark run.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        --seconds <s> [--out FILE]

For each seed, in one process: the cell's own set-up and window at its
own load, then the reference check with the fp8 control in the
program's place (`run_serve`/`run_train` with control=True), so
`control_correct` is the harness's own verdict on the control and has
to come out false.  A serving cell prints, per seed, the program's mean
and widest logit gap (the lower reading's sample) and the control's at
the same positions (the upper reading's).  A training cell prints the
program's loss, gradient and change gaps against the reference, and the
same numbers for the fp8 control and for half of the batch left out,
each put in the program's place.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from bench import run
    from bench.references import load as load_reference
    from bench.spec import Spec
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    spec = Spec.load()
    cell = spec.cell(args.workload)
    run.device_info(cell["chips"], True)
    run.configure_jax()
    counter = run.CompileCounter()
    cfg, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    model = build_model(ModelConfig(**cfg["program"]))
    ref_mod = load_reference(cfg["reference"])
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        tdir = tempfile.mkdtemp()
        fn = run.run_train if mix["kind"] == "train" else run.run_serve
        r = fn(spec, cell, cfg, mix, seed, args.seconds, False, tdir,
               counter, model, control=True)
        got = r["checked"]
        # the harness's verdict with the control in the program's place
        row = {"seed": seed, "control_correct": r["ok"]}
        if mix["kind"] == "train":
            lim = spec.check(cell["name"])
            first, ref = got["first"], got["reference"]
            row["program"] = {k: v["value"] for k, v in got["program"].items()}
            row["control_fp8"] = {k: got[k]["value"] for k in lim}
            half = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
                    for b in first["batches"]]
            hb = ref_mod.train_steps(cfg["program"], cfg["train"], seed,
                                     half)
            row["fault_half_batch"] = {
                k: v["value"] for k, v in
                run.compare_train(hb, ref, lim).items()}
            for name, res in (("program", first), ("control_fp8",
                              got["control"]), ("fault_half_batch", hb)):
                row[name]["loss_gap"] = run.loss_gap(res, ref)
        else:
            g = got.get("reference", {})
            row.update({
                "program_mean_gap": g.get("mean_gap"),
                "control_mean_gap": g.get("control_mean_gap"),
                "limit": got.get("mean_gap", {}).get("limit"),
                "program_gap": g.get("gap"), "control_gap": g.get("control_gap"),
                "tokens": g.get("tokens"), "attempted": r["attempted"],
                "failed": r["failed"]})
        row["memory_peak_bytes"] = r["memory_peak_bytes"]
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        del r
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
