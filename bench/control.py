"""Readings that the correctness limits are set from, for one cell.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 6 --out readings.json

In one process, set-up once, then for every seed: the program's numbers as
a run compares them (a short window at the cell's own load); and for every
control seed the same numbers read from the control, the float32 reference
computed with float8 (e4m3, one scale per tensor) products in the
program's place, one step below the bfloat16 the configuration computes
in.  A training cell adds the planted fault that halves the batch
(``bench/faults.py``); a state left unchanged reads 1 on the parameter
change by construction.  Only the chip gives readings that set limits;
``bench/tests`` runs the same functions at the smoke sizes.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time


def serve_readings(c: dict, seeds, control_seeds, seconds: float) -> list:
    from bench import serve

    pool = serve.Pool(c, seeds[0], seconds)
    out = []
    for i, seed in enumerate(seeds):
        if i:
            pool.reseed(seed)
        got = pool.serve(seed, seconds, False)
        ref = {}
        params = pool.eng.params
        gaps = serve.score(c["config"], params, got["sample"], got["outputs"],
                           against=ref)
        row = {"seed": seed, "failed": got["failed"],
               "compiles": got["compiles"],
               "tokens": int(sum(len(o) for o in got["outputs"].values())),
               "program": {"max_logit_gap": float(max(g.max()
                                                      for g in gaps))}}
        if seed in control_seeds:
            cg = serve.score(c["config"], params, got["sample"],
                             got["outputs"], fp8=True, against=ref)
            row["control"] = {"max_logit_gap": float(max(g.max()
                                                         for g in cg))}
        # Drop this seed's weights before the next seed's are made: two
        # copies of a 1.6B model in float32 do not fit beside the pool.
        del params
        out.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    return out


def train_readings(c: dict, seeds, control_seeds) -> list:
    from bench import faults, train

    tr = train.Trainer(c)
    keys = ("loss_gap", "grad_norm_gap", "update_norm_gap")
    out = []
    for seed in seeds:
        hb = tr.batches(seed)
        _, state, _, prog = tr.start(seed, hb)
        del state
        gc.collect()
        ref = train.reference_steps(c["config"], c["mix"], seed, hb)
        got = train.readings(prog, ref)
        row = {"seed": seed, "program": {k: got[k] for k in keys},
               "worst_leaves": [got["grad_leaf"], got["update_leaf"]],
               "still_leaves": got["still_leaves"]}
        if seed in control_seeds:
            ctl = train.readings(train.reference_steps(
                c["config"], c["mix"], seed, hb, fp8=True), ref)
            row["control"] = {k: ctl[k] for k in keys}
            _, state, _, bad = tr.start(seed, hb, fault=faults.half_batch)
            del state
            gc.collect()
            half = train.readings(bad, ref)
            row["half_batch"] = {k: half[k] for k in keys}
        out.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    from bench import harness

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=6.0)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    bench = harness.load_benchmark()
    c = harness.cell(bench, args.workload)
    try:
        harness.require_chips(c["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    t0 = time.perf_counter()
    if c["mix"]["driver"] == "train":
        rows = train_readings(c, seeds, control)
    else:
        rows = serve_readings(c, seeds, control, args.seconds)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "rows": rows,
                   "seconds": time.perf_counter() - t0}, f, indent=1)
    return 0


if __name__ == "__main__":
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path[0:1] = [str(root), str(root / "src")]
    sys.exit(main())
