"""Health-sentinel overhead: serving throughput with the sentinel on vs off.

The robustness layer folds a per-row state-health reduction
(``core/health.py:unhealthy_rows`` — non-finite / magnitude / calibration
checks over every cache leaf) into the continuous-batching ``segment_fn``.
Because the reduction is fused into the segment's existing jit (no extra
dispatch, no extra host sync), its cost must be a small fraction of the
decode math.  This benchmark measures that cost directly:

* **sentinel_on**  — ``make_pool_setup(..., health=HealthConfig())``, the
  serving default; and
* **sentinel_off** — ``make_pool_setup(..., health=None)``, which replaces
  the reduction with a constant all-healthy vector;

serve the SAME deterministic request stream through the real
``ContinuousBatcher`` and compare min-of-repeats wall clock.

Gate: overhead <= 2% of the sentinel-off throughput (the ISSUE acceptance
bar).  Writes ``BENCH_robustness.json`` at the repo root (schema:
benchmarks/README.md).  CPU-container numbers are only meaningful relative
to each other on the same host.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_robustness [--smoke] \
        [--out PATH] [--repeats K]
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.configs.base import ArchConfig
from repro.core.health import HealthConfig
from repro.launch.batcher import ContinuousBatcher, synthetic_traffic
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_pool_setup

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_robustness.json")
GATE_PCT = 2.0


def _cfg(impl: str, *, blk: int) -> ArchConfig:
    h = 4
    return ArchConfig(
        name=f"robustness-bench-{impl}", family="dense", n_layers=2,
        d_model=128, n_heads=h, n_kv_heads=h, d_ff=256, vocab=512,
        head_dim=32, attn_impl=impl, diag_block=blk, lln_chunk=blk,
        softmax_chunk=2 * blk,
        lln_fixed_ab=2.1 if impl != "softmax" else 0.0,
        compute_dtype="float32", param_dtype="float32", remat="none",
        tie_embeddings=True)


def bench_one(impl: str, *, slots, n_requests, prompt_len, gen_lens,
              segment, blk, repeats, mesh, verbose) -> dict:
    from repro.models import build_model
    cfg = _cfg(impl, blk=blk)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len = prompt_len + max(gen_lens) + 1
    reqs = synthetic_traffic(n_requests, cfg.vocab, [prompt_len], gen_lens,
                             seed=3)
    useful = sum(rq.gen_len for rq in reqs)

    engines = {}
    for mode, health in (("sentinel_off", None),
                         ("sentinel_on", HealthConfig())):
        pool = make_pool_setup(cfg, mesh, slots=slots, max_len=max_len,
                               segment=segment, health=health)
        eng = ContinuousBatcher(pool, params)
        eng.warmup([prompt_len])
        eng.run(reqs)                      # warm the full stream's shapes
        engines[mode] = eng

    walls = {"sentinel_off": [], "sentinel_on": []}
    for it in range(repeats):
        order = (("sentinel_off", "sentinel_on") if it % 2 == 0
                 else ("sentinel_on", "sentinel_off"))
        for mode in order:
            stats = engines[mode].run(reqs)
            assert stats.completed_tokens == useful
            walls[mode].append(stats.wall_s)
    off_s = min(walls["sentinel_off"])
    on_s = min(walls["sentinel_on"])
    overhead_pct = (on_s - off_s) / off_s * 100.0
    row = {
        "name": impl,
        "traffic": {"requests": n_requests, "slots": slots,
                    "prompt_len": prompt_len, "gen_lens": gen_lens,
                    "segment": segment, "useful_tokens": useful},
        "tok_s": {"sentinel_off": useful / off_s,
                  "sentinel_on": useful / on_s},
        "wall_s": {"sentinel_off": off_s, "sentinel_on": on_s},
        "overhead_pct": overhead_pct,
        "gate_pct": GATE_PCT,
        "pass": overhead_pct <= GATE_PCT,
    }
    if verbose:
        t = row["tok_s"]
        print(f"  off {t['sentinel_off']:7.1f} tok/s -> on "
              f"{t['sentinel_on']:7.1f} tok/s  "
              f"overhead {overhead_pct:+.2f}% "
              f"({'PASS' if row['pass'] else 'FAIL'} <= {GATE_PCT}%)",
              flush=True)
    return row


def run(out_path: str = DEFAULT_OUT, smoke: bool = False,
        repeats: int = 3, verbose: bool = True) -> dict:
    if smoke:
        impls = ["lln_diag"]
        slots, n_requests, prompt_len, segment, blk = 2, 4, 16, 4, 16
        gen_lens = [3, 3, 9]
        repeats = 1
    else:
        impls = ["lln_diag", "softmax"]
        slots, n_requests, prompt_len, segment, blk = 4, 12, 16, 8, 16
        gen_lens = [9, 9, 33]
    mesh = make_mesh((1, 1), ("data", "model"))
    rows = []
    with mesh:
        for impl in impls:
            if verbose:
                print(f"== {impl} ==", flush=True)
            rows.append(bench_one(impl, slots=slots, n_requests=n_requests,
                                  prompt_len=prompt_len, gen_lens=gen_lens,
                                  segment=segment, blk=blk, repeats=repeats,
                                  mesh=mesh, verbose=verbose))
    report = {
        "backend": jax.default_backend(),
        "interpret_mode": jax.default_backend() == "cpu",
        "repeats": repeats,
        "modes": {
            "sentinel_off": "make_pool_setup(health=None): segment_fn "
                            "returns a constant all-healthy row vector",
            "sentinel_on": "make_pool_setup(health=HealthConfig()): "
                           "per-row non-finite/magnitude/calibration "
                           "reduction fused into segment_fn's jit",
        },
        "gate": f"sentinel overhead <= {GATE_PCT}% of sentinel-off wall "
                "clock on every cell",
        "results": rows,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    if verbose:
        print(f"wrote {out_path}")
    return report


def run_rows(verbose: bool = True):
    """benchmarks/run.py adapter: (name, us_per_call, derived) CSV rows —
    us = sentinel-on wall time for the stream, derived = overhead fraction
    vs sentinel-off."""
    report = run(verbose=verbose)
    return [(f"robustness_{row['name']}",
             row["wall_s"]["sentinel_on"] * 1e6,
             row["overhead_pct"] / 100.0) for row in report["results"]]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true", help="one tiny cell (CI)")
    args = ap.parse_args()
    run(args.out, smoke=args.smoke, repeats=args.repeats)


if __name__ == "__main__":
    main()
