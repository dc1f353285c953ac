"""Speculative-decode bench: tokens/verify-step and acceptance across
k × impl × r.

Each cell runs greedy draft-then-verify generation
(``launch/steps.py:make_spec_setup`` — tied first-``draft_layers`` draft,
chunked target verify, per-row partial commit) for ``steps`` tokens per
row and reports:

* ``acceptance_rate`` — accepted drafts / drafted tokens;
* ``tokens_per_step`` — committed tokens per verify iteration (the
  sequential-dependency win; 1.0 is the non-speculative loop, k+1 the
  ceiling).  This is the gated figure: > 1 whenever any draft survives;
* ``target_passes_per_iter`` — FULL target-transformer passes traced per
  verify iteration (``models/transformer.py:DECODE_PASS_COUNTS``; the
  jitted loop's scan body traces exactly once, so the trace count IS the
  per-iteration dispatch count).  Single-pass verify holds this at 1:
  the score pass returns per-layer k/v residuals and the accepted prefix
  is folded with the O(T d^2) ``lm_commit`` einsum instead of a second
  pass.  Gated <= 1.25 by tests/test_bench_spec.py;
* ``spec_tok_s`` / ``base_tok_s`` — wall-clock tokens/s of the
  speculative loop vs the non-speculative scanned loop on the same
  shape (AOT-compiled, compile excluded; the timed scan is right-sized
  to the iterations the run actually needs, discovered by an untimed
  worst-case probe — greedy decoding is deterministic, so both runs
  commit identical tokens).  On this CPU container the verify pass
  costs ~2 target dispatches (score + commit) and the draft is a large
  fraction of the tiny target, so wall-clock parity is out of reach;
  tokens/step is the hardware-independent metric.

CSV rows follow the repo convention (name, us_per_call, derived) with
``us_per_call`` = wall-us per committed token and ``derived`` =
tokens_per_step.  Writes ``BENCH_spec.json`` at the repo root
(schema: benchmarks/README.md).

Usage:
    PYTHONPATH=src python -m benchmarks.bench_spec [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch.mesh import make_mesh
from repro.launch.steps import (flatten_spec_tokens, make_serve_setup,
                                make_spec_setup)
from repro.models import build_model, synthetic_batch
from repro.models.transformer import DECODE_PASS_COUNTS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_spec.json")


def _cfg(impl: str, r: int, n_layers: int) -> ArchConfig:
    h = 4
    return ArchConfig(
        name=f"bench-spec-{impl}-r{r}", family="dense", n_layers=n_layers,
        d_model=64, n_heads=h, n_kv_heads=h // r, d_ff=128, vocab=256,
        head_dim=16, attn_impl=impl, diag_block=8, lln_chunk=8,
        softmax_chunk=32, lln_fixed_ab=2.1 if impl != "softmax" else 0.0,
        compute_dtype="float32", param_dtype="float32", remat="none",
        tie_embeddings=True)


def _cell(impl: str, r: int, k: int, draft_layers: int, *, batch: int,
          prompt: int, steps: int, n_layers: int):
    cfg = _cfg(impl, r, n_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len = prompt + steps + k + 2
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("spec", max_len, batch, "decode")
    batch_in = synthetic_batch(cfg, batch, max_len, text_seq=prompt)
    with mesh:
        # Non-speculative baseline: the scanned generation loop.
        serve = make_serve_setup(cfg, shape, mesh, multi_pod=False)
        logits, caches = serve.prefill_fn(params, batch_in)
        tok0 = jnp.argmax(logits[:, -1] if logits.ndim == 3 else logits,
                          -1).astype(jnp.int32)
        pos0 = jnp.asarray(prompt, jnp.int32)
        key = jax.random.PRNGKey(1)
        base = serve.make_generate(steps, 0.0)
        base = base.lower(params, caches, tok0, pos0, key).compile()
        t0 = time.perf_counter()
        ref_toks, _ = base(params, caches, tok0, pos0, key)
        jax.block_until_ready(ref_toks)
        t_base = time.perf_counter() - t0

        # Speculative loop on the same shape.  Discovery pass first: run
        # the worst-case-length scan (iters = steps) untimed to learn how
        # many verify iterations this (deterministic, greedy) run really
        # needs, then TIME a right-sized scan — a fixed worst-case scan
        # would keep paying full draft+verify cost for dead iterations
        # after every row has finished, turning wall-clock into an
        # artifact of the scan length rather than of speculation.
        spec = make_spec_setup(cfg, shape, mesh, spec_k=k,
                               draft_layers=draft_layers)
        lg, tc, dc = spec.prefill_fn(params, batch_in)
        tok0s = jnp.argmax(lg[:, -1] if lg.ndim == 3 else lg,
                           -1).astype(jnp.int32)
        probe = spec.make_generate(steps)
        toks, n_emit, n_acc, live, *_ = jax.block_until_ready(
            probe(params, tc, dc, tok0s, pos0, key))
        n_emit_h = np.asarray(n_emit)
        iters_used = [int(np.argmax(np.cumsum(n_emit_h[b_]) >= steps)) + 1
                      for b_ in range(batch)]
        lg, tc, dc = spec.prefill_fn(params, batch_in)   # fresh caches
        gen = spec.make_generate(steps, iters=max(iters_used))
        # Trace-time dispatch audit: lowering traces the scan body once,
        # so the counter delta is full target passes PER verify iteration
        # (score counts; the O(T d^2) residual commit does not).
        DECODE_PASS_COUNTS.clear()
        lowered = gen.lower(params, tc, dc, tok0s, pos0, key)
        target_passes = DECODE_PASS_COUNTS.get(cfg.name, 0)
        draft_passes = DECODE_PASS_COUNTS.get(f"{cfg.name}-draft"
                                              f"{draft_layers}", 0)
        gen = lowered.compile()
        t0 = time.perf_counter()
        toks, n_emit, n_acc, live, *_ = gen(params, tc, dc, tok0s, pos0,
                                            key)
        jax.block_until_ready(toks)
        t_spec = time.perf_counter() - t0

    flat = flatten_spec_tokens(toks, n_emit, steps)
    parity = bool(np.array_equal(flat, np.asarray(ref_toks)))
    n_acc_h, live_h = np.asarray(n_acc), np.asarray(live)
    drafted = float(live_h.sum() * k)
    acc_rate = float(n_acc_h.sum()) / max(drafted, 1.0)
    tokens_per_step = float(np.mean([steps / i for i in iters_used]))
    total = steps * batch
    return {
        "name": f"spec_{impl}_r{r}_k{k}_dl{draft_layers}",
        "us_per_call": t_spec * 1e6 / total,
        "acceptance_rate": acc_rate,
        "tokens_per_step": tokens_per_step,
        "target_passes_per_iter": float(target_passes),
        "draft_passes_per_iter": float(draft_passes),
        "spec_tok_s": total / max(t_spec, 1e-9),
        "base_tok_s": total / max(t_base, 1e-9),
        "speedup_vs_base": t_base / max(t_spec, 1e-9),
        "greedy_parity": parity,
    }


def run(out_path: str = DEFAULT_OUT, smoke: bool = False,
        verbose: bool = True):
    batch, prompt = 2, 16
    if smoke:
        steps, n_layers = 8, 2
        cells = [("lln_diag", 1, 2, 2), ("softmax", 1, 2, 1)]
    else:
        steps, n_layers = 24, 2
        cells = [(impl, r, k, dl)
                 for impl in ("softmax", "lln", "lln_diag")
                 for r in (1, 4)
                 for k, dl in ((2, 1), (4, 2))]
    rows = []
    for impl, r, k, dl in cells:
        rows.append(_cell(impl, r, k, dl, batch=batch, prompt=prompt,
                          steps=steps, n_layers=n_layers))
        if verbose:
            c = rows[-1]
            print(f"  {c['name']:32s} acc {c['acceptance_rate']:.2f}  "
                  f"tok/step {c['tokens_per_step']:.2f}  "
                  f"tgt-passes/iter {c['target_passes_per_iter']:.0f}  "
                  f"parity {c['greedy_parity']}")
    report = {
        "host_backend": jax.default_backend(),
        "shape": {"batch": batch, "prompt": prompt, "steps": steps,
                  "n_layers": n_layers},
        "rows": rows,
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
    return [(c["name"], c["us_per_call"], c["tokens_per_step"])
            for c in rows]


def run_rows(verbose: bool = True):
    """benchmarks/run.py adapter (no JSON write in the aggregate pass)."""
    return run(out_path="", smoke=True, verbose=verbose)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args(argv)
    run(out_path=args.out, smoke=args.smoke)


if __name__ == "__main__":
    main()
