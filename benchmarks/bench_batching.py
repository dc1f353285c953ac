"""Continuous vs static batching: goodput under skewed request lengths.

Serves the SAME mixed-length request stream two ways through the real
serving machinery and compares **goodput** — completed (requested) tokens
per second of wall clock, compiles excluded:

* **static** — FCFS waves of ``slots`` requests through
  ``launch/steps.py:make_serve_setup``: one batched prefill per wave, then
  ``ServeSetup.make_generate`` runs until the LONGEST request of the wave
  finishes.  Rows that asked for fewer tokens idle in lockstep (their
  surplus tokens are generated but not counted — that is the goodput gap).
* **continuous** — the slotted pool (``launch/batcher.py``): per-row
  positions and masks let a freed slot admit the next queued request
  mid-stream, so short requests stop paying for the straggler.

Each cell additionally serves the same stream through the **pooled
speculative** engine (``make_pool_setup(spec_k=..., draft_layers=...)``:
paired target+draft row states, draft-k/verify/accept per segment step,
single-pass verify) and reports its goodput plus acceptance and committed
tokens per verify iteration — the sequential-dependency win on top of
continuous admission.

Traffic is deterministic and skewed (most requests want a few tokens, a
minority want many — the shape that hurts static batching in production).
Both engines serve identical Request streams and both are warmed first.

Writes ``BENCH_batching.json`` at the repo root (schema:
benchmarks/README.md).  CPU-container numbers are only meaningful relative
to each other on the same host.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_batching [--smoke] \
        [--out PATH] [--repeats K]
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
from repro.launch.batcher import ContinuousBatcher, synthetic_traffic
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_pool_setup, make_serve_setup

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_batching.json")


def _cfg(r: int, impl: str, *, blk: int) -> ArchConfig:
    # Fixed alpha/beta (the pooled-serving convention): per-request
    # calibration is then prompt-batch independent, which lets the engine
    # admit same-length prompts as one batched prefill (launch/batcher.py).
    h = 4
    return ArchConfig(
        name=f"batching-bench-r{r}", family="dense", n_layers=2,
        d_model=128, n_heads=h, n_kv_heads=h // r, d_ff=256, vocab=512,
        head_dim=32, attn_impl=impl, diag_block=blk, lln_chunk=blk,
        softmax_chunk=2 * blk,
        lln_fixed_ab=2.1 if impl != "softmax" else 0.0,
        compute_dtype="float32", param_dtype="float32", remat="none",
        tie_embeddings=True)


class _StaticWaves:
    """FCFS static batching: waves of ``slots`` through make_generate."""

    def __init__(self, cfg, mesh, params, *, slots, prompt_len, max_len):
        from repro.models import build_model
        self.model = build_model(cfg)
        self.params, self.slots, self.mesh = params, slots, mesh
        shape = ShapeSpec("static", max_len, slots, "decode")
        self.setup = make_serve_setup(cfg, shape, mesh, multi_pod=False)
        self.prompt_len = prompt_len
        self._gen_fns: dict = {}

    def _gen_fn(self, steps: int):
        if steps not in self._gen_fns:
            self._gen_fns[steps] = self.setup.make_generate(steps, 0.0)
        return self._gen_fns[steps]

    def serve(self, reqs) -> dict:
        """Serve all requests; returns rid -> generated tokens."""
        outputs = {}
        for i in range(0, len(reqs), self.slots):
            wave = reqs[i:i + self.slots]
            # Pad the last wave by repeating its tail request; the pad
            # rows' tokens are generated but never counted.
            rows = wave + [wave[-1]] * (self.slots - len(wave))
            prompts = jnp.asarray(np.stack([r.prompt for r in rows]))
            batch = {"inputs": prompts, "targets": prompts,
                     "mask": jnp.ones(prompts.shape, jnp.float32)}
            logits, caches = self.setup.prefill_fn(self.params, batch)
            last = logits[:, -1] if logits.ndim == 3 else logits
            tok0 = jnp.argmax(last, -1).astype(jnp.int32)
            toks = [np.asarray(tok0)]
            steps = max(r.gen_len for r in wave) - 1
            if steps > 0:
                out, _ = self._gen_fn(steps)(
                    self.params, caches, tok0,
                    jnp.asarray(self.prompt_len, jnp.int32),
                    jax.random.PRNGKey(0))
                toks.append(np.asarray(out).T)
            all_toks = np.concatenate([t.reshape(-1, self.slots) for t in
                                       toks], axis=0)      # (1+steps, B)
            for j, r in enumerate(wave):
                outputs[r.rid] = all_toks[:r.gen_len, j]
        return outputs

    def wave_steps(self, reqs) -> int:
        """Decode row-steps dispatched (slot-occupancy denominator)."""
        total = 0
        for i in range(0, len(reqs), self.slots):
            wave = reqs[i:i + self.slots]
            total += (max(r.gen_len for r in wave) - 1) * self.slots
        return total


def bench_one(r: int, impl: str, *, slots, n_requests, prompt_len,
              gen_lens, segment, blk, repeats, mesh, verbose,
              spec_k=2, draft_layers=1) -> dict:
    from repro.models import build_model
    cfg = _cfg(r, impl, blk=blk)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len = prompt_len + max(gen_lens) + 1 + spec_k
    reqs = synthetic_traffic(n_requests, cfg.vocab, [prompt_len], gen_lens,
                             seed=r)
    useful = sum(rq.gen_len for rq in reqs)

    static = _StaticWaves(cfg, mesh, params, slots=slots,
                          prompt_len=prompt_len, max_len=max_len)
    pool = make_pool_setup(cfg, mesh, slots=slots, max_len=max_len,
                           segment=segment)
    eng = ContinuousBatcher(pool, params)
    spec_pool = make_pool_setup(cfg, mesh, slots=slots, max_len=max_len,
                                segment=segment, spec_k=spec_k,
                                draft_layers=draft_layers)
    spec_eng = ContinuousBatcher(spec_pool, params)

    # Warm every compile: static prefill + each distinct wave length, and
    # each pool's prefill/admit/segment.
    static.serve(reqs)
    eng.warmup([prompt_len])
    eng.run(reqs)
    spec_eng.warmup([prompt_len])
    spec_eng.run(reqs)

    st_ts, ct_ts, sp_ts, ct_steps = [], [], [], 0
    spec_stats = None
    for it in range(repeats):
        order = (("static", "cont", "spec") if it % 2 == 0
                 else ("spec", "cont", "static"))
        for mode in order:
            if mode == "static":
                t0 = time.perf_counter()
                static.serve(reqs)
                st_ts.append(time.perf_counter() - t0)
            elif mode == "cont":
                stats = eng.run(reqs)
                assert stats.completed_tokens == useful
                ct_ts.append(stats.wall_s)
                ct_steps = stats.decode_steps
            else:
                spec_stats = spec_eng.run(reqs)
                assert spec_stats.completed_tokens == useful
                sp_ts.append(spec_stats.wall_s)
    st_s, ct_s, sp_s = min(st_ts), min(ct_ts), min(sp_ts)
    row = {
        "name": f"r{r}_{impl}", "r": r, "impl": impl,
        "traffic": {"requests": n_requests, "slots": slots,
                    "prompt_len": prompt_len, "gen_lens": gen_lens,
                    "segment": segment, "useful_tokens": useful},
        "goodput_tok_s": {"static": useful / st_s,
                          "continuous": useful / ct_s,
                          "continuous_spec": useful / sp_s},
        "wall_s": {"static": st_s, "continuous": ct_s,
                   "continuous_spec": sp_s},
        "speedup": st_s / ct_s,
        "continuous_spec": {
            "spec_k": spec_k, "draft_layers": draft_layers,
            "acceptance_rate": spec_stats.acceptance_rate,
            "goodput_tokens_per_iter":
                spec_stats.goodput_tokens_per_iter,
            "verify_iters": spec_stats.verify_iters,
        },
        "slot_utilization": {
            "static": useful / max(static.wave_steps(reqs) + n_requests, 1),
            "continuous": useful / max(ct_steps * slots + n_requests, 1),
        },
    }
    if verbose:
        g = row["goodput_tok_s"]
        u = row["slot_utilization"]
        sp = row["continuous_spec"]
        print(f"  static {g['static']:7.1f} tok/s (util {u['static']:.2f})"
              f" -> continuous {g['continuous']:7.1f} tok/s "
              f"(util {u['continuous']:.2f})  speedup {row['speedup']:.2f}x"
              f"  | spec {g['continuous_spec']:7.1f} tok/s "
              f"(acc {sp['acceptance_rate']:.2f}, "
              f"{sp['goodput_tokens_per_iter']:.2f} tok/iter)",
              flush=True)
    return row


def run(out_path: str = DEFAULT_OUT, smoke: bool = False,
        repeats: int = 3, verbose: bool = True) -> dict:
    if smoke:
        cells = [(1, "lln_diag")]
        slots, n_requests, prompt_len, segment, blk = 2, 5, 16, 4, 16
        gen_lens = [3, 3, 9]
        repeats = 1
    else:
        cells = [(r, impl) for r in (1, 4) for impl in ("softmax",
                                                        "lln_diag")]
        slots, n_requests, prompt_len, segment, blk = 4, 16, 16, 8, 16
        # Skewed: 3/4 of requests want 9 tokens, 1/4 want 129 — the
        # long-tail shape that makes lockstep waves idle short rows.
        gen_lens = [9, 9, 9, 129]
    mesh = make_mesh((1, 1), ("data", "model"))
    rows = []
    with mesh:
        for r, impl in cells:
            if verbose:
                print(f"== r{r} {impl} ==", flush=True)
            rows.append(bench_one(r, impl, slots=slots,
                                  n_requests=n_requests,
                                  prompt_len=prompt_len, gen_lens=gen_lens,
                                  segment=segment, blk=blk,
                                  repeats=repeats, mesh=mesh,
                                  verbose=verbose))
    report = {
        "backend": jax.default_backend(),
        "interpret_mode": jax.default_backend() == "cpu",
        "repeats": repeats,
        "modes": {
            "static": "FCFS waves of `slots` requests: batched prefill + "
                      "one make_generate segment per wave, run until the "
                      "wave's longest request finishes (surplus tokens "
                      "discarded)",
            "continuous": "slotted pool (launch/batcher.py): per-row "
                          "positions + masked rows; freed slots admit the "
                          "next queued request mid-stream via "
                          "dynamic-slice state writes",
            "continuous_spec": "the same slotted pool with speculative "
                               "rows (make_pool_setup spec_k/draft_layers):"
                               " paired target+draft states, one "
                               "draft-k/verify/accept iteration per "
                               "segment step, single-pass verify",
        },
        "gate": "continuous goodput >= 1.3x static on at least one cell "
                "under the skewed traffic",
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
    us = continuous-engine wall time for the stream, derived = goodput
    speedup over static waves."""
    report = run(verbose=verbose)
    return [(f"batching_{row['name']}", row["wall_s"]["continuous"] * 1e6,
             row["speedup"]) for row in report["results"]]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true", help="one tiny cell (CI)")
    args = ap.parse_args()
    run(args.out, smoke=args.smoke, repeats=args.repeats)


if __name__ == "__main__":
    main()
