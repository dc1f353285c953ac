"""Serving-path benchmark: kernelized + scanned serve vs the seed path.

Measures, per (GQA ratio r, attention impl), on a small real model driven
through the real serving machinery (``launch/steps.py:make_serve_setup``):

* **prefill latency** — ``seed``: the seed two-pass prefill
  (``use_serve_kernel=False``: jnp causal scan + a second full-key einsum to
  rebuild the decode state, repeated KV, H-head tails) vs ``kernel``: the
  state-emitting one-pass prefill (``kernels/ops.py:lln_prefill`` — Pallas
  kernel on TPU, its chunked ``lax.scan`` twin on the CPU container — plus
  the block-diag kernel for the lln_diag hybrid, G-head tails).  The softmax
  impl has no LLN state to build, so its prefill path is unchanged by
  construction and its ratio is reported as context, not a gate.
* **steady-state decode tok/s** — ``loop``: the seed per-token Python loop
  (one jitted dispatch per generated token) vs ``scan``: the whole segment
  folded into one jitted ``lax.scan`` with donated cache carry
  (``ServeSetup.make_generate``).  Both exclude the compile-bearing first
  step.
* **chunked multi-token decode** — scoring T draft tokens through
  ``model.decode`` in one dispatch (the ``lln_decode_chunk`` path) vs T
  sequential single-token dispatches (speculative-decode building block).

Writes ``BENCH_serve.json`` at the repo root (schema: benchmarks/README.md).
Absolute numbers on the CPU container are only meaningful relative to each
other on the same host.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_serve [--smoke] \
        [--out PATH] [--repeats K]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_serve_setup
from repro.models import build_model, synthetic_batch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_serve.json")

IMPLS = ("softmax", "lln", "lln_diag")


def _cfg(r: int, impl: str, *, blk: int, serve_kernel: bool) -> ArchConfig:
    h = 4
    return ArchConfig(
        name=f"serve-bench-r{r}", family="dense", n_layers=2, d_model=128,
        n_heads=h, n_kv_heads=h // r, d_ff=256, vocab=512, head_dim=32,
        attn_impl=impl, diag_block=blk, lln_chunk=blk, softmax_chunk=2 * blk,
        use_serve_kernel=serve_kernel, compute_dtype="float32",
        param_dtype="float32", remat="none", tie_embeddings=True)


class _Bench:
    """One (r, impl, mode) serving session on a 1x1 mesh."""

    def __init__(self, cfg, batch_size: int, prompt: int, gen: int, mesh):
        self.cfg, self.gen, self.prompt = cfg, gen, prompt
        self.model = build_model(cfg)
        max_len = prompt + gen
        shape = ShapeSpec("bench", max_len, batch_size, "decode")
        self.setup = make_serve_setup(cfg, shape, mesh, multi_pod=False)
        self.params = self.model.init(jax.random.PRNGKey(0))
        self.batch = synthetic_batch(cfg, batch_size, max_len,
                                     text_seq=prompt)
        self.pos0 = jnp.asarray(prompt, jnp.int32)

    def prefill(self):
        logits, caches = self.setup.prefill_fn(self.params, self.batch)
        jax.block_until_ready(logits)
        return logits, caches

    def first_step(self, logits, caches):
        tok = jnp.argmax(logits[:, -1] if logits.ndim == 3 else logits,
                         -1).astype(jnp.int32)
        logits, caches = self.setup.decode_fn(self.params, caches, tok,
                                              self.pos0)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return tok, caches

    def time_loop_decode(self) -> float:
        """Seed decode: one jitted dispatch per token; first step excluded."""
        tok, caches = self.first_step(*self.prefill())
        jax.block_until_ready(tok)
        t0 = time.perf_counter()
        for i in range(self.gen - 1):
            logits, caches = self.setup.decode_fn(
                self.params, caches, tok,
                self.pos0 + jnp.asarray(1 + i, jnp.int32))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(tok)
        return time.perf_counter() - t0

    def time_scan_decode(self, gen_fn) -> float:
        tok, caches = self.first_step(*self.prefill())
        key = jax.random.PRNGKey(1)
        jax.block_until_ready(tok)
        t0 = time.perf_counter()
        toks, _ = gen_fn(self.params, caches, tok, self.pos0 + 1, key)
        jax.block_until_ready(toks)
        return time.perf_counter() - t0

    def time_chunk_decode(self, chunk_t: int):
        """Score chunk_t draft tokens: one chunked dispatch vs chunk_t
        sequential dispatches (compile excluded for both)."""
        draft = jnp.ones((self.batch["inputs"].shape[0], chunk_t), jnp.int32)
        decode_chunk = jax.jit(
            lambda p, c, t, pos: self.model.decode(p, c, t, pos))
        seq_times, chunk_times = [], []
        for it in range(2):                      # it 0 warms the compiles
            _, caches = self.prefill()
            t0 = time.perf_counter()
            lg, caches = decode_chunk(self.params, caches, draft, self.pos0)
            jax.block_until_ready(lg)
            if it:
                chunk_times.append(time.perf_counter() - t0)
            _, caches = self.prefill()
            t0 = time.perf_counter()
            for i in range(chunk_t):
                lg, caches = self.setup.decode_fn(
                    self.params, caches, draft[:, i],
                    self.pos0 + jnp.asarray(i, jnp.int32))
            jax.block_until_ready(lg)
            if it:
                seq_times.append(time.perf_counter() - t0)
        return min(chunk_times), min(seq_times)


def bench_one(r: int, impl: str, *, batch: int, prompt: int, gen: int,
              blk: int, chunk_t: int, repeats: int, mesh) -> dict:
    modes = {}
    for mode, sk in (("seed", False), ("kernel", True)):
        modes[mode] = _Bench(_cfg(r, impl, blk=blk, serve_kernel=sk),
                             batch, prompt, gen, mesh)
    # --- prefill: warm both, then interleave min-of-K (order alternated
    # per round so host-load drift and order bias hit both modes equally).
    for b in modes.values():
        b.prefill()
    pf = {m: [] for m in modes}
    order = list(modes.items())
    for i in range(repeats):
        for m, b in (order if i % 2 == 0 else order[::-1]):
            t0 = time.perf_counter()
            b.prefill()
            pf[m].append(time.perf_counter() - t0)
    prefill_us = {m: min(v) * 1e6 for m, v in pf.items()}

    # --- decode: seed python loop vs scanned segment (interleaved) -------
    kb = modes["kernel"]
    steps = gen - 1
    gen_fn = kb.setup.make_generate(steps, 0.0)
    kb.time_scan_decode(gen_fn)                  # compile
    modes["seed"].time_loop_decode()             # warm the loop's step
    loop_ts, scan_ts = [], []
    for i in range(repeats):
        if i % 2 == 0:
            loop_ts.append(modes["seed"].time_loop_decode())
            scan_ts.append(kb.time_scan_decode(gen_fn))
        else:
            scan_ts.append(kb.time_scan_decode(gen_fn))
            loop_ts.append(modes["seed"].time_loop_decode())
    loop_s, scan_s = min(loop_ts), min(scan_ts)
    n_tok = steps * batch

    # --- chunked multi-token decode --------------------------------------
    chunk_s, seq_s = kb.time_chunk_decode(chunk_t)

    return {
        "name": f"r{r}_{impl}", "r": r, "impl": impl,
        "shape": {"batch": batch, "prompt": prompt, "gen": gen,
                  "heads": 4, "kv_heads": 4 // r, "head_dim": 32,
                  "block": blk, "chunk_t": chunk_t},
        "prefill_us": prefill_us,
        "prefill_speedup": prefill_us["seed"] / prefill_us["kernel"],
        "decode": {
            "seed_loop_tok_s": n_tok / loop_s,
            "scan_tok_s": n_tok / scan_s,
            "speedup": loop_s / scan_s,
        },
        "decode_chunk": {
            "chunk_us": chunk_s * 1e6,
            "sequential_us": seq_s * 1e6,
            "speedup": seq_s / chunk_s,
        },
    }


def run(out_path: str = DEFAULT_OUT, smoke: bool = False,
        repeats: int = 5, verbose: bool = True) -> dict:
    if smoke:
        cells = [(1, "softmax"), (1, "lln_diag")]
        batch, prompt, gen, blk, chunk_t, repeats = 2, 32, 5, 16, 4, 1
    else:
        cells = [(r, impl) for r in (1, 4) for impl in IMPLS]
        batch, prompt, gen, blk, chunk_t = 2, 128, 17, 32, 8
    mesh = make_mesh((1, 1), ("data", "model"))
    rows = []
    with mesh:
        for r, impl in cells:
            if verbose:
                print(f"== r{r} {impl} ==", flush=True)
            row = bench_one(r, impl, batch=batch, prompt=prompt, gen=gen,
                            blk=blk, chunk_t=chunk_t, repeats=repeats,
                            mesh=mesh)
            rows.append(row)
            if verbose:
                d = row["decode"]
                print(f"  prefill seed {row['prefill_us']['seed']:9.0f}us"
                      f" -> kernel {row['prefill_us']['kernel']:9.0f}us"
                      f" ({row['prefill_speedup']:.2f}x)   decode loop "
                      f"{d['seed_loop_tok_s']:7.0f} -> scan "
                      f"{d['scan_tok_s']:7.0f} tok/s ({d['speedup']:.2f}x)"
                      f"   chunk[{chunk_t}] "
                      f"{row['decode_chunk']['speedup']:.2f}x", flush=True)
    report = {
        "backend": jax.default_backend(),
        "interpret_mode": jax.default_backend() == "cpu",
        "repeats": repeats,
        "modes": {
            "seed": "use_serve_kernel=False prefill (jnp scan + second "
                    "full-key state einsum, repeated KV, H-head tails) + "
                    "per-token Python dispatch loop",
            "kernel": "state-emitting one-pass prefill (Pallas / scan twin) "
                      "+ jitted lax.scan generation segment (donated carry) "
                      "+ G-head tails",
        },
        "gate": "kernel beats seed on steady-state tok/s for every row and "
                "on prefill latency for every LLN row (softmax prefill is "
                "the same code path in both modes; its ratio is context)",
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
    us = kernel-path prefill latency, derived = steady-state scan tok/s."""
    report = run(verbose=verbose)
    return [(f"serve_{row['name']}", row["prefill_us"]["kernel"],
             row["decode"]["scan_tok_s"]) for row in report["results"]]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="two tiny cells (CI)")
    args = ap.parse_args()
    run(args.out, smoke=args.smoke, repeats=args.repeats)


if __name__ == "__main__":
    main()
