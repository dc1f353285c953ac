"""Dispatch-overhead microbench: engine indirection vs direct kernel calls.

The AttentionEngine adds a layer of indirection over the kernel entry
points (spec resolution, ``AttentionState`` packing/unpacking).  Under
``jax.jit`` all of that happens at trace time, so the per-step cost of the
engine path must be indistinguishable from calling the kernels directly —
this bench gates exactly that claim:

* ``decode`` — one jitted chunked decode step: the legacy composition
  (``LLNDecodeState`` + ``core/attention.py:decode_lln_chunk``) vs
  ``AttentionEngine.decode`` on the same ``AttentionState``;
* ``prefill`` — the direct ``kernels/ops.py:lln_prefill`` kernel call vs
  ``AttentionEngine.prefill`` (which additionally assembles the state
  pytree: tails, per-row counters, calibration broadcast).

``derived`` is the ratio engine_us / direct_us (interleaved min-of-K on
jitted, pre-compiled callables) — ~1.0 means the indirection is free.
Writes ``BENCH_dispatch.json`` at the repo root (benchmarks/README.md).

Usage:
    PYTHONPATH=src python -m benchmarks.bench_dispatch [--smoke] \
        [--out PATH] [--repeats K]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.core import attention as ca
from repro.core import lln as core_lln
from repro.core.engine import AttentionEngine
from repro.kernels import ops as kops
from repro.kernels.registry import AttnSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_dispatch.json")


def _qkv(seed, b, n, h, g, d):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, n, h, d)),
            jax.random.normal(kk, (b, n, g, d)),
            jax.random.normal(kv, (b, n, g, d)))


def _time_interleaved(fns, repeats: int):
    """Min-of-``repeats`` per callable, interleaved so drift hits both."""
    for fn in fns:
        jax.block_until_ready(fn())            # warm (compile outside)
    best = [float("inf")] * len(fns)
    for _ in range(repeats):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[i] = min(best[i], time.perf_counter() - t0)
    return [b * 1e6 for b in best]             # us


def run(out_path: str = DEFAULT_OUT, smoke: bool = False,
        repeats: int = 9, verbose: bool = True):
    b, n, t, g, r, d = (2, 64, 4, 2, 2, 16) if smoke else (4, 256, 8, 2, 4, 64)
    h = g * r
    chunk = 32 if smoke else 128
    spec = AttnSpec(impl="lln_diag", causal=True, r=r, lln_chunk=chunk,
                    diag_block=chunk, fixed_ab=2.1)
    eng = AttentionEngine(spec=spec, heads=h, kv_heads=g, head_dim=d,
                          v_dim=d, cache_dtype=jnp.float32)
    q, k, v = _qkv(0, b, n, h, g, d)
    qn, kn, vn = _qkv(1, b, t, h, g, d)
    alpha = jnp.full((h,), 1.3)
    beta = jnp.full((g,), 1.1)

    rows = []

    # --- prefill: legacy composition (direct kernel calls + hand-rolled
    # state assembly, the pre-engine ``attn_prefill`` body) vs engine ------
    def legacy_prefill(q, k, v):
        lln_out, s, z, c_k = kops.lln_prefill(q, k, v, alpha, beta,
                                              chunk=chunk)
        diag = kops.block_diag_fwd(q, k, v, chunk, True)
        out = (0.5 * (lln_out.astype(jnp.float32)
                      + diag.astype(jnp.float32))).astype(v.dtype)
        tail_k, tail_v = k[:, -chunk:], v[:, -chunk:]
        return out, (s, z, c_k, tail_k, tail_v)

    direct_pf = jax.jit(legacy_prefill)
    engine_pf = jax.jit(lambda q, k, v: eng.prefill(q, k, v, max_len=n + t,
                                                    alpha=alpha, beta=beta))
    us_direct, us_engine = _time_interleaved(
        [lambda: direct_pf(q, k, v), lambda: engine_pf(q, k, v)], repeats)
    rows.append(("dispatch_prefill_direct", us_direct, 1.0))
    rows.append(("dispatch_prefill_engine", us_engine,
                 us_engine / max(us_direct, 1e-9)))

    # --- decode step: legacy composition vs engine ------------------------
    _, state = jax.block_until_ready(engine_pf(q, k, v))

    def legacy_step(state, qn, kn, vn):
        st = ca.LLNDecodeState(
            lln=core_lln.LLNState(s=state.s, z=state.z, c_k=state.c_k),
            tail_k=state.tail_k, tail_v=state.tail_v, pos=state.pos)
        return ca.decode_lln_chunk(st, qn, kn, vn, state.alpha, state.beta,
                                   impl="lln_diag", backend="auto")

    legacy_dec = jax.jit(legacy_step)
    engine_dec = jax.jit(lambda state, qn, kn, vn: eng.decode(state, qn,
                                                              kn, vn))
    us_direct, us_engine = _time_interleaved(
        [lambda: legacy_dec(state, qn, kn, vn),
         lambda: engine_dec(state, qn, kn, vn)], repeats)
    rows.append(("dispatch_decode_direct", us_direct, 1.0))
    rows.append(("dispatch_decode_engine", us_engine,
                 us_engine / max(us_direct, 1e-9)))

    report = {
        "host_backend": jax.default_backend(),
        "shape": {"b": b, "n": n, "t": t, "h": h, "g": g, "d": d,
                  "chunk": chunk},
        "repeats": repeats,
        "rows": [{"name": nm, "us_per_call": us, "ratio_vs_direct": dr}
                 for nm, us, dr in rows],
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
    if verbose:
        for nm, us, dr in rows:
            print(f"  {nm:32s} {us:10.1f} us  ratio {dr:.3f}")
    return rows


def run_rows(verbose: bool = True):
    """benchmarks/run.py adapter (no JSON write in the aggregate pass)."""
    return run(out_path="", smoke=True, repeats=3, verbose=verbose)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--repeats", type=int, default=9)
    args = ap.parse_args(argv)
    run(out_path=args.out, smoke=args.smoke, repeats=args.repeats)


if __name__ == "__main__":
    main()
