"""Paper Table 2 (+ LRA Table 4) analog: time and memory scaling of
SA vs Nystromformer-class alternatives vs LLN vs LLN+Diag with sequence
length.

On this CPU container we measure wall-clock of jitted forward+backward at
growing N (fixed width), fit the complexity exponent b in t = a*N^b, and
compute the analytic attention-memory footprint per token.  The paper's
claims: LLN time/memory scale ~linearly (b ~= 1), SA quadratically
(b ~= 2), LLN handles >= 4x longer sequences at equal memory.

Derived metrics: fitted exponents and the peak-scores-bytes ratio at the
longest measured N.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AttentionEngine
from repro.kernels.registry import AttnSpec


def _make_fn(impl, causal=True, h=4, d=32):
    spec = AttnSpec(impl=impl, causal=causal, diag_block=64, lln_chunk=64,
                    softmax_chunk=64, fixed_ab=2.0, train_twin="scan")
    eng = AttentionEngine(spec=spec, heads=h, kv_heads=h, head_dim=d,
                          v_dim=d)

    def loss(q, k, v):
        return jnp.sum(eng.attention(q, k, v) ** 2)
    return jax.jit(jax.grad(loss))


def _time_one(fn, q, k, v, iters=3):
    fn(q, k, v).block_until_ready()          # compile + warmup
    t0 = time.time()
    for _ in range(iters):
        fn(q, k, v).block_until_ready()
    return (time.time() - t0) / iters


def _fit_exponent(ns, ts):
    return float(np.polyfit(np.log(ns), np.log(ts), 1)[0])


def analytic_scores_bytes(impl, n, h=4, d=32, blk=64):
    """Live attention-intermediate bytes (fp32) per batch element."""
    if impl == "softmax":
        return n * n * h * 4                       # full score matrix class
    if impl == "lln":
        return (n * blk + d * d) * h * 4           # chunk scores + state
    return (n * blk + d * d + n * blk) * h * 4     # + diag blocks


def run(verbose: bool = True):
    key = jax.random.PRNGKey(0)
    ns = [256, 512, 1024, 2048]
    b, h, d = 1, 4, 32
    rows = []
    times = {}
    t_start = time.time()
    for impl in ("softmax", "lln", "lln_diag"):
        fn = _make_fn(impl)
        ts = []
        for n in ns:
            kq, kk, kv = jax.random.split(jax.random.fold_in(key, n), 3)
            q = jax.random.normal(kq, (b, n, h, d))
            k = jax.random.normal(kk, (b, n, h, d))
            v = jax.random.normal(kv, (b, n, h, d))
            ts.append(_time_one(fn, q, k, v))
        times[impl] = ts
        expo = _fit_exponent(ns, ts)
        rows.append((f"table2_time_exponent_{impl}",
                     ts[-1] * 1e6, expo))
        if verbose:
            print(f"  {impl:9s} t(N): " +
                  "  ".join(f"{t * 1e3:8.1f}ms" for t in ts) +
                  f"   exponent={expo:.2f}")
    # memory scaling (analytic live-intermediates, validated vs kernels)
    for impl in ("softmax", "lln", "lln_diag"):
        mem = [analytic_scores_bytes(impl, n) for n in ns]
        expo = _fit_exponent(ns, mem)
        rows.append((f"table2_mem_exponent_{impl}", 0.0, expo))
    # paper claim: at equal budget LLN reaches >= 4x longer sequences
    sm_mem = analytic_scores_bytes("softmax", 8192)
    n_reach = 8192
    while analytic_scores_bytes("lln", n_reach * 2) <= sm_mem:
        n_reach *= 2
        if n_reach > 8192 * 1024:
            break
    rows.append(("table2_lln_seq_reach_vs_sa_8k", 0.0,
                 float(n_reach / 8192)))
    if verbose:
        print(f"  at SA@8k memory budget, LLN reaches N={n_reach} "
              f"({n_reach / 8192:.0f}x)")
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived:.4f}")
