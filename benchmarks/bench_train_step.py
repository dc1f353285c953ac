"""Train-step benchmark: fwd vs fwd+bwd per attention impl and VJP path.

Times one jit'd training step — attention layer forward, backward and an
AdamW update — for each LLN attention entry point, comparing the two
backward implementations behind the same ``custom_vjp``:

* ``jnp_fallback`` — Pallas forward, legacy ``jax.vjp``-through-the-
  reference backward (``pallas_bwd=False``; the pre-fusion behaviour, kept
  as the ragged-length fallback);
* ``pallas_vjp``   — the fused-VJP path (default): Pallas backward kernels
  on compiled backends, their chunked ``lax.scan`` twins under interpret
  mode (see ``kernels/lln_backward.py``).  Either way the backward reuses
  the saved forward residuals instead of recomputing the forward.

Writes ``BENCH_train_step.json`` at the repo root (see benchmarks/README.md
for the schema).  Runs on whatever backend JAX selects — on the CPU
container the kernels execute in interpret mode, so absolute numbers are
only meaningful relative to each other on the same host.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_train_step [--smoke] \
        [--out PATH] [--repeats K]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_train_step.json")

IMPLS = ("lln_causal", "lln_bidir", "lln_diag")


@dataclasses.dataclass(frozen=True)
class Shape:
    b: int
    n: int
    h: int
    g: int
    d: int
    e: int
    chunk: int

    @property
    def name(self) -> str:
        return (f"b{self.b}_n{self.n}_h{self.h}_g{self.g}"
                f"_d{self.d}_c{self.chunk}")


SHAPES = [
    Shape(b=1, n=512, h=8, g=2, d=64, e=128, chunk=128),
    Shape(b=2, n=512, h=8, g=2, d=64, e=128, chunk=128),
    Shape(b=1, n=1024, h=8, g=2, d=64, e=128, chunk=128),
]
SMOKE_SHAPES = [Shape(b=1, n=64, h=2, g=1, d=8, e=16, chunk=32)]


def _attn(impl: str, q, k, v, alpha, beta, chunk: int, pallas_bwd: bool):
    if impl == "lln_causal":
        return kops.lln_attention(q, k, v, alpha, beta, True, chunk,
                                  pallas_bwd)
    if impl == "lln_bidir":
        return kops.lln_attention(q, k, v, alpha, beta, False, chunk,
                                  pallas_bwd)
    if impl == "lln_diag":
        return kops.lln_diag_attention(q, k, v, alpha, beta, True, chunk,
                                       pallas_bwd)
    raise ValueError(impl)


def _make_problem(shape: Shape, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (shape.b, shape.n, shape.e))
    y = jax.random.normal(ks[1], (shape.b, shape.n, shape.e))
    params = {
        "wq": jax.random.normal(ks[2], (shape.e, shape.h * shape.d)) * 0.05,
        "wk": jax.random.normal(ks[3], (shape.e, shape.g * shape.d)) * 0.05,
        "wv": jax.random.normal(ks[4], (shape.e, shape.g * shape.d)) * 0.05,
        "wo": jax.random.normal(ks[5], (shape.h * shape.d, shape.e)) * 0.05,
    }
    alpha = jnp.full((shape.h,), 1.2)
    beta = jnp.full((shape.g,), 1.0)
    return x, y, params, alpha, beta


def _loss_fn(impl: str, shape: Shape, pallas_bwd: bool, alpha, beta):
    def loss(params, x, y):
        b, n = x.shape[:2]
        q = (x @ params["wq"]).reshape(b, n, shape.h, shape.d)
        k = (x @ params["wk"]).reshape(b, n, shape.g, shape.d)
        v = (x @ params["wv"]).reshape(b, n, shape.g, shape.d)
        out = _attn(impl, q, k, v, alpha, beta, shape.chunk, pallas_bwd)
        pred = out.reshape(b, n, shape.h * shape.d) @ params["wo"]
        return jnp.mean((pred - y) ** 2)
    return loss


def _time_interleaved(fns_args: list, repeats: int = 7) -> list:
    """Min wall time in microseconds for each (fn, args) pair.

    All candidates are warmed first (compile excluded), then the timed
    rounds interleave the candidates so host-load drift hits every path
    equally; min-of-rounds is the standard low-variance estimator for a
    deterministic jit'd step on a noisy container."""
    for fn, args in fns_args:
        jax.block_until_ready(fn(*args))
    samples = [[] for _ in fns_args]
    for _ in range(repeats):
        for i, (fn, args) in enumerate(fns_args):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            samples[i].append((time.perf_counter() - t0) * 1e6)
    return [min(s) for s in samples]


def bench_shape(shape: Shape, repeats: int) -> dict:
    x, y, params, alpha, beta = _make_problem(shape)
    opt_state = adamw_init(params)
    cfg = AdamWConfig()
    row: dict = {"shape": dataclasses.asdict(shape)}
    for impl in IMPLS:
        fwd = jax.jit(_loss_fn(impl, shape, True, alpha, beta))
        steps = {}
        for mode, pallas_bwd in (("jnp_fallback", False),
                                 ("pallas_vjp", True)):
            loss = _loss_fn(impl, shape, pallas_bwd, alpha, beta)

            @jax.jit
            def step(params, opt_state, x, y, loss=loss):
                g = jax.grad(loss)(params, x, y)
                return adamw_update(g, opt_state, params, 1e-3, cfg)

            steps[mode] = step
        fwd_us, jnp_us, pallas_us = _time_interleaved(
            [(fwd, (params, x, y)),
             (steps["jnp_fallback"], (params, opt_state, x, y)),
             (steps["pallas_vjp"], (params, opt_state, x, y))],
            repeats=repeats)
        row[impl] = {
            "fwd_us": fwd_us,
            "fwd_bwd_us": {"jnp_fallback": jnp_us, "pallas_vjp": pallas_us},
            "bwd_speedup": jnp_us / pallas_us,
        }
    return row


def run(out_path: str = DEFAULT_OUT, smoke: bool = False,
        repeats: int = 7, verbose: bool = True) -> dict:
    shapes = SMOKE_SHAPES if smoke else SHAPES
    rows = []
    for shape in shapes:
        if verbose:
            print(f"== {shape.name} ==", flush=True)
        row = bench_shape(shape, repeats)
        rows.append({"name": shape.name, **row})
        if verbose:
            for impl in IMPLS:
                e = row[impl]
                print(f"  {impl:11s} fwd {e['fwd_us']:9.0f}us   "
                      f"fwd+bwd jnp {e['fwd_bwd_us']['jnp_fallback']:9.0f}us"
                      f" -> pallas {e['fwd_bwd_us']['pallas_vjp']:9.0f}us"
                      f"  ({e['bwd_speedup']:.2f}x)", flush=True)
    report = {
        "backend": jax.default_backend(),
        "interpret_mode": jax.default_backend() == "cpu",
        "repeats": repeats,
        "modes": {
            "jnp_fallback": "Pallas forward, legacy jax.vjp reference "
                            "backward (pallas_bwd=False)",
            "pallas_vjp": "fused VJP: Pallas backward kernels (compiled) / "
                          "their lax.scan twins (interpret), reusing saved "
                          "forward residuals (default)",
        },
        "results": rows,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    if verbose:
        print(f"wrote {out_path}")
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--smoke", action="store_true",
                    help="single tiny shape (CI)")
    args = ap.parse_args()
    run(args.out, smoke=args.smoke, repeats=args.repeats)


if __name__ == "__main__":
    main()
