"""Long-horizon decode soak: drift-free state + flat telemetry to 500k tokens.

PR 7's length-robustness layer makes three promises this benchmark checks
end to end on a synthetic 500k-token decode stream (CPU-sized state,
``core/lln.py:decode_chunk`` — the same math the serving pool scans):

* **drift-free state** — with renormalization on (``renorm`` threshold),
  every state leaf stays finite and inside the fp32-safe magnitude bound
  (the health sentinel's ``max_abs``) over the whole horizon, and ``z``
  stays pinned near the threshold while the baseline's ``z`` grows
  without bound (the running-sum pathology);
* **semantics-preserving renorm** — the renormalized run's decode outputs
  match the baseline token-for-token (the normalized LLN form is exactly
  invariant to the reference constant), and its drift-corrected
  ``log_mass`` (``z`` + ``log_scale``) matches the baseline's raw log
  mass — telemetry is renorm-invariant;
* **flat telemetry** — on a stationary stream the streaming concentration
  drift (``core/metrics.py:streaming_concentration``) is flat from 4k to
  500k (a drifting value is the dilution/explosion pathology), with the
  beta(n) length schedule on.

A fourth cell measures the SERVING cost of the telemetry: the same
deterministic request stream through ``ContinuousBatcher`` with
``make_pool_setup(telemetry=True)`` vs ``telemetry=False`` — the fused
reduction must cost <= 2% wall clock (same gate as the health sentinel,
``bench_robustness``).

Writes ``BENCH_longctx.json`` at the repo root (schema:
benchmarks/README.md).

Usage:
    PYTHONPATH=src python -m benchmarks.bench_longctx [--smoke] \
        [--out PATH] [--tokens N]
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lln
from repro.core import moment_matching as mm
from repro.core.metrics import streaming_concentration

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_longctx.json")

GATE_OVERHEAD_PCT = 2.0      # telemetry on-vs-off serving wall clock
GATE_FLAT = 0.5              # max-min of conc_drift over the back half
GATE_FP32_SAFE = 1e6         # health sentinel max_abs: every robust leaf
GATE_GROWTH_RATIO = 4.0      # renorm z_final / z_anchor must stay under
GROWTH_FRACTION = 0.4        # baseline must grow >= this fraction of the
                             # token ratio (z is a running sum: ~linear)

B, H, D, DV = 2, 2, 16, 16
RENORM = 64.0
BETA_N = 0.5
CALIB_LEN = 1024


def _chunk_fn(renorm, beta_n):
    """One jitted soak step: fold T tokens, return state + telemetry."""

    @jax.jit
    def step(state, q, k, v, alpha, beta, pos):
        gain = mm.length_gain(pos.astype(jnp.float32), beta_n=beta_n,
                              calib_len=CALIB_LEN)
        out, state = lln.decode_chunk(state, q, k, v, alpha * gain,
                                      beta, renorm=renorm)
        conc = streaming_concentration(
            state.z, c=jnp.squeeze(state.c_k, axis=(-1, -3)),
            log_scale=state.log_scale, pos=pos[None].repeat(B))
        zmax = jnp.max(state.z)
        leafmax = jnp.maximum(jnp.max(jnp.abs(state.s)),
                              jnp.maximum(zmax,
                                          jnp.max(jnp.abs(state.c_k))))
        return state, out, conc, zmax, leafmax

    return step


def soak(total_tokens: int, chunk: int, *, renorm, beta_n, seed=0) -> dict:
    """Decode ``total_tokens`` synthetic tokens in ``chunk``-sized folds,
    recording telemetry at every fold.  Stationary stream: any drift in
    the instruments is the estimator's, not the data's."""
    steps = total_tokens // chunk
    key = jax.random.PRNGKey(seed)
    alpha = jnp.full((B, H), 0.4, jnp.float32)
    beta = jnp.full((B, H), 0.4, jnp.float32)
    state = lln.LLNState.init(B, H, D, DV)
    step = _chunk_fn(renorm if renorm > 0 else None, beta_n)

    trace = {"pos": [], "conc_drift": [], "log_mass": [], "tau_hat": [],
             "z_max": [], "leaf_max": []}
    out_probe = None
    for i in range(steps):
        kk = jax.random.fold_in(key, i)
        kq, kkk, kv = jax.random.split(kk, 3)
        q = jax.random.normal(kq, (B, chunk, H, D), jnp.float32)
        k = jax.random.normal(kkk, (B, chunk, H, D), jnp.float32)
        v = jax.random.normal(kv, (B, chunk, H, DV), jnp.float32)
        pos = jnp.asarray(i * chunk, jnp.int32)
        state, out, conc, zmax, leafmax = step(state, q, k, v, alpha,
                                               beta, pos)
        if i == 0:
            out_probe = np.asarray(out)      # first-chunk outputs: parity
        trace["pos"].append((i + 1) * chunk)
        trace["conc_drift"].append(float(conc["conc_drift"][0]))
        trace["log_mass"].append(float(conc["log_mass"][0]))
        trace["tau_hat"].append(float(conc["tau_hat"][0]))
        trace["z_max"].append(float(zmax))
        trace["leaf_max"].append(float(leafmax))
    trace["out_probe"] = out_probe
    trace["final_out"] = np.asarray(out)
    return trace


def soak_cells(total_tokens: int, chunk: int, verbose: bool) -> list[dict]:
    """baseline (renorm off) vs renorm (on, beta off) vs robust (renorm +
    beta(n)).  The baseline/renorm pair shares the token stream, so renorm
    invariance is a bitwise-comparable claim."""
    base = soak(total_tokens, chunk, renorm=0.0, beta_n=0.0)
    ren = soak(total_tokens, chunk, renorm=RENORM, beta_n=0.0)
    rob = soak(total_tokens, chunk, renorm=RENORM, beta_n=BETA_N)

    anchor = min(4096, total_tokens // 8)
    k4 = max(0, min(len(base["pos"]) - 2,
                    int(np.searchsorted(base["pos"], anchor))))
    token_ratio = base["pos"][-1] / base["pos"][k4]
    rows = []

    def growth(tr):
        return tr["z_max"][-1] / max(tr["z_max"][k4], 1e-30)

    # 1) baseline grows without bound (a running sum: ~linearly in the
    # token ratio); renorm pins z at the threshold — once pinned it stays
    # flat, so the back half of the renorm trace must not grow.
    g_base = growth(base)
    min_base = GROWTH_FRACTION * token_ratio
    ren_back = ren["z_max"][len(ren["z_max"]) // 2:]
    g_ren_back = max(ren_back) / max(min(ren_back), 1e-30)
    rows.append({
        "name": "z_growth", "anchor_tokens": int(base["pos"][k4]),
        "final_tokens": int(base["pos"][-1]),
        "baseline_ratio": g_base, "baseline_min": min_base,
        "renorm_back_half_ratio": g_ren_back,
        "renorm_z_max": max(ren["z_max"]),
        "pass": bool(g_base >= min_base
                     and g_ren_back <= GATE_GROWTH_RATIO
                     and max(ren["z_max"]) <= RENORM * (1.0 + 1e-3)),
    })
    # 2) every robust leaf finite + fp32-safe over the whole horizon.
    leaf_max = max(rob["leaf_max"])
    rows.append({
        "name": "fp32_safe", "robust_leaf_max": leaf_max,
        "bound": GATE_FP32_SAFE,
        "pass": bool(np.isfinite(leaf_max) and leaf_max <= GATE_FP32_SAFE),
    })
    # 3) renorm-invariant outputs AND telemetry (same stream, renorm
    # on/off): log_mass agrees because log_scale repays the shift exactly.
    lm_err = float(np.max(np.abs(np.asarray(ren["log_mass"])
                                 - np.asarray(base["log_mass"]))))
    out_err = float(np.max(np.abs(ren["final_out"] - base["final_out"])))
    rows.append({
        "name": "renorm_invariance", "log_mass_err": lm_err,
        "final_out_err": out_err,
        "pass": bool(lm_err <= 1e-3 and out_err <= 1e-3),
    })
    # 4) flat concentration drift over the back half, beta(n) on.
    back = np.asarray(rob["conc_drift"][len(rob["conc_drift"]) // 2:])
    spread = float(back.max() - back.min())
    rows.append({
        "name": "telemetry_flat", "drift_spread_back_half": spread,
        "gate": GATE_FLAT, "tau_hat_final": rob["tau_hat"][-1],
        "pass": bool(spread <= GATE_FLAT
                     and np.isfinite(rob["tau_hat"][-1])),
    })
    if verbose:
        for r in rows:
            print(f"  {r['name']}: {'PASS' if r['pass'] else 'FAIL'} "
                  + json.dumps({k: v for k, v in r.items()
                                if k not in ('name', 'pass')}), flush=True)
    return rows


def overhead_cell(repeats: int, smoke: bool, verbose: bool) -> dict:
    """Serving cost of the fused telemetry: telemetry=True vs False
    through the real ContinuousBatcher, min-of-repeats wall clock."""
    from repro.configs.base import ArchConfig
    from repro.launch.batcher import ContinuousBatcher, synthetic_traffic
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_pool_setup
    from repro.models import build_model

    h = 4
    cfg = ArchConfig(
        name="longctx-bench", family="dense", n_layers=2, d_model=128,
        n_heads=h, n_kv_heads=h, d_ff=256, vocab=512, head_dim=32,
        attn_impl="lln_diag", diag_block=16, lln_chunk=16,
        softmax_chunk=32, lln_fixed_ab=2.1, compute_dtype="float32",
        param_dtype="float32", remat="none", tie_embeddings=True)
    slots, n_req, plen, seg = (2, 4, 16, 4) if smoke else (4, 12, 16, 8)
    gen_lens = [3, 3, 9] if smoke else [9, 9, 33]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = synthetic_traffic(n_req, cfg.vocab, [plen], gen_lens, seed=3)
    useful = sum(rq.gen_len for rq in reqs)
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh:
        engines = {}
        for mode, tele in (("telemetry_off", False), ("telemetry_on", True)):
            pool = make_pool_setup(cfg, mesh, slots=slots,
                                   max_len=plen + max(gen_lens) + 1,
                                   segment=seg, telemetry=tele)
            eng = ContinuousBatcher(pool, params)
            eng.warmup([plen])
            eng.run(reqs)
            engines[mode] = eng
        walls = {m: [] for m in engines}
        for it in range(repeats):
            order = (("telemetry_off", "telemetry_on") if it % 2 == 0
                     else ("telemetry_on", "telemetry_off"))
            for mode in order:
                stats = engines[mode].run(reqs)
                assert stats.completed_tokens == useful
                walls[mode].append(stats.wall_s)
    off_s, on_s = min(walls["telemetry_off"]), min(walls["telemetry_on"])
    overhead_pct = (on_s - off_s) / off_s * 100.0
    row = {"name": "telemetry_overhead",
           "traffic": {"requests": n_req, "slots": slots,
                       "prompt_len": plen, "gen_lens": gen_lens,
                       "segment": seg, "useful_tokens": useful},
           "tok_s": {"telemetry_off": useful / off_s,
                     "telemetry_on": useful / on_s},
           "wall_s": {"telemetry_off": off_s, "telemetry_on": on_s},
           "overhead_pct": overhead_pct, "gate_pct": GATE_OVERHEAD_PCT,
           "pass": overhead_pct <= GATE_OVERHEAD_PCT}
    if verbose:
        t = row["tok_s"]
        print(f"  telemetry off {t['telemetry_off']:7.1f} tok/s -> on "
              f"{t['telemetry_on']:7.1f} tok/s  overhead "
              f"{overhead_pct:+.2f}% "
              f"({'PASS' if row['pass'] else 'FAIL'} "
              f"<= {GATE_OVERHEAD_PCT}%)", flush=True)
    return row


def run(out_path: str = DEFAULT_OUT, smoke: bool = False,
        tokens: int = 500_000, repeats: int = 3,
        verbose: bool = True) -> dict:
    if smoke:
        tokens, chunk, repeats = 8_000, 200, 1
    else:
        chunk = 500
    if verbose:
        print(f"== soak: {tokens} tokens, chunk {chunk}, B={B} H={H} "
              f"D={D} ==", flush=True)
    rows = soak_cells(tokens, chunk, verbose)
    if verbose:
        print("== serving telemetry overhead ==", flush=True)
    rows.append(overhead_cell(repeats, smoke, verbose))
    report = {
        "backend": jax.default_backend(),
        "soak": {"tokens": tokens, "chunk": chunk, "batch": B, "heads": H,
                 "head_dim": D, "renorm": RENORM, "beta_n": BETA_N,
                 "calib_len": CALIB_LEN},
        "modes": {
            "baseline": "renorm off, beta(n) off — the unguarded "
                        "running-sum recurrence",
            "renorm": "renorm threshold on (drift-free state), beta(n) "
                      "off — output/telemetry parity cell vs baseline",
            "robust": "renorm + beta(n) length schedule — the serving "
                      "long-horizon configuration",
        },
        "gates": {
            "z_growth": f"baseline z grows >= {GROWTH_FRACTION} x the "
                        f"token ratio from the 4k anchor while the "
                        f"renorm trace's back half is flat "
                        f"(<= {GATE_GROWTH_RATIO}x) and under the "
                        f"threshold",
            "fp32_safe": f"every robust state leaf finite and |x| <= "
                         f"{GATE_FP32_SAFE:g} over the whole horizon",
            "renorm_invariance": "outputs and log_mass match baseline "
                                 "to 1e-3 (renorm is semantics-preserving)",
            "telemetry_flat": f"conc_drift spread over the back half <= "
                              f"{GATE_FLAT}",
            "telemetry_overhead": f"fused telemetry costs <= "
                                  f"{GATE_OVERHEAD_PCT}% serving wall "
                                  "clock",
        },
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
    us = telemetry-on serving wall clock, derived = pass fraction of the
    soak gates."""
    report = run(verbose=verbose)
    rows = report["results"]
    over = next(r for r in rows if r["name"] == "telemetry_overhead")
    passed = sum(1 for r in rows if r["pass"]) / len(rows)
    return [("longctx_soak", over["wall_s"]["telemetry_on"] * 1e6, passed)]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--tokens", type=int, default=500_000)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="8k-token soak + tiny serving cell (CI)")
    args = ap.parse_args()
    report = run(args.out, smoke=args.smoke, tokens=args.tokens,
                 repeats=args.repeats)
    # Smoke-scale wall clocks are too noisy to hard-gate (same policy as
    # bench_robustness); the deterministic soak gates always count.
    gated = [r for r in report["results"]
             if not (args.smoke and r["name"] == "telemetry_overhead")]
    if not all(r["pass"] for r in gated):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
