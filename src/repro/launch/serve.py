"""Serving driver: batched prefill + scanned decode loop.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
      --batch 4 --prompt-len 64 --gen 32

Demonstrates the two cache regimes: softmax KV cache vs the paper's O(d^2)
LLN state (--attn-impl lln_diag), which is what makes long_500k serveable.

Generation runs as a single jitted ``lax.scan`` segment (one dispatch for
the whole tail of the generation, donated cache carry); the first decode
step runs standalone — it carries the compile — and is reported separately
so the tok/s figure measures steady state.  ``--no-scan`` restores the
seed-style one-dispatch-per-token Python loop (the benchmark baseline);
``--no-serve-kernel`` selects ``attn_backend=ref`` (the seed two-pass jnp
path); ``--attn-backend`` picks any registry backend explicitly
(``kernels/registry.py``: auto | pallas | scan | ref).

``--continuous`` switches to the continuous-batching pool
(``launch/batcher.py``): mixed-length synthetic traffic is admitted into
freed slots mid-stream (per-row positions, masked rows), e.g.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
      --continuous --requests 16 --batch 4 --gen-lens 4,4,4,24

and reports goodput (completed tok/s) instead of lockstep tok/s.
``--continuous --speculative`` makes the pool rows speculative (pooled
draft+verify with per-row ``commit_len`` and single-pass verify;
docs/serving.md "Speculative continuous batching") and adds
acceptance-aware goodput to the report:

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
      --continuous --speculative --spec-k 3 --requests 8 --batch 2

The continuous pool carries the robustness layer (docs/serving.md
"Failure handling"): ``--deadline`` puts a wall-clock budget on every
request, ``--queue-cap`` bounds admission, ``--no-health`` disables the
state-health sentinel, ``--fault-plan`` injects a scripted
``launch/faults.py:FaultPlan`` (JSON path or inline literal), and
``--snapshot-dir``/``--snapshot-every``/``--restore`` snapshot the pool
at segment boundaries and resume it after a crash:

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
      --continuous --requests 8 --snapshot-dir /tmp/pool --snapshot-every 2 \
      --fault-plan '{"events": [{"kind": "kill", "segment": 4}]}'
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
      --continuous --requests 0 --snapshot-dir /tmp/pool --restore
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import (flatten_spec_tokens, init_params,
                                make_pool_setup, make_serve_setup,
                                make_spec_setup, sample_token)
from repro.models import build_model, synthetic_batch


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--attn-impl", default=None,
                    choices=[None, "softmax", "lln", "lln_diag",
                             "log_linear"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mesh", default="1,1")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-scan", dest="scan", action="store_false",
                    default=True, help="seed-style per-token dispatch loop")
    ap.add_argument("--no-serve-kernel", dest="serve_kernel",
                    action="store_false", default=True,
                    help="seed two-pass prefill (attn_backend=ref)")
    ap.add_argument("--attn-backend", default=None,
                    choices=[None, "auto", "pallas", "scan", "ref"],
                    help="explicit attention backend (kernels/registry.py)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching pool (mixed-length traffic)")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-then-verify decoding (partial-commit "
                         "verify; see docs/serving.md)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="[--speculative] tied first-k-layers draft depth "
                         "(default: half the target's layers)")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="[--speculative] draft tokens per verify chunk")
    ap.add_argument("--requests", type=int, default=16,
                    help="[--continuous] synthetic requests to serve")
    ap.add_argument("--segment", type=int, default=8,
                    help="[--continuous] decode steps per scanned segment")
    ap.add_argument("--gen-lens", default=None,
                    help="[--continuous] comma list of generation budgets "
                         "(skewed by default)")
    ap.add_argument("--prompt-lens", default=None,
                    help="[--continuous] comma list of prompt lengths")
    ap.add_argument("--deadline", type=float, default=None,
                    help="[--continuous] per-request wall-clock budget (s)")
    ap.add_argument("--queue-cap", type=int, default=1024,
                    help="[--continuous] admission-queue bound")
    ap.add_argument("--drift", action="store_true",
                    help="[--continuous] quarantine rows whose streaming "
                         "concentration drift exceeds the HealthConfig "
                         "threshold (long-horizon serving)")
    ap.add_argument("--no-health", dest="health", action="store_false",
                    default=True,
                    help="[--continuous] disable the state-health sentinel")
    ap.add_argument("--fault-plan", default=None,
                    help="[--continuous] FaultPlan JSON (path or inline)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="[--continuous] pool snapshot directory")
    ap.add_argument("--snapshot-every", type=int, default=4,
                    help="[--continuous] segments between snapshots")
    ap.add_argument("--restore", action="store_true",
                    help="[--continuous] resume from the latest snapshot "
                         "in --snapshot-dir before serving new requests")
    args = ap.parse_args(argv)
    enable_compile_cache()

    overrides = {}
    if args.attn_impl:
        overrides["attn_impl"] = args.attn_impl
    if not args.serve_kernel:
        overrides["use_serve_kernel"] = False
    if args.attn_backend:
        overrides["attn_backend"] = args.attn_backend
    cfg = get_config(args.arch, smoke=args.smoke, **overrides)
    model = build_model(cfg)

    data, model_ax = (int(x) for x in args.mesh.split(","))
    mesh = make_mesh((data, model_ax), ("data", "model"))
    if args.continuous:
        return _run_continuous(cfg, model, mesh, args)
    if args.speculative:
        return _run_speculative(cfg, model, mesh, args)
    max_len = args.prompt_len + args.gen + cfg.num_prefix_tokens
    shape = ShapeSpec("cli", max_len, args.batch, "decode")

    with mesh:
        setup = make_serve_setup(cfg, shape, mesh, multi_pod=False)
        params = init_params(model, mesh, args.seed)
        batch = synthetic_batch(cfg, args.batch, max_len,
                                text_seq=args.prompt_len)
        batch = {k: v for k, v in batch.items()}

        t0 = time.time()
        logits, caches = setup.prefill_fn(params, batch)
        logits.block_until_ready()
        t_prefill = time.time() - t0
        caches = jax.device_put(caches, setup.cache_shardings)

        tok0 = jnp.argmax(logits[:, -1] if logits.ndim == 3 else logits,
                          -1).astype(jnp.int32)
        tok = tok0
        generated = [np.asarray(tok0)]
        pos = batch["inputs"].shape[1]
        if cfg.family == "vlm":
            pos += cfg.num_prefix_tokens

        # First decode step standalone: it carries the compile, so it is
        # excluded from the steady-state tok/s either way.
        t_first = t_steady = 0.0
        if args.gen > 1:
            t0 = time.time()
            logits, caches = setup.decode_fn(params, caches, tok,
                                             jnp.asarray(pos, jnp.int32))
            tok = sample_token(logits, args.temperature,
                               jax.random.PRNGKey(args.seed))
            generated.append(np.asarray(tok))
            jax.block_until_ready(tok)
            t_first = time.time() - t0

        steady_steps = max(args.gen - 2, 0)
        if steady_steps > 0 and args.scan:
            gen_fn = setup.make_generate(steady_steps, args.temperature)
            key = jax.random.PRNGKey(args.seed + 1)
            # AOT-compile the segment so the compile does not pollute the
            # steady-state figure — lowering never executes, so the segment
            # (and its donated cache carry) runs exactly once below.
            gen_fn = gen_fn.lower(params, caches, tok,
                                  jnp.asarray(pos + 1, jnp.int32),
                                  key).compile()
            t0 = time.time()
            toks, caches = gen_fn(params, caches, tok,
                                  jnp.asarray(pos + 1, jnp.int32), key)
            toks.block_until_ready()
            t_steady = time.time() - t0
            generated.extend(np.asarray(toks).T)
        elif steady_steps > 0:
            t0 = time.time()
            for i in range(steady_steps):
                logits, caches = setup.decode_fn(
                    params, caches, tok, jnp.asarray(pos + 1 + i, jnp.int32))
                tok = sample_token(logits, args.temperature,
                                   jax.random.PRNGKey(args.seed + 1 + i))
                generated.append(np.asarray(tok))
            jax.block_until_ready(tok)
            t_steady = time.time() - t0

        toks = np.stack(generated, 1)
        mode = "scan" if args.scan else "loop"
        tok_s = steady_steps * args.batch / max(t_steady, 1e-9)
        print(f"prefill: {args.batch}x{args.prompt_len} in {t_prefill:.3f}s"
              f"  (serve_kernel={cfg.use_serve_kernel})")
        print(f"decode : first step {t_first:.3f}s (compile, excluded); "
              f"{steady_steps} steady steps [{mode}] in {t_steady:.3f}s "
              f"({tok_s:.1f} tok/s)")
        print("sample tokens:", toks[0, :16].tolist())
        return toks


def _run_speculative(cfg, model, mesh, args):
    """Draft-then-verify decoding: tied first-k-layers draft + chunked
    verify with per-row partial commit (docs/serving.md)."""
    draft_layers = args.draft_layers or max(cfg.n_layers // 2, 1)
    steps = max(args.gen - 1, 1)
    max_len = args.prompt_len + args.gen + args.spec_k + 2
    shape = ShapeSpec("spec", max_len, args.batch, "decode")

    with mesh:
        setup = make_spec_setup(cfg, shape, mesh, spec_k=args.spec_k,
                                draft_layers=draft_layers)
        params = init_params(model, mesh, args.seed)
        batch = synthetic_batch(cfg, args.batch, max_len,
                                text_seq=args.prompt_len)

        t0 = time.time()
        logits, tgt_caches, dr_caches = setup.prefill_fn(params, batch)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0
        tok0 = jnp.argmax(logits[:, -1] if logits.ndim == 3 else logits,
                          -1).astype(jnp.int32)

        gen_fn = setup.make_generate(steps, args.temperature)
        pos0 = jnp.asarray(args.prompt_len, jnp.int32)
        key = jax.random.PRNGKey(args.seed + 1)
        gen_fn = gen_fn.lower(params, tgt_caches, dr_caches, tok0, pos0,
                              key).compile()
        t0 = time.time()
        toks, n_emit, n_acc, live, *_ = gen_fn(params, tgt_caches,
                                               dr_caches, tok0, pos0, key)
        jax.block_until_ready(toks)
        t_gen = time.time() - t0

        n_emit_h = np.asarray(n_emit)
        n_acc_h = np.asarray(n_acc)
        live_h = np.asarray(live)
        drafted = float(live_h.sum() * args.spec_k)
        acc_rate = float(n_acc_h.sum()) / max(drafted, 1.0)
        iters_used = [int(np.argmax(np.cumsum(n_emit_h[r]) >= steps)) + 1
                      for r in range(args.batch)]
        tps = float(np.mean([steps / i for i in iters_used]))
        flat = flatten_spec_tokens(toks, n_emit, steps)
        tok_s = steps * args.batch / max(t_gen, 1e-9)
        print(f"prefill: {args.batch}x{args.prompt_len} (target + "
              f"{draft_layers}-layer draft) in {t_prefill:.3f}s")
        print(f"speculative: k={args.spec_k}, draft_layers={draft_layers}; "
              f"{steps} tokens/row in {t_gen:.3f}s ({tok_s:.1f} tok/s over "
              f"the worst-case {steps}-iteration scan; bench_spec times a "
              f"right-sized scan)")
        print(f"  acceptance rate {acc_rate:.2f}, "
              f"tokens/verify-step {tps:.2f} "
              f"(1.0 = non-speculative)")
        print("sample tokens:", flat[0, :16].tolist())
        return flat


def _run_continuous(cfg, model, mesh, args):
    """Continuous-batching pool over mixed-length synthetic traffic."""
    from repro.checkpoint.manager import CheckpointManager
    from repro.core.health import HealthConfig
    from repro.launch.batcher import ContinuousBatcher, synthetic_traffic
    from repro.launch.faults import FaultPlan, SimulatedCrash

    gen_lens = ([int(x) for x in args.gen_lens.split(",")]
                if args.gen_lens else [args.gen // 4 or 1] * 3 + [args.gen])
    prompt_lens = ([int(x) for x in args.prompt_lens.split(",")]
                   if args.prompt_lens else [args.prompt_len])
    # --speculative composes with --continuous: the pool rows run the
    # pooled draft+verify loop (spec_k slack reserved in the cache).
    spec_k = args.spec_k if args.speculative else 0
    draft_layers = (args.draft_layers or max(cfg.n_layers // 2, 1)) \
        if args.speculative else 0
    max_len = max(prompt_lens) + max(gen_lens) + spec_k
    plan = FaultPlan.load(args.fault_plan) if args.fault_plan else None
    mgr = (CheckpointManager(args.snapshot_dir, keep_n=3, interval=1)
           if args.snapshot_dir else None)

    with mesh:
        setup = make_pool_setup(cfg, mesh, slots=args.batch,
                                max_len=max_len, segment=args.segment,
                                temperature=args.temperature,
                                spec_k=spec_k, draft_layers=draft_layers,
                                health=HealthConfig(
                                    check_drift=bool(args.drift))
                                if args.health else None)
        params = init_params(model, mesh, args.seed)
        eng = ContinuousBatcher(setup, params, queue_cap=args.queue_cap,
                                snapshot_mgr=mgr,
                                snapshot_every=(args.snapshot_every
                                                if mgr else 0))
        reqs = synthetic_traffic(args.requests, cfg.vocab, prompt_lens,
                                 gen_lens, seed=args.seed)
        if args.deadline is not None:
            for r in reqs:
                r.deadline_s = args.deadline
        eng.warmup(prompt_lens)
        try:
            stats = eng.run(reqs, key=jax.random.PRNGKey(args.seed + 1),
                            fault_plan=plan, resume=args.restore)
        except SimulatedCrash as e:
            print(f"simulated crash at segment boundary {e.segment}; "
                  f"resume with --restore --snapshot-dir "
                  f"{args.snapshot_dir}")
            return None

    # Same definition as benchmarks/bench_batching.py: useful tokens over
    # dispatched row-steps (+1 prefill-emitted token per request).
    util = stats.completed_tokens / max(
        stats.decode_steps * args.batch + max(stats.admitted, 1), 1)
    print(f"continuous: {args.requests} requests over {args.batch} slots, "
          f"segment={args.segment}, gen_lens={gen_lens}"
          + (f", speculative k={spec_k} draft_layers={draft_layers}"
             if spec_k else ""))
    print(f"  {stats.completed_tokens} tokens in {stats.wall_s:.3f}s "
          f"({stats.completed_tokens / max(stats.wall_s, 1e-9):.1f} tok/s "
          f"goodput), {stats.segments} segments, "
          f"slot utilization {util:.2f}")
    if stats.spec_k:
        print(f"  speculative: acceptance {stats.acceptance_rate:.2f} "
              f"({stats.accepted_tokens}/{stats.drafted_tokens} drafts), "
              f"{stats.goodput_tokens_per_iter:.2f} tokens/verify-iter "
              f"over {stats.verify_iters} iterations")
    by = {}
    for v in stats.statuses.values():
        by[v] = by.get(v, 0) + 1
    print(f"  statuses: {by}; recoveries={stats.recoveries}, "
          f"snapshots={stats.snapshots}, "
          f"stragglers={len(stats.stragglers)}, "
          f"segment EWMA {stats.segment_ewma_s * 1e3:.1f}ms"
          + (f" (restored from step {stats.restored_step})"
             if stats.restored_step is not None else ""))
    if stats.telemetry:
        t = stats.telemetry
        print(f"  concentration: drift_max {t['conc_drift_max']:.2f}, "
              f"log_mass {t['log_mass_mean']:.2f}, "
              f"log_var {t['log_mass_var_mean']:.3f}, "
              f"tau_hat {t['tau_hat_mean']:.3f}"
              + (" [drift quarantine ON]" if args.drift else ""))
    if stats.outputs:
        rid0 = min(stats.outputs)
        print(f"request {rid0} tokens:",
              stats.outputs[rid0][:16].tolist())
    return stats


if __name__ == "__main__":
    main()
