"""Training driver.

Runs a real training loop on whatever devices exist (CPU smoke scale up to
full pods — the step construction is identical; only the mesh differs),
with checkpoint/resume, straggler watchdog, prefetched data, and periodic
metrics.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --smoke \
      --steps 50 --seq 128 --batch 8 --ckpt-dir /tmp/ckpt

Production pods use the same entry point with --mesh data,model sizes.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.data.pipeline import HostShardedSource, Prefetcher, device_placer
from repro.data.synthetic import lm_batches, mlm_batches
from repro.distributed import sharding as shd
from repro.distributed.straggler import StepWatchdog
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_setup
from repro.models import synthetic_batch


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced config")
    ap.add_argument("--attn-impl", default=None,
                    choices=[None, "softmax", "lln", "lln_diag"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="1,1",
                    help="data,model mesh sizes (devices must exist)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    overrides = {}
    if args.attn_impl:
        overrides["attn_impl"] = args.attn_impl
    cfg = get_config(args.arch, smoke=args.smoke, **overrides)

    data, model_ax = (int(x) for x in args.mesh.split(","))
    mesh = make_mesh((data, model_ax), ("data", "model"))
    shape = ShapeSpec("cli", args.seq, args.batch, "train")

    with mesh:
        setup = make_train_setup(cfg, shape, mesh, multi_pod=False,
                                 peak_lr=args.lr, total_steps=args.steps)

        def init_state():
            return setup.init_fn(jax.random.PRNGKey(args.seed))

        start_step = 0
        mgr = None
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir,
                                    interval=args.ckpt_interval)
            state, start_step = mgr.restore_or_init(init_state,
                                                    setup.state_shardings)
        else:
            state = init_state()

        # Data pipeline: host-sharded + prefetch + device placement.
        if cfg.family == "encoder":
            gen = lambda b, s: mlm_batches(cfg.vocab, b, args.seq, seed=s)
        else:
            gen = lambda b, s: lm_batches(cfg.vocab, b, args.seq, seed=s)
        if cfg.family in ("encdec", "vlm"):
            # Multimodal stubs: synthetic continuous frontends.
            def gen(b, s):
                step = 0
                while True:
                    yield {k: np.asarray(v) for k, v in synthetic_batch(
                        cfg, b, args.seq,
                        key=jax.random.PRNGKey(hash((s, step)) % 2**31)).items()}
                    step += 1
        specs = {k: v.sharding.spec for k, v in setup.batch.items()}
        source = HostShardedSource(gen, args.batch, start_step=start_step)
        pipe = Prefetcher(source, place=device_placer(mesh, specs))

        watchdog = StepWatchdog(
            on_anomaly=lambda r: print(f"[straggler] step {r.step} took "
                                       f"{r.duration:.2f}s ({r.ratio:.1f}x)"))
        history = []
        t_start = time.time()
        for step in range(start_step, args.steps):
            batch = next(pipe)
            watchdog.start()
            state, metrics = setup.step_fn(state, batch)
            loss = float(metrics["loss"])
            watchdog.stop(step)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d}  loss {loss:8.4f}  "
                      f"gnorm {float(metrics['grad_norm']):7.3f}  "
                      f"lr {float(metrics['lr']):.2e}", flush=True)
            history.append({"step": step, "loss": loss})
            if mgr:
                mgr.maybe_save(step, state)
        pipe.close()
        if mgr:
            mgr.finalize(args.steps, state)
        dt = time.time() - t_start
        print(f"done: {args.steps - start_step} steps in {dt:.1f}s "
              f"({(args.steps - start_step) / max(dt, 1e-9):.2f} it/s); "
              f"{len(watchdog.anomalies)} straggler events")
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(history, f)
        return history


if __name__ == "__main__":
    main()
