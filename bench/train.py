"""Training driver: the program's train step on staged MLM batches.

Set-up builds one object, the program's compiled step (``make_train_setup``)
with its state (weights from the seed, AdamW moments at zero), stages the
mix's distinct batches on the device, and drives the step through its first
``check_steps`` steps on the first batches.  The readings the correctness
check needs are taken there: each step's loss, each leaf's norm of the first
gradient as the optimizer got it (its first moment over ``1 - b1``), and
each leaf's norm of the parameters' change over those steps.  The same
step and state then run the window, cycling through the batches.

After the window the state is freed and the float32 reference
(``bench/reference.py``) repeats the first steps from the same seed.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from pathlib import Path

import numpy as np

from bench import reference, traffic, weights
from bench.program import (CompileCounter, check_layout, peak_bytes,
                           program_config)
from bench.trace import Tracer

TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_out" / "trace"

#: A leaf whose reference gradient norm is below this share of the median
#: leaf's moves under Adam by round-off alone, and is left out of the
#: parameter-change comparison.
STILL_LEAF = 1e-3


def leaf_norms(tree) -> dict:
    """{path: float32 norm} of a tree, computed on the device."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        t))(tree)
    return {jax.tree_util.keystr(p): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(norms)}


def change_norms(after, before) -> dict:
    import jax
    import jax.numpy as jnp
    diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: (x.astype(jnp.float32) - y.astype(jnp.float32)),
        a, b))(after, before)
    return leaf_norms(diff)


def _worst(got: dict, want: dict, keep=None) -> tuple[float, str]:
    """Largest |norm_got - norm_want| over max(norm_want, median norm)."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    worst = (0.0, "")
    for k in keys:
        gap = abs(got[k] - want[k]) / max(want[k], med, 1e-30)
        worst = max(worst, (gap, k))
    return worst


def readings(prog: dict, ref: dict) -> dict:
    """The numbers compared: loss, first gradient and parameter change."""
    loss = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    med = float(np.median(list(ref["grad"].values())))
    moving = {k for k, v in ref["grad"].items() if v >= STILL_LEAF * med}
    grad, grad_leaf = _worst(prog["grad"], ref["grad"])
    step, step_leaf = _worst(prog["change"], ref["change"], moving)
    return {"loss_gap": loss, "grad_norm_gap": grad,
            "update_norm_gap": step, "grad_leaf": grad_leaf,
            "update_leaf": step_leaf,
            "still_leaves": sorted(set(ref["grad"]) - moving)}


def clip_norm(opt: dict) -> float:
    """The global-norm clip; none (``null``) is an infinite one."""
    return float("inf") if opt["clip_norm"] is None else opt["clip_norm"]


def reference_steps(config: dict, mix: dict, seed: int, batches,
                    fp8: bool = False) -> dict:
    """The reference's first ``check_steps`` steps from ``seed``, its step
    counter at the mix's ``start_step``."""
    import jax
    import jax.numpy as jnp

    sz = reference.sizes_of(config)
    opt = mix["optimizer"]
    params = weights.make(reference.layout(sz), seed)
    p0 = jax.tree_util.tree_map(jnp.copy, params)
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    m, v = zeros(params), zeros(params)

    @jax.jit
    def step(params, m, v, i, batch):
        loss, grads = jax.value_and_grad(reference.mlm_loss)(
            params, batch, sz, fp8=fp8)
        params, m, v, clipped = reference.adamw(params, grads, m, v, i, opt)
        return params, m, v, loss, clipped

    out = {"losses": []}
    for i in range(mix["check_steps"]):
        b = {k: jnp.asarray(x) for k, x in batches[i].items()}
        params, m, v, loss, clipped = step(
            params, m, v, jnp.float32(opt["start_step"] + i), b)
        out["losses"].append(float(loss))
        if i == 0:
            out["grad"] = leaf_norms(clipped)
        del clipped
    out["change"] = change_norms(params, p0)
    return out


class Trainer:
    """The program's compiled train step for one cell: set-up."""

    def __init__(self, c: dict):
        from repro.configs.base import ShapeSpec
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_train_setup
        from repro.models import build_model
        from repro.optim import AdamWConfig

        self.config, self.mix = c["config"], c["mix"]
        opt = self.mix["optimizer"]
        if opt["warmup_steps"] != min(500, opt["total_steps"] // 10):
            raise ValueError("the program's schedule warms up over "
                             "min(500, total_steps // 10) steps; the mix "
                             "must state that")
        cfg = program_config(self.config)
        self.sz = reference.sizes_of(self.config)
        self.layout = reference.layout(self.sz)
        self.mesh = make_mesh((1, 1), ("data", "model"))
        with self.mesh:
            self.ts = make_train_setup(
                cfg, ShapeSpec("bench", self.mix["seq"], self.mix["batch"],
                               "train"),
                self.mesh, multi_pod=False, peak_lr=opt["peak_lr"],
                total_steps=opt["total_steps"],
                opt_cfg=AdamWConfig(b1=opt["b1"], b2=opt["b2"],
                                    eps=opt["eps"],
                                    weight_decay=opt["weight_decay"],
                                    clip_norm=clip_norm(opt)))
        check_layout(build_model(cfg), self.layout)
        self.compiles = CompileCounter()

    def batches(self, seed: int) -> list:
        mix = self.mix
        return traffic.mlm_batches(self.sz["vocab"], mix["batch"],
                                   mix["seq"], mix["batches"], seed,
                                   mix["mask_rate"])

    def start(self, seed: int, host_batches, fault=None):
        """State from ``seed`` (AdamW moments at zero, the step counter at
        the mix's ``start_step``, past warm-up, so that each checked update
        is a whole one), driven through the first steps; returns (step,
        state, device batches, the program's readings)."""
        import jax
        import jax.numpy as jnp
        from repro.optim import adamw_init

        mix = self.mix
        start = mix["optimizer"]["start_step"]

        def init(p):
            return {"params": p, "opt": {**adamw_init(p),
                                         "step": jnp.asarray(start, jnp.int32)}}

        with self.mesh:
            state = jax.jit(init, out_shardings=self.ts.state_shardings)(
                weights.make(self.layout, seed))
            batches = [jax.device_put(x) for x in host_batches]
            step = self.ts.step_fn if fault is None else fault(self.ts.step_fn)
            p0 = jax.tree_util.tree_map(jnp.copy, state["params"])
            prog = {"losses": []}
            for i in range(mix["check_steps"]):
                state, m = step(state, batches[i])
                prog["losses"].append(float(m["loss"]))
                if i == 0:
                    prog["grad"] = {
                        k: v / (1.0 - mix["optimizer"]["b1"]) for k, v in
                        leaf_norms(state["opt"]["m"]).items()}
            prog["change"] = change_norms(state["params"], p0)
        return step, state, batches, prog

    def window(self, step, state, batches, seconds: float, trace: bool):
        """Cycle through the batches for ``seconds``; returns the window's
        facts.  The host keeps at most two steps in flight."""
        import jax

        tracer = Tracer(TRACE_DIR if trace else None)
        compiles = self.compiles
        tracer.start()
        span = jax.profiler.TraceAnnotation("bench.window")
        i = first = self.mix["check_steps"]
        pending = deque()
        with self.mesh:
            t_open = time.perf_counter()
            compiles.start()
            span.__enter__()
            while True:
                with jax.profiler.TraceAnnotation("bench.step"):
                    state, m = step(state, batches[i % len(batches)])
                i += 1
                pending.append(m["loss"])
                if len(pending) > 2:
                    with jax.profiler.TraceAnnotation("bench.sync"):
                        pending.popleft().block_until_ready()
                if time.perf_counter() - t_open >= seconds:
                    break
            jax.block_until_ready(state)
            t_close = time.perf_counter()
            span.__exit__(None, None, None)
            compiles.stop()
            tracer.stop()
        return {"t_open": t_open, "window_s": t_close - t_open,
                "steps": i - first, "last_loss": float(m["loss"]),
                "compiles": compiles.in_window, "trace": tracer.summary()}


def run(c: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float, fault=None) -> dict:
    """One run of a training cell; returns its record.  ``fault`` (tests)
    wraps the step function to plant a fault in the timed path."""
    import jax

    tr = Trainer(c)
    host_batches = tr.batches(seed)
    step, state, batches, prog = tr.start(seed, host_batches, fault)
    w = tr.window(step, state, batches, seconds, trace)
    del step, state, batches
    peak = peak_bytes()
    kind = jax.devices()[0].device_kind
    mix = c["mix"]
    del tr
    gc.collect()
    ref = reference_steps(c["config"], mix, seed, host_batches)
    got = readings(prog, ref)
    lim = c["limits"]
    checks = {k: {"value": got[k], "limit": float(lim[k])}
              for k in ("loss_gap", "grad_norm_gap", "update_norm_gap")}
    checks["compiles_in_window"] = {"value": w["compiles"], "limit": 0}
    finite = bool(np.isfinite(w["last_loss"]))
    correct = finite and all(chk["value"] <= chk["limit"]
                             for chk in checks.values())
    return {
        "kind": "train", "correct": correct, "attempted": w["steps"],
        "failed": 0 if finite else w["steps"], "checks": checks,
        "setup_s": w["t_open"] - t_start, "window_s": w["window_s"],
        "memory_peak_bytes": peak, "device_kind": kind,
        "train": {"steps": w["steps"],
                  "tokens_per_step": mix["batch"] * mix["seq"],
                  "readings": got},
        "config": c["config"], "mix": mix, "trace": w["trace"],
    }
