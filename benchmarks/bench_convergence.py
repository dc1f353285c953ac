"""Paper Fig. 8a / Table 1 proxy: LLN(+Diag) convergence vs Softmax
Attention on RoBERTa-style MLM pre-training (synthetic Markov corpus —
GLUE itself is not available offline; the tracked quantity is the paper's
own headline evidence, the loss-curve gap).

Also logs the moment-matched alpha/beta trajectory (Fig. 9 analog).

Derived metrics:
  * final-loss gap |LLN+Diag - SA| (paper: curves overlap);
  * final-loss gap |LLN - SA|;
  * mean alpha over training (paper: ~2.0-2.2).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.attention import batch_alpha_beta
from repro.kernels.registry import AttnSpec
from repro.data.synthetic import mlm_batches
from repro.models import build_model
from repro.optim import AdamWConfig, adamw_init, adamw_update


def _train_curve(cfg, steps, seed=0, lr=3e-3, batch=8, seq=128,
                 track_alpha=False):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    state = adamw_init(params)
    opt_cfg = AdamWConfig(weight_decay=0.01)

    @jax.jit
    def step_fn(params, state, b):
        loss, grads = jax.value_and_grad(model.loss)(params, b)
        params, state, _ = adamw_update(grads, state, params, lr, opt_cfg)
        return params, state, loss

    @jax.jit
    def alpha_of(params, b):
        # probe layer-0 q/k statistics -> the dynamic (alpha, beta)
        from repro.models.layers import apply_norm, dense, embed_lookup
        lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
        x = embed_lookup(params["embed"], b["inputs"], cfg.cdtype)
        h = apply_norm(lp["ln1"], x, cfg.norm)
        bq, n, _ = h.shape
        q = dense(lp["attn"]["q_w"], h, cfg.cdtype).reshape(
            bq, n, cfg.n_heads, cfg.hd)
        k = dense(lp["attn"]["k_w"], h, cfg.cdtype).reshape(
            bq, n, cfg.n_kv_heads, cfg.hd)
        a, b_ = batch_alpha_beta(q, k, AttnSpec())
        return jnp.mean(a), jnp.mean(b_)

    gen = mlm_batches(cfg.vocab, batch, seq, seed=0)
    losses, alphas = [], []
    for _ in range(steps):
        b = {k: jnp.asarray(v) for k, v in next(gen).items()}
        params, state, loss = step_fn(params, state, b)
        losses.append(float(loss))
        if track_alpha:
            a, bb = alpha_of(params, b)
            alphas.append((float(a), float(bb)))
    return np.asarray(losses), alphas


def run(steps: int = 60, verbose: bool = True):
    t0 = time.time()
    curves = {}
    alphas = None
    for impl in ("softmax", "lln", "lln_diag"):
        cfg = get_config("roberta-lln", smoke=True, attn_impl=impl)
        curves[impl], a = _train_curve(cfg, steps,
                                       track_alpha=(impl == "lln_diag"))
        if impl == "lln_diag":
            alphas = a
        if verbose:
            c = curves[impl]
            print(f"  {impl:9s} loss: {c[0]:.3f} -> {np.mean(c[-5:]):.3f}")
    dt_us = (time.time() - t0) * 1e6 / (3 * steps)
    gap_diag = float(np.abs(np.mean(curves['lln_diag'][-10:])
                            - np.mean(curves['softmax'][-10:])))
    gap_lln = float(np.abs(np.mean(curves['lln'][-10:])
                           - np.mean(curves['softmax'][-10:])))
    mean_alpha = float(np.mean([a for a, _ in alphas])) if alphas else -1
    if verbose and alphas:
        print(f"  fig9 alpha trajectory: start {alphas[0][0]:.2f} "
              f"end {alphas[-1][0]:.2f}")
    return [("fig8a_final_gap_lln_diag_vs_sa", dt_us, gap_diag),
            ("fig8a_final_gap_lln_vs_sa", dt_us, gap_lln),
            ("fig8a_sa_learned_delta", dt_us,
             float(curves['softmax'][0] - np.mean(curves['softmax'][-5:]))),
            ("fig9_mean_alpha", dt_us, mean_alpha)]


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived:.4f}")
