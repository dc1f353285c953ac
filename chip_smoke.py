"""Smoke run of the main serving and training paths on one TPU chip.

    python chip_smoke.py                # one chip: `serve`, `train`, `ssd`
    python chip_smoke.py --four-chips   # four chips: phase `four_chips` only

Everything runs in this one process, through the entry points a user calls,
at the full width of the models the repo ships, with random weights made
from fixed seeds:

* ``serve``: stablelm-1.6b with ``lln_diag`` attention through the pool
  (``make_pool_setup`` -> ``ContinuousBatcher.warmup`` -> ``run``), 8
  requests over 4 slots at prompt lengths 256/512 and generation budgets
  8/24.  Checks: every request ends ``done`` with its budget of tokens; the
  compiled prefill and segment programs hold the Pallas kernels
  (``tpu_custom_call``); the prefill logits of one prompt agree with the
  jnp reference path (``attn_backend="ref"``) on the same chip.
* ``train``: roberta-lln (the paper's bidirectional ``lln_diag`` encoder)
  through ``make_train_setup``: 5 MLM steps at batch 8, sequence 512.
  Checks: every loss is finite; the step program holds the Pallas kernels;
  the first loss and every gradient leaf of the first batch agree with the
  jnp reference path, which tests the backward kernels as well.
* ``ssd``: the Mamba2 SSD kernel at mamba2-130m's full width (24 heads of
  64, state 128, chunk 256) against the jnp chunked scan, forward and
  gradients, then 3 mamba2-130m training steps through ``make_train_setup``:
  every loss finite and the step program holds the kernel.
* ``four_chips``: yi-9b sharded over a ``data=1, model=4`` mesh with the
  ``tp_heads`` serving rules.  yi-9b cut to 4 layers (fp32 compute and
  matmuls) serves the same requests on one chip and sharded over four:
  greedy tokens must be equal and prefill logits agree; then the full 48-layer
  bf16 yi-9b serves 4 requests, all ``done``.

Each phase prints one JSON line of facts (compile and run seconds, request
statuses, losses, ``peak_bytes_in_use``, kernel presence); none of them is
a speed claim.  A failed check raises, so the script exits non-zero.  The
last line of a passing run is ``{"ok": true, "device": {...}}``.  With no
TPU the script exits non-zero before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

#: Prefill logits agree with the jnp reference within this share of the
#: reference's largest |logit| (see CHANGES.md for the reasoning).
LOGITS_RTOL = 5e-2
#: First training loss agrees with the jnp reference within this much.
LOSS_ATOL = 2e-2
#: Each gradient leaf of the first batch agrees with the jnp reference's
#: within this share of the reference leaf's norm.
GRAD_RTOL = 1e-1
#: SSD kernel output and input gradients agree with the jnp chunked scan
#: within this share of the reference's largest magnitude.
SSD_RTOL = 2e-2
#: Sharded (model=4) prefill logits agree with one chip's within this share
#: of max |logit|, both computed in fp32 at "highest" matmul precision.
CUT_LOGITS_RTOL = 1e-3


def _fail(msg: str):
    raise RuntimeError(msg)


def _peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def _compile(fn, *args):
    """AOT-compile ``fn`` for ``args``: (executable, seconds, has kernel)."""
    t0 = time.perf_counter()
    exe = fn.lower(*args).compile()
    return exe, time.perf_counter() - t0, "tpu_custom_call" in exe.as_text()


def _grads(cfg, mesh, params, batch):
    """(loss, grads) of ``cfg``'s model under ``mesh``'s logical rules."""
    import jax

    from repro.distributed.sharding import logical_rules, make_rules
    from repro.models import build_model

    model = build_model(cfg)
    rules = make_rules(cfg, multi_pod=False)

    def fn(p, b):
        with logical_rules(mesh, rules):
            return jax.value_and_grad(model.loss)(p, b)
    with mesh:
        return jax.jit(fn)(params, batch)


def _worst_leaf(got, want):
    """(path, error) of the leaf whose ||got - want|| / ||want|| is largest."""
    import jax
    import numpy as np

    worst = ("", 0.0)
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = np.asarray(_leaf(want, path), np.float64)
        norm = np.linalg.norm(w)
        if norm == 0.0:
            continue
        err = float(np.linalg.norm(np.asarray(g, np.float64) - w) / norm)
        if err > worst[1]:
            worst = (jax.tree_util.keystr(path), err)
    return worst


def _leaf(tree, path):
    for k in path:
        tree = tree[k.key]
    return tree


def _rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _pool_run(cfg, mesh, *, slots, prompt_lens, gen_lens, requests, seed=0,
              segment=8):
    """Serve ``requests`` synthetic requests through the pool on ``mesh``;
    returns (facts, stats, params, setup)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.batcher import ContinuousBatcher, synthetic_traffic
    from repro.launch.steps import init_params, make_pool_setup

    max_len = max(prompt_lens) + max(gen_lens)
    with mesh:
        setup = make_pool_setup(cfg, mesh, slots=slots, max_len=max_len,
                                segment=segment)
        params = init_params(setup.model, mesh, seed)
        tokens = jnp.zeros((1, prompt_lens[0]), jnp.int32)
        _, pf_s, pf_kernel = _compile(setup.prefill_fn(prompt_lens[0], 1),
                                      params, tokens)
        z = jnp.zeros((slots,), jnp.int32)
        _, seg_s, seg_kernel = _compile(
            setup.segment_fn, params, setup.cache_init(), z, z, z,
            jnp.zeros((slots,), jnp.bool_), jax.random.PRNGKey(seed))
        eng = ContinuousBatcher(setup, params)
        t0 = time.perf_counter()
        eng.warmup(prompt_lens)
        warm_s = time.perf_counter() - t0
        reqs = synthetic_traffic(requests, cfg.vocab, prompt_lens, gen_lens,
                                 seed=seed)
        stats = eng.run(reqs, key=jax.random.PRNGKey(seed + 1))
    statuses = {}
    for v in stats.statuses.values():
        statuses[v] = statuses.get(v, 0) + 1
    if statuses != {"done": requests}:
        _fail(f"{cfg.name}: request statuses {statuses}, want all done")
    short = [r.rid for r in reqs if len(stats.outputs[r.rid]) != r.gen_len]
    if short:
        _fail(f"{cfg.name}: requests {short} emitted the wrong token count")
    if not (pf_kernel and seg_kernel):
        _fail(f"{cfg.name}: Pallas kernel missing (prefill {pf_kernel}, "
              f"segment {seg_kernel})")
    facts = {"model": cfg.name, "attn_impl": cfg.attn_impl,
             "layers": cfg.n_layers, "d_model": cfg.d_model,
             "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
             "requests": requests, "slots": slots,
             "prompt_lens": list(prompt_lens), "gen_lens": list(gen_lens),
             "statuses": statuses, "completed_tokens": stats.completed_tokens,
             "prefill_compile_s": pf_s, "segment_compile_s": seg_s,
             "warmup_s": warm_s, "run_s": stats.wall_s,
             "prefill_has_kernel": pf_kernel,
             "segment_has_kernel": seg_kernel}
    return facts, stats, params, setup


def _prefill_logits(setup, params, prompt):
    import jax.numpy as jnp
    with setup.mesh:
        logits, _ = setup.prefill_fn(prompt.shape[0], 1)(
            params, jnp.asarray(prompt[None]))
    return logits


def serve_phase(cfg, *, slots=4, prompt_lens=(256, 512), gen_lens=(8, 24),
                requests=8):
    """Pool serving at full width + Pallas-vs-reference prefill logits."""
    import numpy as np

    from repro.launch.batcher import synthetic_traffic
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_pool_setup

    mesh = make_mesh((1, 1), ("data", "model"))
    facts, _, params, setup = _pool_run(
        cfg, mesh, slots=slots, prompt_lens=prompt_lens, gen_lens=gen_lens,
        requests=requests)
    prompt = synthetic_traffic(1, cfg.vocab, prompt_lens, gen_lens)[0].prompt
    ref_setup = make_pool_setup(cfg.replace(attn_backend="ref"), mesh,
                                slots=slots, max_len=setup.max_len)
    got = _prefill_logits(setup, params, prompt)
    want = _prefill_logits(ref_setup, params, prompt)
    err = _rel_err(got, want)
    same_argmax = bool(np.argmax(np.asarray(got)) == np.argmax(np.asarray(want)))
    _emit("serve", **facts, logits_rel_err_vs_ref=err,
          logits_rtol=LOGITS_RTOL, argmax_equal_vs_ref=same_argmax,
          peak_bytes_in_use=_peak_bytes())
    if not err <= LOGITS_RTOL:
        _fail(f"serve: prefill logits differ from the jnp reference by "
              f"{err} > {LOGITS_RTOL} of max |logit|")


def train_phase(cfg, *, batch=8, seq=512, steps=5):
    """MLM training steps at full width + first loss vs the jnp path."""
    import jax

    from repro.configs.base import ShapeSpec
    from repro.data.synthetic import mlm_batches
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_train_setup

    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh:
        setup = make_train_setup(cfg, ShapeSpec("smoke", seq, batch, "train"),
                                 mesh, multi_pod=False, total_steps=steps)
        state = setup.init_fn(jax.random.PRNGKey(0))
        data = mlm_batches(cfg.vocab, batch, seq, seed=0)
        batches = [jax.device_put(next(data)) for _ in range(steps)]
    _, grads = _grads(cfg, mesh, state["params"], batches[0])
    ref_loss, ref_grads = _grads(cfg.replace(attn_backend="ref"), mesh,
                                 state["params"], batches[0])
    ref_loss = float(ref_loss)
    grad_leaf, grad_err = _worst_leaf(grads, ref_grads)
    del grads, ref_grads
    with mesh:
        step, compile_s, has_kernel = _compile(setup.step_fn, state,
                                               batches[0])
        losses = []
        t0 = time.perf_counter()
        for b in batches:
            state, metrics = step(state, b)
            losses.append(float(metrics["loss"]))
        run_s = time.perf_counter() - t0
    _emit("train", model=cfg.name, attn_impl=cfg.attn_impl,
          layers=cfg.n_layers, d_model=cfg.d_model, batch=batch, seq=seq,
          losses=losses, ref_first_loss=ref_loss, loss_atol=LOSS_ATOL,
          grad_worst_leaf=grad_leaf, grad_rel_err_vs_ref=grad_err,
          grad_rtol=GRAD_RTOL, step_compile_s=compile_s, run_s=run_s,
          step_has_kernel=has_kernel, peak_bytes_in_use=_peak_bytes())
    if not all(math.isfinite(x) for x in losses):
        _fail(f"train: non-finite loss in {losses}")
    if not has_kernel:
        _fail("train: the step program holds no Pallas kernel")
    if not abs(losses[0] - ref_loss) <= LOSS_ATOL:
        _fail(f"train: first loss {losses[0]} vs jnp reference {ref_loss}")
    if not grad_err <= GRAD_RTOL:
        _fail(f"train: gradient {grad_leaf} differs from the jnp reference "
              f"by {grad_err} > {GRAD_RTOL} of its norm")


def ssd_phase(cfg, *, batch=8, seq=512, steps=3):
    """SSD kernel vs the jnp chunked scan at full width + training steps."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeSpec
    from repro.kernels import ssd_scan
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_train_setup
    from repro.models import synthetic_batch
    from repro.models.ssm import ssd_chunked

    h = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    g, s, chunk = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_chunk
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    shape = (batch, seq, h, cfg.ssm_head_dim)
    xbar = jax.random.normal(ks[0], shape, jnp.float32)
    b_in = jax.random.normal(ks[1], (batch, seq, g, s), cfg.cdtype)
    c_in = jax.random.normal(ks[2], (batch, seq, g, s), cfg.cdtype)
    # The model's decay law: log a = softplus(dt) * -exp(a_log), with
    # a_log spanning log 1 .. log 16 over the heads.
    a = -jnp.exp(jnp.log(jnp.linspace(1.0, 16.0, h)))
    log_a = jax.nn.softplus(jax.random.normal(ks[3], shape[:3])) * a
    cot = jax.random.normal(ks[4], shape, jnp.float32)

    def ref(x, b, c, la):
        rep = h // g
        y, _ = ssd_chunked(x, jnp.repeat(b, rep, 2), jnp.repeat(c, rep, 2),
                           la, chunk=chunk)
        return y

    def kernel(x, b, c, la):
        return ssd_scan(x, b, c, la, chunk)

    def vjp(fn):
        def run(ct, *args):
            y, back = jax.vjp(fn, *args)
            return y, back(ct)
        return jax.jit(run)

    # fp32 matmuls at "highest" so the jnp scan is an fp32 reference and
    # does not round its operands to bf16 as TPU matmuls do by default.
    args = (cot, xbar, b_in, c_in, log_a)
    with jax.default_matmul_precision("highest"):
        fwd, fwd_s, fwd_kernel = _compile(vjp(kernel), *args)
        y_k, g_k = fwd(*args)
        y_r, g_r = vjp(ref)(*args)
    y_err = _rel_err(y_k, y_r)
    g_err = max(_rel_err(a, b) for a, b in zip(g_k, g_r))
    del y_k, g_k, y_r, g_r

    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh:
        setup = make_train_setup(cfg, ShapeSpec("smoke", seq, batch, "train"),
                                 mesh, multi_pod=False, total_steps=steps)
        state = setup.init_fn(jax.random.PRNGKey(0))
        data = [synthetic_batch(cfg, batch, seq, jax.random.PRNGKey(i))
                for i in range(steps)]
        step, compile_s, has_kernel = _compile(setup.step_fn, state, data[0])
        losses = []
        for b in data:
            state, metrics = step(state, b)
            losses.append(float(metrics["loss"]))
    _emit("ssd", model=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
          heads=h, head_dim=cfg.ssm_head_dim, state=s, chunk=chunk,
          batch=batch, seq=seq, kernel_rel_err_vs_ref=y_err,
          grad_rel_err_vs_ref=g_err, ssd_rtol=SSD_RTOL,
          kernel_compile_s=fwd_s, kernel_has_kernel=fwd_kernel,
          losses=losses, step_compile_s=compile_s, step_has_kernel=has_kernel,
          peak_bytes_in_use=_peak_bytes())
    if not (fwd_kernel and has_kernel):
        _fail(f"ssd: Pallas kernel missing (kernel {fwd_kernel}, step "
              f"{has_kernel})")
    if not (y_err <= SSD_RTOL and g_err <= SSD_RTOL):
        _fail(f"ssd: kernel differs from the jnp scan by {y_err} (output), "
              f"{g_err} (gradients) > {SSD_RTOL} of max magnitude")
    if not all(math.isfinite(x) for x in losses):
        _fail(f"ssd: non-finite loss in {losses}")


def four_chips_phase(cfg, *, cut_layers=4, slots=4, prompt_lens=(256, 512),
                     gen_lens=(8, 16), requests=4):
    """tp_heads serving over model=4 against one chip, then full width."""
    import jax
    import numpy as np

    from repro.launch.batcher import synthetic_traffic
    from repro.launch.mesh import make_mesh

    one = make_mesh((1, 1), ("data", "model"))
    four = make_mesh((1, 4), ("data", "model"))
    # The comparison computes in fp32 with fp32 matmuls: at the TPU's
    # default precision each fp32 matmul rounds its operands to bf16, and
    # the one-ulp flips that a different reduction order causes (3.4e-3 of
    # max |logit| over 4 layers) are enough to swap near-tied greedy tokens
    # of a random-weight model.  At "highest" the two runs differ by the
    # order of fp32 sums alone, so equal tokens test the sharding.
    cut = cfg.replace(n_layers=cut_layers, compute_dtype="float32")
    kw = dict(slots=slots, prompt_lens=prompt_lens, gen_lens=gen_lens,
              requests=requests)
    prompt = synthetic_traffic(1, cfg.vocab, prompt_lens, gen_lens)[0].prompt
    with jax.default_matmul_precision("highest"):
        f1, s1, p1, setup1 = _pool_run(cut, one, **kw)
        want = _prefill_logits(setup1, p1, prompt)
        del p1, setup1
        f4, s4, p4, setup4 = _pool_run(cut, four, **kw)
        got = _prefill_logits(setup4, p4, prompt)
        del p4, setup4
    err = _rel_err(got, want)
    # First position where each request's sharded tokens leave one chip's.
    diverge = {r: int(np.argmax(np.asarray(s1.outputs[r])
                                != np.asarray(s4.outputs[r])))
               for r in s1.outputs
               if not np.array_equal(s1.outputs[r], s4.outputs[r])}
    full, _, _, _ = _pool_run(cfg, four, **kw)
    _emit("four_chips", cut_one_chip=f1, cut_four_chips=f4,
          cut_tokens_equal=not diverge, cut_first_divergence=diverge,
          cut_logits_rel_err=err, cut_logits_rtol=CUT_LOGITS_RTOL, full=full,
          peak_bytes_in_use=_peak_bytes())
    if diverge:
        _fail(f"four_chips: sharded greedy tokens differ from one chip at "
              f"{diverge} (request: first position)")
    if not err <= CUT_LOGITS_RTOL:
        _fail(f"four_chips: sharded prefill logits differ by {err} > "
              f"{CUT_LOGITS_RTOL} of max |logit|")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the yi-9b model=4 sharded phase")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this script runs only on the chip",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: {need} chips needed, {len(devices)} found",
              file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.four_chips:
        four_chips_phase(get_config("yi-9b", attn_impl="lln_diag"))
    else:
        serve_phase(get_config("stablelm-1.6b", attn_impl="lln_diag"))
        train_phase(get_config("roberta-lln"))
        ssd_phase(get_config("mamba2-130m"))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
