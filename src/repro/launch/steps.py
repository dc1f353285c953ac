"""pjit step builders: train_step / prefill_step / decode_step per arch.

Everything AOT-friendly: the builders return (step_fn, in_struct, shardings)
so launchers and the dry-run lower against ShapeDtypeStructs without
allocating anything.
"""
from __future__ import annotations

import dataclasses
import re
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core import speculative
from repro.core.health import HealthConfig, unhealthy_rows
from repro.core.metrics import streaming_concentration_tree
from repro.distributed import sharding as shd
from repro.models import (Model, build_model, draft_config, draft_params)
from repro.optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation).
# ---------------------------------------------------------------------------

def batch_struct(cfg: ArchConfig, shape: ShapeSpec, mesh, rules) -> dict:
    """Training/prefill batch ShapeDtypeStructs with shardings attached."""
    b, n = shape.global_batch, shape.seq_len
    batch_axes = rules["act_batch"]
    seq_axes = rules["act_seq"]

    def flt(shape_, spec):
        spec = shd.fit_spec(P(*spec), shape_, mesh)
        return jax.ShapeDtypeStruct(shape_, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))

    n_text = n
    if cfg.family == "vlm":
        n_text = max(n - cfg.num_prefix_tokens, 8)
    out = {}
    for name in ("inputs", "targets"):
        spec = shd.fit_spec(P(batch_axes, seq_axes), (b, n_text), mesh)
        out[name] = jax.ShapeDtypeStruct(
            (b, n_text), jnp.int32, sharding=NamedSharding(mesh, spec))
    spec = shd.fit_spec(P(batch_axes, seq_axes), (b, n_text), mesh)
    out["mask"] = jax.ShapeDtypeStruct(
        (b, n_text), jnp.float32, sharding=NamedSharding(mesh, spec))
    if cfg.family == "encdec":
        out["src"] = flt((b, n, cfg.frontend_dim), (batch_axes, seq_axes, None))
    if cfg.family == "vlm":
        out["patches"] = flt((b, cfg.num_prefix_tokens, cfg.frontend_dim),
                             (batch_axes, None, None))
    return out


def cache_shardings(cache_tree, cfg, mesh, rules):
    """Decode-cache shardings.

    The dominant bytes at decode are the caches, so they MUST use the model
    axis.  Heads shard over 'model' when divisible; otherwise we shard the
    *feature* dim (head_dim, or the MLA latent) — attention contractions
    over that dim become psum partials, which XLA handles (flash-decode
    along the feature axis).  SSM conv tails and scalars replicate.
    """
    msize = shd._axis_size(mesh, "model")
    kv_div = cfg.n_kv_heads % msize == 0
    h_div = cfg.n_heads % msize == 0
    kv_ax = "model" if kv_div else None
    kv_fd = None if kv_div else "model"
    h_ax = "model" if h_div else None
    h_fd = None if h_div else "model"
    b_ax = rules["act_batch"]

    per_name = [
        (r"(^|/)(len|pos|alpha|beta|log_scale)$", ()),
        # LLN tails carry G kv-heads on the kernelized serve path (H on the
        # seed path / MLA); fit_spec drops non-divisible axes either way.
        (r"(^|/)(tail_k|tail_v)$", (b_ax, None, kv_ax, kv_fd)),
        # MLA latent cache: shard the latent dim
        (r"(^|/)ckv$", (b_ax, None, "model")),
        (r"(^|/)kr$", (b_ax, None, None)),
        (r"(^|/)c_k$", (b_ax, None, h_ax, None)),
        # log_linear Fenwick pyramid: (B, L, H, D[, Dv]) — scale axis
        # replicates (L = lln_num_scales is tiny), heads/feature as LLN
        (r"(^|/)sl$", (b_ax, None, h_ax, h_fd, None)),
        (r"(^|/)zl$", (b_ax, None, h_ax, h_fd)),
        (r"(^|/)cl$", (b_ax, None, h_ax)),
        # softmax KV caches (kv heads) / cross-attn caches
        (r"(^|/)(ck|cv|k|v)$", (b_ax, None, kv_ax, kv_fd)),
        # LLN state: heads when divisible, else the feature dim
        (r"(^|/)s$", (b_ax, h_ax, h_fd, None)),
        (r"(^|/)z$", (b_ax, h_ax, h_fd)),
        # SSM state: heads when divisible (zamba 112 ok, mamba 24 not)
        (r"(^|/)state$", (b_ax, h_ax, None, None)),
        (r"(^|/)conv$", (b_ax, None, None)),
    ]

    def leaf(kp, a):
        path = shd._path_str(kp)
        axes: tuple = (None,) * a.ndim
        for pat, ax in per_name:
            if re.search(pat, path):
                lead = a.ndim - len(ax)
                axes = (None,) * lead + tuple(ax)
                break
        spec = shd.fit_spec(P(*axes), a.shape, mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(leaf, cache_tree)


# ---------------------------------------------------------------------------
# Step builders.
# ---------------------------------------------------------------------------

def init_params(model: Model, mesh, seed: int):
    """Build a model's parameters where their shardings
    (``distributed/sharding.py:param_shardings``) place them: one jitted
    init with those ``out_shardings``, so no device ever holds the whole
    tree unless the mesh says so."""
    key = jax.random.PRNGKey(seed)
    shardings = shd.param_shardings(jax.eval_shape(model.init, key), mesh)
    return jax.jit(model.init, out_shardings=shardings)(key)


@dataclasses.dataclass
class TrainSetup:
    """``init_fn(key) -> state`` builds ``{"params", "opt"}`` already laid
    out by ``state_shardings``."""
    step_fn: Any
    init_fn: Any
    state_struct: Any
    state_shardings: Any
    batch: dict
    rules: dict


def make_train_setup(cfg: ArchConfig, shape: ShapeSpec, mesh, *,
                     multi_pod: bool, peak_lr: float = 3e-4,
                     total_steps: int = 10000,
                     cast_params_once: bool | None = None,
                     opt_cfg: AdamWConfig = AdamWConfig()) -> TrainSetup:
    """``cast_params_once``: cast fp32 master params to compute dtype *before*
    the loss — FSDP weight all-gathers then move bf16 instead of fp32 (2x
    collective-bytes reduction on every weight gather; gradients arrive in
    bf16 and are accumulated into the fp32 AdamW moments as usual)."""
    model = build_model(cfg)
    rules = shd.make_rules(cfg, multi_pod=multi_pod)
    if cast_params_once is None:
        cast_params_once = cfg.cast_params_once

    def init_state(key):
        params = model.init(key)
        return {"params": params, "opt": adamw_init(params)}

    state_struct = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    state_shardings = shd.param_shardings(state_struct, mesh)
    batch = batch_struct(cfg, shape, mesh, rules)

    accum = max(int(cfg.grad_accum), 1)

    def compute_grads(params, batch):
        if accum == 1:
            return jax.value_and_grad(model.loss)(params, batch)
        # Microbatched gradient accumulation (activation peak / accum).
        mb = jax.tree_util.tree_map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
            batch)

        def body(carry, mbatch):
            loss_sum, gacc = carry
            loss, grads = jax.value_and_grad(model.loss)(params, mbatch)
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            return (loss_sum + loss, gacc), None

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, gacc), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), g0), mb)
        grads = jax.tree_util.tree_map(lambda g: g / accum, gacc)
        return loss_sum / accum, grads

    def train_step(state, batch):
        with shd.logical_rules(mesh, rules):
            params_c = state["params"]
            if cast_params_once:
                params_c = jax.tree_util.tree_map(
                    lambda p: p.astype(cfg.cdtype)
                    if p.dtype == jnp.float32 and p.ndim >= 2 else p,
                    params_c)
            loss, grads = compute_grads(params_c, batch)
            with jax.named_scope("optimizer"):
                lr = warmup_cosine(state["opt"]["step"], peak_lr=peak_lr,
                                   warmup_steps=min(500, total_steps // 10),
                                   total_steps=total_steps)
                params, opt, metrics = adamw_update(
                    grads, state["opt"], state["params"], lr, opt_cfg)
        return ({"params": params, "opt": opt},
                {"loss": loss, "lr": lr, **metrics})

    step_fn = jax.jit(train_step,
                      in_shardings=(state_shardings, None),
                      out_shardings=(state_shardings, None),
                      donate_argnums=(0,))
    init_fn = jax.jit(init_state, out_shardings=state_shardings)
    return TrainSetup(step_fn=step_fn, init_fn=init_fn,
                      state_struct=state_struct,
                      state_shardings=state_shardings, batch=batch,
                      rules=rules)


def sample_token(logits, temperature: float, key) -> jnp.ndarray:
    """Greedy (temperature == 0) or temperature sampling; jit-safe.  The one
    sampling rule shared by the scanned generation loop and the per-token
    serve driver."""
    if temperature > 0:
        return jax.random.categorical(key, logits / temperature,
                                      -1).astype(jnp.int32)
    return jnp.argmax(logits, -1).astype(jnp.int32)


@dataclasses.dataclass
class ServeSetup:
    """Jitted serving entry points for one (cfg, mesh, batch-shape).

    ``prefill_fn(params, batch) -> (last logits, caches)`` — batched prompt
    forward building the decode caches (state-emitting LLN kernel path by
    default).  ``decode_fn(params, caches, token, pos) -> (logits, caches)``
    — one decode step, donated caches.  ``make_generate(steps, temperature)``
    builds a jitted scanned generation segment
    ``(params, caches, tok, pos0, key) -> (tokens (B, steps), caches)``:
    the whole segment is ONE dispatch — a ``lax.scan`` over the decode step
    with donated cache carry (vs one jitted dispatch per token from a
    Python loop).  ``tok`` is the (B,) int32 token decoded first; ``pos0``
    its scalar absolute position; greedy when ``temperature == 0`` (the
    PRNG key is then unused).  All rows advance in lockstep — for
    mixed-length traffic see ``make_pool_setup``.
    """
    prefill_fn: Any
    decode_fn: Any
    params_struct: Any
    params_shardings: Any
    batch: dict
    cache_struct: Any
    cache_shardings: Any
    rules: dict
    token_struct: Any = None
    pos_struct: Any = None
    make_generate: Any = None


def make_serve_setup(cfg: ArchConfig, shape: ShapeSpec, mesh, *,
                     multi_pod: bool) -> ServeSetup:
    model = build_model(cfg)
    rules = shd.make_rules(cfg, multi_pod=multi_pod, serve=True)
    b, n = shape.global_batch, shape.seq_len

    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_shardings = shd.param_shardings(params_struct, mesh)
    batch = batch_struct(cfg, shape, mesh, rules)

    def prefill_step(params, batch):
        with shd.logical_rules(mesh, rules):
            return model.prefill(params, batch, n)

    cache_struct = jax.eval_shape(
        lambda p: model.cache_init(p, b, n), params_struct)
    cache_shard = cache_shardings(cache_struct, cfg, mesh, rules)

    def decode_step(params, caches, token, pos):
        with shd.logical_rules(mesh, rules):
            return model.decode(params, caches, token, pos)

    batch_axes = rules["act_batch"]
    tok_spec = shd.fit_spec(P(batch_axes), (b,), mesh)
    token_struct = jax.ShapeDtypeStruct((b,), jnp.int32,
                                        sharding=NamedSharding(mesh, tok_spec))
    pos_struct = jax.ShapeDtypeStruct((), jnp.int32)

    prefill_fn = jax.jit(prefill_step, in_shardings=(params_shardings, None))
    # Token in_sharding is left open: a (B,) int token is tiny and arrives
    # committed-replicated from the previous step's argmax; pinning it to the
    # data axis would make older jax reject the arg instead of resharding.
    decode_fn = jax.jit(decode_step,
                        in_shardings=(params_shardings, cache_shard,
                                      None, None),
                        out_shardings=(None, cache_shard),
                        donate_argnums=(1,))

    def make_generate(steps: int, temperature: float = 0.0):
        """Build a jitted scanned generation segment: ``steps`` greedy (or
        temperature-sampled) decode steps folded into one ``lax.scan`` with
        the cache carry donated — one XLA dispatch per segment."""

        def gen(params, caches, tok, pos0, key):
            def body(carry, i):
                caches, tok = carry
                logits, caches = model.decode(params, caches, tok, pos0 + i)
                tok = sample_token(logits, temperature,
                                   jax.random.fold_in(key, i))
                return (caches, tok), tok

            with shd.logical_rules(mesh, rules):
                (caches, _), toks = jax.lax.scan(
                    body, (caches, tok), jnp.arange(steps, dtype=jnp.int32))
            return toks.transpose(1, 0), caches

        return jax.jit(gen,
                       in_shardings=(params_shardings, cache_shard,
                                     None, None, None),
                       out_shardings=(None, cache_shard),
                       donate_argnums=(1,))

    setup = ServeSetup(prefill_fn=prefill_fn, decode_fn=decode_fn,
                       params_struct=params_struct,
                       params_shardings=params_shardings, batch=batch,
                       cache_struct=cache_struct, cache_shardings=cache_shard,
                       rules=rules, make_generate=make_generate)
    setup.token_struct = token_struct
    setup.pos_struct = pos_struct
    return setup


# ---------------------------------------------------------------------------
# Speculative decoding: draft-then-verify over the partial-commit contract.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpecSetup:
    """Jitted speculative-decode entry points for one (cfg, mesh, shape).

    The loop is draft-then-verify (Leviathan et al. / Chen et al.) over
    the engine's partial-commit contract: each iteration the tied
    first-``draft_layers`` draft proposes ``spec_k`` tokens sequentially,
    the target scores the whole chunk ``[tok, d_1..d_k]`` in ONE
    ``commit_len=0`` pass (state untouched), the acceptance rule
    (``core/speculative.py``) turns the logits into a per-row
    ``commit_len``, and one verify-commit pass per model folds exactly the
    accepted prefix into the LLN ``(s, z, c_k)`` / diag tails / KV rows —
    a rejected draft never enters the running sums, so nothing is ever
    popped.  Rows of one batch accept different counts: positions, emit
    counts and commits are per-row throughout.

    * ``prefill_fn(params, batch) -> (last logits, tgt_caches,
      draft_caches)`` — both models prefill the prompt (the draft is a
      zero-copy first-k slice of the target's stacked layer params).
    * ``make_generate(steps, temperature=0.0, iters=None)`` — ONE jitted
      ``lax.scan`` whose carry holds BOTH decode states; each scan step is
      one draft+verify iteration emitting 1..k+1 tokens per row.  Returns
      ``(toks (B, iters, k+1), n_emit (B, iters), n_accept (B, iters),
      live (B, iters), tgt_caches, draft_caches)``; rows stop emitting
      once they reach ``steps`` tokens (``commit_len`` drops to 0 — the
      masked-row machinery).  ``iters`` defaults to ``steps`` (the worst
      case: every verify emits exactly one token).
      :func:`flatten_spec_tokens` flattens the per-iteration buffers into
      (B, steps) sequences on the host.

    Greedy (``temperature == 0``) speculative decode is token-for-token
    the plain greedy scanned loop (``tests/test_speculative.py``); the
    win is sequential target dispatches per token, reported by
    ``benchmarks/bench_spec.py``.
    """
    cfg: Any
    draft_cfg: Any
    model: Any
    draft_model: Any
    mesh: Any
    rules: dict
    spec_k: int
    draft_layers: int
    max_len: int
    prefill_fn: Any
    make_generate: Any = None


def make_spec_setup(cfg: ArchConfig, shape: ShapeSpec, mesh, *,
                    spec_k: int, draft_layers: int,
                    multi_pod: bool = False) -> SpecSetup:
    """Build the speculative-decode loop for a dense/MoE decoder.

    ``shape.seq_len`` is the cache budget: it must cover the prompt plus
    the generation budget plus one verify chunk of overshoot
    (``prompt + steps + spec_k + 1``).
    """
    if spec_k < 1:
        raise ValueError(f"spec_k must be >= 1, got {spec_k}")
    dcfg = draft_config(cfg, draft_layers)   # validates k and the family
    model = build_model(cfg)
    dmodel = build_model(dcfg)
    rules = shd.make_rules(cfg, multi_pod=multi_pod, serve=True)
    max_len = shape.seq_len
    k = spec_k

    def _prefill(params, batch):
        with shd.logical_rules(mesh, rules):
            logits, tgt = model.prefill(params, batch, max_len)
            _, dr = dmodel.prefill(draft_params(params, cfg, draft_layers),
                                   batch, max_len)
        return logits, tgt, dr

    prefill_fn = jax.jit(_prefill)

    def make_generate(steps: int, temperature: float = 0.0,
                      iters: Optional[int] = None):
        n_iters = steps if iters is None else iters

        def gen(params, tgt_caches, dr_caches, tok, pos0, key):
            b = tok.shape[0]
            dparams = draft_params(params, cfg, draft_layers)
            pos0 = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (b,))

            def body(carry, i):
                tgt_caches, dr_caches, tok, pos, count = carry
                it_key = jax.random.fold_in(key, i)

                # Draft k tokens sequentially; the scratch state the
                # drafting accumulates is DISCARDED — the committed draft
                # state is refolded below through the same partial-commit
                # contract as the target.
                def dstep(dc, j):
                    dcache, cur = dc
                    lg, dcache = dmodel.decode(dparams, dcache, cur,
                                               pos + j)
                    nxt = sample_token(lg, temperature,
                                       jax.random.fold_in(it_key, j))
                    return (dcache, nxt), (nxt, lg)

                _, (drafts, dlogits) = jax.lax.scan(
                    dstep, (dr_caches, tok),
                    jnp.arange(k, dtype=jnp.int32))
                drafts = drafts.T                          # (B, k)
                dlogits = dlogits.transpose(1, 0, 2)       # (B, k, V)

                # Verify: ONE commit_len=0 target pass scores ALL k+1
                # positions (caches bitwise untouched) and returns the
                # per-layer (k, v) commit residuals.
                chunk = jnp.concatenate([tok[:, None], drafts], axis=1)
                tlogits, t_resid = model.score(params, tgt_caches, chunk,
                                               pos)
                n_acc, nxt, commit = speculative.verify_tokens(
                    drafts, tlogits, temperature,
                    key=jax.random.fold_in(it_key, k + 1),
                    draft_logits=dlogits)
                live = count < steps
                commit = jnp.where(live, commit, 0)

                # Single-pass verify: the accepted prefix folds from the
                # score residuals with the O(T d^2) per-layer einsum — no
                # second full target pass.  The draft (a first-k slice)
                # still commits via its own chunked decode.
                tgt_caches = model.commit(tgt_caches, t_resid, commit)
                _, dr_caches = dmodel.decode(dparams, dr_caches, chunk,
                                             pos, commit_len=commit)

                n_emit = jnp.where(live, n_acc + 1, 0)
                toks_out = speculative.emit_tokens(drafts, n_acc, nxt)
                tok = jnp.where(live, nxt, tok)
                pos = pos + commit
                count = count + n_emit
                return ((tgt_caches, dr_caches, tok, pos, count),
                        (toks_out, n_emit, jnp.where(live, n_acc, 0),
                         live))

            init = (tgt_caches, dr_caches, tok, pos0,
                    jnp.zeros((b,), jnp.int32))
            with shd.logical_rules(mesh, rules):
                (tgt_caches, dr_caches, *_), ys = jax.lax.scan(
                    body, init, jnp.arange(n_iters, dtype=jnp.int32))
            toks, n_emit, n_acc, live = ys
            return (toks.transpose(1, 0, 2), n_emit.T, n_acc.T, live.T,
                    tgt_caches, dr_caches)

        return jax.jit(gen, donate_argnums=(1, 2))

    return SpecSetup(cfg=cfg, draft_cfg=dcfg, model=model,
                     draft_model=dmodel, mesh=mesh, rules=rules,
                     spec_k=spec_k, draft_layers=draft_layers or
                     cfg.draft_layers, max_len=max_len,
                     prefill_fn=prefill_fn, make_generate=make_generate)


def flatten_spec_tokens(toks, n_emit, steps: int) -> np.ndarray:
    """Host-side flatten of one speculative run: per-iteration emit
    buffers ``toks (B, iters, k+1)`` + counts ``n_emit (B, iters)`` ->
    (B, steps) token sequences (each row concatenates its emitted
    prefixes; overshoot past ``steps`` is dropped)."""
    toks = np.asarray(toks)
    n_emit = np.asarray(n_emit)
    b = toks.shape[0]
    out = np.zeros((b, steps), np.int32)
    for r in range(b):
        seq: list[int] = []
        for it in range(toks.shape[1]):
            n = int(n_emit[r, it])
            seq.extend(int(x) for x in toks[r, it, :n])
            if len(seq) >= steps:
                break
        if len(seq) < steps:
            raise ValueError(f"row {r} emitted {len(seq)} < {steps} tokens"
                             " — increase iters")
        out[r] = np.asarray(seq[:steps], np.int32)
    return out


# ---------------------------------------------------------------------------
# Continuous batching: slotted request pool over per-row caches.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PoolSetup:
    """Jitted building blocks of the continuous-batching engine
    (``launch/batcher.py`` drives them; ``docs/serving.md`` has the
    lifecycle diagram).

    * ``cache_init()`` — pooled per-row caches for ``slots`` rows at
      ``max_len``: every leaf carries the slot axis, and the per-layer
      ``len``/``pos`` counters are (B,) vectors ((B, H) alpha/beta) so each
      slot sits at its own depth with its own prompt calibration.
    * ``prefill_fn(plen, batch=1)`` — a jitted slot-local prefill
      ``(params, tokens (batch, plen)) -> (last logits, slot caches)`` at
      the requests' EXACT prompt length (compiled once per distinct
      (length, group size) — the ragged-prompt rule: LLN state accumulates
      every key it sees, so right-padding a prompt would corrupt the
      carry; see docs/serving.md).  ``batch > 1`` admits a same-length
      group in one dispatch (the engine only groups when per-request
      semantics are preserved: softmax, or fixed alpha/beta — dynamic
      moment matching pools statistics over the prompt batch).
    * ``admit_fn(pooled, slot_caches, slot_idx)`` — scatters the k rows of
      a slot-local cache into pool rows ``slot_idx`` ((k,) int32) via one
      fused per-leaf scatter (donated pooled carry, no host copies).
    * ``segment_fn(params, caches, tok, pos, remaining, active, key) ->
      (caches, tok, pos, remaining, active, tokens (S, B), emitted (S, B),
      unhealthy (B,), metrics)`` — ``segment`` decode steps folded into
      ONE jitted ``lax.scan`` with donated cache carry.  Each step decodes every
      slot, samples only active rows, advances per-row positions, and
      retires rows whose ``remaining`` hits zero (in-scan evict: the
      row's mask drops, so by the masked-row contract nothing it does
      from then on can mutate state).  ``unhealthy`` is the state-health
      sentinel (``core/health.py``) evaluated on the post-segment caches
      INSIDE the same dispatch — one fused reduction, no extra round
      trip; all-False when the pool was built with ``health=None``.
      ``metrics`` is the streaming concentration telemetry
      (``core/metrics.py:streaming_concentration_tree``), a dict of (B,)
      instruments (``conc_drift``/``log_mass``/``log_mass_var``/
      ``tau_hat``) computed from the carried O(d^2) LLN state in the
      SAME jit (None when ``telemetry=False`` or the pool carries no
      LLN state — decided at trace time, so the structure is stable).  With ``health.check_drift`` set, rows whose
      ``|conc_drift|`` exceeds ``health.max_conc_drift`` are OR-ed into
      ``unhealthy`` — concentration drift rides the same quarantine /
      re-prefill / replay recovery as corruption.
      Steady-state throughput therefore matches the static
      ``make_generate`` loop — admits/evicts never leave the scan.
    * ``replay_fn(params, caches, chunk (B, R), pos (B,), commit (B,))``
      — advance per-row state over already-committed tokens WITHOUT
      emitting: one partial-commit chunked decode (``commit_len``
      contract; rows with ``commit = 0`` are bitwise untouched).  The
      quarantine → re-prefill recovery path uses it to rebuild a row's
      state from its committed tokens (re-prefill the prompt, then
      replay the emitted tokens in ``R``-sized pieces) — exact under
      every calibration mode, because the replayed trajectory IS the
      original decode trajectory.  Fixed ``R = replay_chunk`` keeps this
      one compile total.
    * ``evict_fn(caches, row_mask)`` — the engine's ``evict`` lifted over
      the stacked layer tree: zeroes the masked rows ((slots,) bool, a
      fixed shape so eviction costs ONE compile total) of every cache
      leaf in one fused (donated) pass, so stale request state never
      outlives its request.  Admission overwrites a slot wholesale either
      way; eviction keeps the pool clean between the two.
    """
    cfg: Any
    model: Any
    mesh: Any
    rules: dict
    slots: int
    max_len: int
    segment: int
    temperature: float
    cache_init: Any
    prefill_fn: Any
    admit_fn: Any
    segment_fn: Any
    evict_fn: Any = None
    replay_fn: Any = None
    health: Any = None
    replay_chunk: int = 8
    telemetry: bool = True
    # Speculative pool (spec_k >= 1): every cache tree becomes the paired
    # {"target", "draft"} dict, each segment step is one draft+verify
    # iteration emitting 0..k+1 tokens per row, ``segment_fn``'s ``toks``
    # is (S, B, k+1) with ``emitted`` (S, B) int32 counts.
    spec_k: int = 0
    draft_layers: int = 0
    draft_model: Any = None


_HEALTH_DEFAULT = HealthConfig()


def make_pool_setup(cfg: ArchConfig, mesh, params_struct=None, *,
                    slots: int, max_len: int, segment: int = 8,
                    temperature: float = 0.0,
                    multi_pod: bool = False,
                    health: Optional[HealthConfig] = _HEALTH_DEFAULT,
                    replay_chunk: int = 8,
                    telemetry: bool = True,
                    spec_k: int = 0,
                    draft_layers: int = 0) -> PoolSetup:
    """Build the jitted pieces of the continuous-batching pool.

    Supports the dense/MoE decoder families with standard attention
    (softmax / lln / lln_diag KV-state caches); MLA caches are not wired
    for per-row decode yet.

    ``health``: a ``core/health.py:HealthConfig`` (the default) folds the
    per-row state-health sentinel into ``segment_fn``'s jitted dispatch;
    ``health=None`` disables it (the ``unhealthy`` output is then all
    False).  ``replay_chunk``: token-chunk width of ``replay_fn`` (the
    quarantine-recovery replay path) — fixed so replay costs one compile.

    ``spec_k >= 1`` makes the pool rows SPECULATIVE: every cache tree is
    the paired ``{"target", "draft"}`` dict (both states prefill on
    admission, advance in lockstep through replay/evict, and the draft is
    the tied first-``draft_layers`` parameter slice — no extra weights),
    and each segment step runs one draft-k/verify/accept iteration whose
    per-row accept counts become per-row ``commit_len`` (done / masked /
    quarantined rows freeze via ``commit_len=0``).  The verify is
    SINGLE-PASS: one ``commit_len=0`` target score returns per-layer
    (k, v) residuals and the accepted prefix folds via the O(T d^2)
    ``lm_commit`` einsum instead of a second full transformer pass.
    ``segment_fn``'s token stream widens to ``toks (S, B, k+1)`` with
    ``emitted (S, B)`` int32 counts per step (0 for frozen rows, up to
    ``spec_k + 1`` otherwise); a row may overshoot its budget by up to
    ``spec_k`` tokens in its final segment — the batcher caps harvest at
    the request budget and ``check_request`` reserves ``spec_k + 1`` cache
    slack.

    The pool's model calibrates moment matching PER ROW
    (``lln_per_row_calib=True``: each request's alpha/beta come from its
    own prompt statistics, (B, H) in the slot cache), which is what makes
    a batched slot prefill exact per request and lets the batcher group
    same-length admits even under dynamic moment matching.
    """
    if cfg.family not in ("dense", "moe", "ssm", "hybrid") \
            or cfg.kv_lora > 0:
        raise NotImplementedError(
            "continuous batching supports dense/moe decoders and "
            "ssm/hybrid models "
            f"(family={cfg.family}, kv_lora={cfg.kv_lora})")
    if spec_k < 0:
        raise ValueError(f"spec_k must be >= 0, got {spec_k}")
    if spec_k >= 1 and cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            "speculative pools need a first-k-layers draft "
            f"(family={cfg.family})")
    cfg = cfg.replace(lln_per_row_calib=True)
    model = build_model(cfg)
    rules = shd.make_rules(cfg, multi_pod=multi_pod, serve=True)
    speculative_pool = spec_k >= 1
    dmodel = None
    if speculative_pool:
        dcfg = draft_config(cfg, draft_layers)  # validates k and the family
        draft_layers = draft_layers or cfg.draft_layers
        dmodel = build_model(dcfg)
    k = spec_k

    def cache_init():
        struct = params_struct if params_struct is not None else \
            jax.eval_shape(model.init, jax.random.PRNGKey(0))
        tgt = model.cache_init(struct, slots, max_len, per_row=True)
        if not speculative_pool:
            return tgt
        # lm_cache_init derives the layout from cfg alone — the params
        # struct is signature-compat only, so the target's serves both.
        return {"target": tgt,
                "draft": dmodel.cache_init(struct, slots, max_len,
                                           per_row=True)}

    def _pf(params, tokens):
        with shd.logical_rules(mesh, rules):
            logits, tgt = model.prefill(params, {"inputs": tokens}, max_len)
            if not speculative_pool:
                return logits, tgt
            _, dr = dmodel.prefill(draft_params(params, cfg, draft_layers),
                                   {"inputs": tokens}, max_len)
        return logits, {"target": tgt, "draft": dr}

    _pf_jit = jax.jit(_pf)

    def prefill_fn(plen: int, batch: int = 1):
        # jax.jit caches executables per input shape, so one jitted object
        # serves every (prompt length, admit-group size); the signature
        # documents that each distinct pair costs one trace/compile.
        del plen, batch
        return _pf_jit

    def _admit(pooled, slot_caches, slot_idx):
        """Scatter a k-row slot-local cache into pool rows ``slot_idx``
        ((k,) int32).  Scalar-per-layer leaves (len/pos/alpha/beta, which a
        batched prefill shares across its rows) broadcast over the group.
        """
        k_rows = slot_idx.shape[0]

        def leaf(pl, sl):
            sl = sl.astype(pl.dtype)
            if sl.ndim == pl.ndim - 1:     # scalar-per-layer (len/pos/alpha)
                sl = jnp.broadcast_to(
                    sl[:, None], sl.shape[:1] + (k_rows,) + sl.shape[1:])
            return pl.at[:, slot_idx].set(sl)
        return jax.tree_util.tree_map(leaf, pooled, slot_caches)

    admit_fn = jax.jit(_admit, donate_argnums=(0,))

    def _evict(pooled, row_mask):
        """AttentionEngine.evict lifted over the stacked layer tree: reset
        the rows where ``row_mask`` ((slots,) bool) is True, on every leaf
        (slot axis at position 1, after the stacked-layer axis), to their
        ``init_state`` values — zeros everywhere EXCEPT the per-row
        calibration ``alpha``/``beta``, which reset to ones.  Zeroing the
        calibration would leave a freed slot carrying an out-of-contract
        value (init is ones), and a stale previous-request alpha/beta must
        never survive into the next request admitted to that slot.  A
        fixed (slots,) mask keeps this ONE compiled executable regardless
        of how many slots free per segment."""
        def clear(path, leaf):
            name = getattr(path[-1], "key", None)
            fill = (jnp.ones((), leaf.dtype) if name in ("alpha", "beta")
                    else jnp.zeros((), leaf.dtype))
            keep = ~row_mask.reshape((1, -1) + (1,) * (leaf.ndim - 2))
            return jnp.where(keep, leaf, fill)
        return jax.tree_util.tree_map_with_path(clear, pooled)

    evict_fn = jax.jit(_evict, donate_argnums=(0,))

    def _sentinel(tree, active):
        """Health + telemetry on the post-segment caches, fused into the
        segment dispatch.  ``tree`` is the TARGET cache tree (the draft of
        a speculative pool is a derived scratch state — corruption shows
        up in the target it commits against).  Row axis is 1 (after the
        stacked-layer axis)."""
        if health is not None:
            unhealthy = unhealthy_rows(tree, row_axis=1, config=health)
        else:
            unhealthy = jnp.zeros((slots,), jnp.bool_)
        # Streaming concentration telemetry on the same post-segment caches
        # (core/metrics.py): O(H d) per row off the carried (s, z, c_k)
        # state, in the SAME jit.  Whether the metrics dict exists is
        # decided at trace time (the cache tree either carries LLN ``z``
        # leaves or it doesn't), so the output pytree is stable per
        # compiled executable: a dict of fixed (B,) keys, or None for
        # ``telemetry=False`` / softmax-only pools.
        metrics = None
        conc = streaming_concentration_tree(tree, row_axis=1) \
            if telemetry else None
        if conc is not None:
            zero = jnp.zeros((slots,), jnp.float32)
            metrics = {k: conc.get(k, zero).astype(jnp.float32)
                       for k in ("log_mass", "log_mass_var",
                                 "tau_hat", "conc_drift")}
            if health is not None and health.check_drift:
                # Concentration drift -> quarantine: rides the same
                # re-prefill/replay recovery as a corrupted row.  Gated on
                # ``active``: a freed slot's zero state has meaningless
                # (hugely negative) log mass.
                drift_bad = active & (jnp.abs(metrics["conc_drift"])
                                      > health.max_conc_drift)
                unhealthy = unhealthy | drift_bad
        return unhealthy, metrics

    def _segment(params, caches, tok, pos, remaining, active, key):
        def body(carry, i):
            caches, tok, pos, remaining, active = carry
            logits, caches = model.decode(params, caches, tok, pos,
                                          row_mask=active)
            # Masked rows' logits are garbage by the decode contract (they
            # may even be NaN from a freshly evicted slot); neutralize them
            # BEFORE sampling so garbage never reaches sample_token.
            with jax.named_scope("sample"):
                logits = jnp.where(active[:, None], logits, 0.0)
                nxt = sample_token(logits, temperature,
                                   jax.random.fold_in(key, i))
            tok = jnp.where(active, nxt, tok)
            emitted = active
            adv = active.astype(jnp.int32)
            pos = pos + adv
            remaining = remaining - adv
            active = active & (remaining > 0)
            return (caches, tok, pos, remaining, active), (tok, emitted)

        with shd.logical_rules(mesh, rules):
            carry, (toks, emitted) = jax.lax.scan(
                body, (caches, tok, pos, remaining, active),
                jnp.arange(segment, dtype=jnp.int32))
        caches, tok, pos, remaining, active = carry
        with jax.named_scope("sentinel"):
            unhealthy, metrics = _sentinel(caches, active)
        return (caches, tok, pos, remaining, active, toks, emitted,
                unhealthy, metrics)

    def _segment_spec(params, caches, tok, pos, remaining, active, key):
        """Speculative segment: each scan step is one draft-k/verify/accept
        iteration over the paired {"target", "draft"} states.  Frozen rows
        (done / masked / quarantined) ride ``commit_len=0`` — bitwise
        inert on both states.  Emits (S, B, k+1) tokens with (S, B) int32
        per-step counts (0 for frozen rows)."""
        dparams = draft_params(params, cfg, draft_layers)

        def body(carry, i):
            caches, tok, pos, remaining, active = carry
            tgt, dr = caches["target"], caches["draft"]
            it_key = jax.random.fold_in(key, i)

            # Draft k tokens sequentially on scratch draft state (the
            # scratch advance is discarded; the committed draft state
            # refolds below through the partial-commit contract).
            def dstep(dc, j):
                dcache, cur = dc
                lg, dcache = dmodel.decode(dparams, dcache, cur, pos + j,
                                           row_mask=active)
                lg = jnp.where(active[:, None], lg, 0.0)
                nxt = sample_token(lg, temperature,
                                   jax.random.fold_in(it_key, j))
                return (dcache, nxt), (nxt, lg)

            _, (drafts, dlogits) = jax.lax.scan(
                dstep, (dr, tok), jnp.arange(k, dtype=jnp.int32))
            drafts = drafts.T                          # (B, k)
            dlogits = dlogits.transpose(1, 0, 2)       # (B, k, V)

            # Single-pass verify: ONE commit_len=0 target score over the
            # whole [tok, d_1..d_k] chunk returns logits for all k+1
            # positions AND the per-layer (k, v) commit residuals; the
            # target caches stay bitwise untouched.
            chunk = jnp.concatenate([tok[:, None], drafts], axis=1)
            tlogits, t_resid = model.score(params, tgt, chunk, pos,
                                           row_mask=active)
            tlogits = jnp.where(active[:, None, None], tlogits, 0.0)
            n_acc, nxt, commit = speculative.verify_tokens(
                drafts, tlogits, temperature,
                key=jax.random.fold_in(it_key, k + 1),
                draft_logits=dlogits)
            # Per-row accept counts -> per-row commit_len; frozen rows
            # commit nothing (the masked-row contract, bitwise).
            commit = jnp.where(active, commit, 0)
            tgt = model.commit(tgt, t_resid, commit, row_mask=active)
            _, dr = dmodel.decode(dparams, dr, chunk, pos,
                                  commit_len=commit, row_mask=active)

            n_emit = jnp.where(active, n_acc + 1, 0)
            toks_out = speculative.emit_tokens(drafts, n_acc, nxt)
            tok = jnp.where(active, nxt, tok)
            pos = pos + commit
            remaining = remaining - n_emit
            active = active & (remaining > 0)
            return ({"target": tgt, "draft": dr}, tok, pos, remaining,
                    active), (toks_out, n_emit)

        with shd.logical_rules(mesh, rules):
            carry, (toks, emitted) = jax.lax.scan(
                body, (caches, tok, pos, remaining, active),
                jnp.arange(segment, dtype=jnp.int32))
        caches, tok, pos, remaining, active = carry
        with jax.named_scope("sentinel"):
            unhealthy, metrics = _sentinel(caches["target"], active)
        return (caches, tok, pos, remaining, active, toks, emitted,
                unhealthy, metrics)

    segment_fn = jax.jit(_segment_spec if speculative_pool else _segment,
                         donate_argnums=(1,))

    def _replay(params, caches, chunk, pos, commit):
        """Advance per-row state over already-committed tokens without
        emitting: one chunked decode under the partial-commit contract
        (rows with ``commit = 0`` are bitwise untouched).  A speculative
        pool replays BOTH paired states — the replayed trajectory is the
        original committed trajectory for each."""
        with shd.logical_rules(mesh, rules):
            if speculative_pool:
                _, tgt = model.decode(params, caches["target"], chunk,
                                      pos, commit_len=commit)
                _, dr = dmodel.decode(draft_params(params, cfg,
                                                   draft_layers),
                                      caches["draft"], chunk, pos,
                                      commit_len=commit)
                return {"target": tgt, "draft": dr}
            _, caches = model.decode(params, caches, chunk, pos,
                                     commit_len=commit)
        return caches

    replay_fn = jax.jit(_replay, donate_argnums=(1,))

    return PoolSetup(cfg=cfg, model=model, mesh=mesh, rules=rules,
                     slots=slots, max_len=max_len, segment=segment,
                     temperature=temperature, cache_init=cache_init,
                     prefill_fn=prefill_fn, admit_fn=admit_fn,
                     segment_fn=segment_fn, evict_fn=evict_fn,
                     replay_fn=replay_fn, health=health,
                     replay_chunk=replay_chunk, telemetry=telemetry,
                     spec_k=spec_k, draft_layers=draft_layers,
                     draft_model=dmodel)
