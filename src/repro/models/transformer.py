"""Decoder-only transformer LM (dense and MoE families).

Layers are stacked along a leading axis and executed with ``lax.scan``
(+ configurable remat) so tracing/compile cost is depth-independent — a
94-layer qwen3-moe traces the block exactly once.

Covers: yi-9b, stablelm-1.6b, qwen3-14b, chatglm3-6b (dense), qwen3-moe
(moe), deepseek-v2 (moe + MLA attention via models/mla.py), and serves as
the text decoder for paligemma and the shared-attention block for zamba2.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from . import mla as mla_mod
from .attention_block import (attn_apply, attn_init, serve_commit,
                              serve_decode, serve_prefill, serve_state_init)
from .layers import (apply_mlp, apply_norm, embed_init, embed_lookup,
                     logits_from_hidden, mlp_init, norm_init, trunc_normal)
from .moe import moe_apply, moe_init


def _use_mla(cfg) -> bool:
    return cfg.kv_lora > 0


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# One transformer block.
# ---------------------------------------------------------------------------

def block_init(key, cfg, *, use_moe: bool):
    ka, km = jax.random.split(key)
    p = {"ln1": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
         "ln2": norm_init(cfg.d_model, cfg.norm, cfg.pdtype)}
    p["attn"] = mla_mod.mla_init(ka, cfg) if _use_mla(cfg) else attn_init(ka, cfg)
    p["moe" if use_moe else "mlp"] = (
        moe_init(km, cfg) if use_moe
        else mlp_init(km, cfg.d_model, cfg.d_ff, cfg.act, cfg.pdtype))
    return p


def block_apply(p, x, cfg, positions, *, use_moe: bool, causal: bool = True,
                prefix_len: int = 0):
    x = constrain(x, "act_batch", "act_seq", "embed")
    with jax.named_scope("attn"):
        h = apply_norm(p["ln1"], x, cfg.norm)
        if _use_mla(cfg):
            attn_out = mla_mod.mla_apply(p["attn"], h, cfg, positions,
                                         causal=causal)
        else:
            attn_out = attn_apply(p["attn"], h, cfg, positions,
                                  causal=causal, prefix_len=prefix_len)
        x = x + attn_out.astype(x.dtype)
    with jax.named_scope("mlp"):
        h = apply_norm(p["ln2"], x, cfg.norm)
        if use_moe:
            ffn_out, aux = moe_apply(p["moe"], h, cfg)
        else:
            ffn_out, aux = apply_mlp(p["mlp"], h, cfg.act, cfg.cdtype), 0.0
        x = x + ffn_out.astype(x.dtype)
    return constrain(x, "act_batch", "act_seq", "embed"), jnp.asarray(
        aux, jnp.float32)


def block_prefill(p, x, cfg, positions, *, use_moe: bool, prefix_len: int = 0,
                  max_len: int = 0):
    with jax.named_scope("attn"):
        h = apply_norm(p["ln1"], x, cfg.norm)
        if _use_mla(cfg):
            attn_out, cache = mla_mod.mla_prefill(p["attn"], h, cfg,
                                                  positions, max_len=max_len)
        else:
            attn_out, cache = serve_prefill(p["attn"], h, cfg, positions,
                                            prefix_len=prefix_len,
                                            max_len=max_len)
        x = x + attn_out.astype(x.dtype)
    return _block_mlp(p, x, cfg, use_moe), cache


def _block_mlp(p, x, cfg, use_moe: bool):
    """The block's second half, ``x + MLP(norm(x))``, without the MoE
    auxiliary loss (the serving paths)."""
    with jax.named_scope("mlp"):
        h = apply_norm(p["ln2"], x, cfg.norm)
        ffn_out = (moe_apply(p["moe"], h, cfg)[0] if use_moe
                   else apply_mlp(p["mlp"], h, cfg.act, cfg.cdtype))
        return x + ffn_out.astype(x.dtype)


def block_score(p, x, cache, cfg, position, *, use_moe: bool,
                row_mask=None):
    """Speculative score pass over one block: a ``commit_len=0`` decode
    that leaves ``cache`` bitwise unchanged and returns the attention
    layer's ``{"k", "v"}`` commit residuals alongside the activations."""
    if _use_mla(cfg):
        raise NotImplementedError(
            "single-pass speculative verify is not wired for MLA")
    with jax.named_scope("attn"):
        h = apply_norm(p["ln1"], x, cfg.norm)
        zeros = jnp.zeros((x.shape[0],), jnp.int32)
        attn_out, _, resid = serve_decode(p["attn"], h, cache, cfg, position,
                                          row_mask=row_mask,
                                          commit_len=zeros,
                                          return_residuals=True)
        x = x + attn_out.astype(x.dtype)
    return _block_mlp(p, x, cfg, use_moe), resid


def block_decode(p, x, cache, cfg, position, *, use_moe: bool,
                 row_mask=None, commit_len=None):
    if _use_mla(cfg) and (row_mask is not None or commit_len is not None):
        raise NotImplementedError(
            "row-masked / partial-commit decode is not wired for MLA")
    with jax.named_scope("attn"):
        h = apply_norm(p["ln1"], x, cfg.norm)
        if _use_mla(cfg):
            attn_out, cache = mla_mod.mla_decode(p["attn"], h, cache, cfg,
                                                 position)
        else:
            attn_out, cache = serve_decode(p["attn"], h, cache, cfg,
                                           position, row_mask=row_mask,
                                           commit_len=commit_len)
        x = x + attn_out.astype(x.dtype)
    return _block_mlp(p, x, cfg, use_moe), cache


# ---------------------------------------------------------------------------
# Full LM.
# ---------------------------------------------------------------------------

def _layer_groups(cfg):
    """(num_dense_first, num_main, main_is_moe)."""
    is_moe = cfg.n_experts > 0
    first = cfg.first_dense_layers if is_moe else 0
    return first, cfg.n_layers - first, is_moe


def lm_init(key, cfg):
    ke, kf, kl, kh = jax.random.split(key, 4)
    first, n_main, is_moe = _layer_groups(cfg)
    p = {"embed": embed_init(ke, cfg.padded_vocab, cfg.d_model, cfg.pdtype),
         "final_norm": norm_init(cfg.d_model, cfg.norm, cfg.pdtype)}
    if first:
        keys = jax.random.split(kf, first)
        p["first_layers"] = jax.vmap(
            lambda k: block_init(k, cfg, use_moe=False))(keys)
    keys = jax.random.split(kl, n_main)
    p["layers"] = jax.vmap(lambda k: block_init(k, cfg, use_moe=is_moe))(keys)
    if not cfg.tie_embeddings:
        p["lm_head"] = trunc_normal(kh, (cfg.d_model, cfg.padded_vocab),
                                    cfg.d_model ** -0.5, cfg.pdtype)
    return p


def lm_head_of(p):
    return p["lm_head"] if "lm_head" in p else p["embed"]["table"].T


def lm_hidden(p, tokens, cfg, *, prefix_embed: Optional[jnp.ndarray] = None):
    """Token ids (B, N) -> final hidden states (B, N, D), plus MoE aux loss.

    ``prefix_embed``: optional (B, M, D) continuous prefix (vlm patches),
    prepended before the token embeddings; attention then uses a prefix-LM
    mask over those positions.
    """
    first, n_main, is_moe = _layer_groups(cfg)
    with jax.named_scope("embed"):
        x = embed_lookup(p["embed"], tokens, cfg.cdtype, cfg.embed_scale)
    prefix_len = 0
    if prefix_embed is not None:
        prefix_len = prefix_embed.shape[1]
        x = jnp.concatenate([prefix_embed.astype(x.dtype), x], axis=1)
    n = x.shape[1]
    positions = jnp.arange(n)
    aux = jnp.zeros((), jnp.float32)

    def body(use_moe):
        def fn(x, lp):
            x, a = block_apply(lp, x, cfg, positions, use_moe=use_moe,
                               prefix_len=prefix_len)
            return x, a
        return _remat(fn, cfg)

    if first:
        x, auxs = jax.lax.scan(body(False), x, p["first_layers"],
                               unroll=bool(cfg.scan_unroll))
        aux += jnp.sum(auxs)
    x, auxs = jax.lax.scan(body(is_moe), x, p["layers"],
                           unroll=bool(cfg.scan_unroll))
    aux += jnp.sum(auxs)
    with jax.named_scope("lm_head"):
        x = apply_norm(p["final_norm"], x, cfg.norm)
    return x, aux


def lm_logits(p, tokens, cfg, **kw):
    h, aux = lm_hidden(p, tokens, cfg, **kw)
    with jax.named_scope("lm_head"):
        return logits_from_hidden(lm_head_of(p), h, cfg.cdtype,
                                  cfg.logit_softcap), aux


# ---------------------------------------------------------------------------
# Serving.
# ---------------------------------------------------------------------------

def lm_cache_init(p, cfg, batch: int, max_len: int, per_row: bool = False):
    """Stacked per-layer decode caches (``AttentionState`` per layer).

    The engine state is ALWAYS per-row ((B,) ``len``/``pos``, (B, H)
    alpha/beta) — the static lockstep batch is the degenerate case — so
    ``per_row`` is accepted for backward compatibility and ignored."""
    del per_row
    first, n_main, is_moe = _layer_groups(cfg)
    one = (mla_mod.mla_state_init(cfg, batch, max_len) if _use_mla(cfg)
           else serve_state_init(cfg, batch, max_len))

    def stack(n):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape), one)
    caches = {"layers": stack(n_main)}
    if first:
        caches["first_layers"] = stack(first)
    return caches


def lm_prefill(p, tokens, cfg, max_len: int,
               prefix_embed: Optional[jnp.ndarray] = None):
    """Prompt forward.  Returns (last-position logits, caches)."""
    first, n_main, is_moe = _layer_groups(cfg)
    with jax.named_scope("embed"):
        x = embed_lookup(p["embed"], tokens, cfg.cdtype, cfg.embed_scale)
    prefix_len = 0
    if prefix_embed is not None:
        prefix_len = prefix_embed.shape[1]
        x = jnp.concatenate([prefix_embed.astype(x.dtype), x], axis=1)
    n = x.shape[1]
    positions = jnp.arange(n)
    caches = {}

    def mk(use_moe):
        def fn(x, lp):
            x, cache = block_prefill(lp, x, cfg, positions, use_moe=use_moe,
                                     prefix_len=prefix_len,
                                     max_len=max_len)
            return x, cache
        return _remat(fn, cfg) if cfg.remat != "none" else fn

    if first:
        x, caches["first_layers"] = jax.lax.scan(mk(False), x,
                                                 p["first_layers"],
                                                 unroll=bool(cfg.scan_unroll))
    x, caches["layers"] = jax.lax.scan(mk(is_moe), x, p["layers"],
                                       unroll=bool(cfg.scan_unroll))
    with jax.named_scope("lm_head"):
        x = apply_norm(p["final_norm"], x, cfg.norm)
        logits = logits_from_hidden(lm_head_of(p), x[:, -1:], cfg.cdtype,
                                    cfg.logit_softcap)
    return logits, caches


# Trace-time full-pass counter: each lm_decode / lm_score TRACE bumps the
# config's entry, so lowering a jitted generation loop (whose lax.scan body
# traces exactly once) counts the full transformer passes per loop
# iteration — benchmarks/bench_spec.py uses it to gate target passes per
# verify iteration.  lm_commit is O(T d^2) per layer and does not count.
DECODE_PASS_COUNTS: dict = {}


def _count_pass(cfg):
    DECODE_PASS_COUNTS[cfg.name] = DECODE_PASS_COUNTS.get(cfg.name, 0) + 1


def lm_decode(p, caches, token, cfg, position, row_mask=None,
              commit_len=None):
    """Decode step.  token: (B,) or (B, T) int32 — T > 1 advances the caches
    over a whole chunk in one dispatch (multi-token/speculative scoring);
    position: scalar int32 index of the first new token, or a per-row (B,)
    vector when the caches were allocated ``per_row`` (continuous
    batching).  ``row_mask``: optional (B,) bool — masked-off rows leave
    every cache leaf untouched and their logits are garbage.
    ``commit_len``: optional per-row (B,) int32 in [0, T] — the
    speculative verify pass: logits cover all T draft positions, every
    layer's cache folds only the accepted prefix (``commit_len=0`` rows
    behave like masked rows).  Returns logits (B, V) for (B,) input,
    (B, T, V) for chunked input."""
    single = token.ndim == 1
    _count_pass(cfg)
    first, n_main, is_moe = _layer_groups(cfg)
    toks = token[:, None] if single else token
    with jax.named_scope("embed"):
        x = embed_lookup(p["embed"], toks, cfg.cdtype, cfg.embed_scale)
    new_caches = {}

    def mk(use_moe):
        def fn(x, xs):
            lp, cache = xs
            x, cache = block_decode(lp, x, cache, cfg, position,
                                    use_moe=use_moe, row_mask=row_mask,
                                    commit_len=commit_len)
            return x, cache
        return fn

    if first:
        x, new_caches["first_layers"] = jax.lax.scan(
            mk(False), x, (p["first_layers"], caches["first_layers"]),
            unroll=bool(cfg.scan_unroll))
    x, new_caches["layers"] = jax.lax.scan(
        mk(is_moe), x, (p["layers"], caches["layers"]),
        unroll=bool(cfg.scan_unroll))
    with jax.named_scope("lm_head"):
        x = apply_norm(p["final_norm"], x, cfg.norm)
        logits = logits_from_hidden(lm_head_of(p), x, cfg.cdtype,
                                    cfg.logit_softcap)
    return (logits[:, 0] if single else logits), new_caches


def lm_score(p, caches, token, cfg, position, row_mask=None):
    """Speculative score pass: logits for a (B, T) draft chunk WITHOUT
    advancing the caches, plus per-layer commit residuals.

    A ``commit_len=0`` decode leaves every cache leaf bitwise unchanged,
    so the caller keeps using ``caches`` as-is; once the acceptance rule
    has produced per-row commit lengths, :func:`lm_commit` folds the
    accepted prefix from the returned residuals with the cheap O(T d^2)
    per-layer einsum — one full transformer pass per verify iteration
    instead of two.  Returns ``(logits (B, T, V), residuals)`` where
    ``residuals`` mirrors the cache dict: stacked per-layer
    ``{"k", "v"}`` (L, B, T, G, D[v]) trees under the same keys.
    """
    _count_pass(cfg)
    first, n_main, is_moe = _layer_groups(cfg)
    with jax.named_scope("embed"):
        x = embed_lookup(p["embed"], token, cfg.cdtype, cfg.embed_scale)
    residuals = {}

    def mk(use_moe):
        def fn(x, xs):
            lp, cache = xs
            x, resid = block_score(lp, x, cache, cfg, position,
                                   use_moe=use_moe, row_mask=row_mask)
            return x, resid
        return fn

    if first:
        x, residuals["first_layers"] = jax.lax.scan(
            mk(False), x, (p["first_layers"], caches["first_layers"]),
            unroll=bool(cfg.scan_unroll))
    x, residuals["layers"] = jax.lax.scan(
        mk(is_moe), x, (p["layers"], caches["layers"]),
        unroll=bool(cfg.scan_unroll))
    with jax.named_scope("lm_head"):
        x = apply_norm(p["final_norm"], x, cfg.norm)
        logits = logits_from_hidden(lm_head_of(p), x, cfg.cdtype,
                                    cfg.logit_softcap)
    return logits, residuals


def lm_commit(caches, residuals, cfg, commit_len, row_mask=None):
    """Fold the accepted prefix of a scored chunk into every layer's cache.

    Params-free: the residuals already carry the post-RoPE (k, v) the
    score pass computed, so the commit is one O(T d^2) einsum per layer
    (``AttentionEngine.commit``) — no projections, no MLP, no logits.
    Bit-identical per backend to re-running :func:`lm_decode` with the
    same ``commit_len``.  Returns the new caches.
    """
    if _use_mla(cfg):
        raise NotImplementedError(
            "single-pass speculative verify is not wired for MLA")
    new_caches = {}

    def fn(carry, xs):
        cache, resid = xs
        return carry, serve_commit(cache, resid, cfg,
                                   commit_len=commit_len,
                                   row_mask=row_mask)

    for name in caches:
        _, new_caches[name] = jax.lax.scan(
            fn, 0, (caches[name], residuals[name]),
            unroll=bool(cfg.scan_unroll))
    return new_caches
