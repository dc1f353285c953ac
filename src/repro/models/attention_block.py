"""Standard attention sub-block: projections + RoPE + unified attention.

Used by the dense/MoE decoder LMs, the seamless encoder/decoder, the
PaliGemma decoder and Zamba2's shared attention block.  Supports the three
attention impls (softmax / lln / lln_diag), GQA/MQA, qk-norm and partial
RoPE.

Serving runs through the unified :class:`repro.core.engine.AttentionEngine`
(one ``AttentionState`` pytree, per-row counters, backend dispatch owned by
``kernels/registry.py``): ``serve_state_init`` / ``serve_prefill`` /
``serve_decode`` are the canonical entry points; the legacy
``attn_cache_init`` / ``attn_prefill`` / ``attn_decode`` names survive as
deprecation shims delegating to them (see ``docs/api.md``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import attention as ca
from repro.core.attention import AttnConfig
from repro.core.engine import AttentionEngine
from repro.kernels.registry import deprecated_shim, on_cpu
from repro.distributed.sharding import constrain
from .layers import dense, dense_init, rms_head_norm, rope


def attn_cfg_of(cfg, causal: bool = True) -> AttnConfig:
    return AttnConfig(impl=cfg.attn_impl, causal=causal,
                      diag_block=cfg.diag_block, lln_chunk=cfg.lln_chunk,
                      softmax_chunk=cfg.softmax_chunk,
                      use_kernel=cfg.use_kernel or not on_cpu(),
                      backend=(None if cfg.attn_backend == "auto"
                               else cfg.attn_backend),
                      fixed_ab=cfg.lln_fixed_ab,
                      num_scales=getattr(cfg, "lln_num_scales", 4),
                      scale_decay=getattr(cfg, "lln_scale_decay", 0.5))


def attn_engine(cfg, causal: bool = True) -> AttentionEngine:
    """The serving engine an ``ArchConfig`` attention layer implies."""
    return AttentionEngine.from_cfg(cfg, causal=causal)


def attn_init(key, cfg, d_in: Optional[int] = None):
    d = d_in or cfg.d_model
    hd, h, g = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {"q_w": dense_init(ks[0], d, h * hd, cfg.pdtype),
         "k_w": dense_init(ks[1], d, g * hd, cfg.pdtype),
         "v_w": dense_init(ks[2], d, g * hd, cfg.pdtype),
         "o_w": dense_init(ks[3], h * hd, cfg.d_model, cfg.pdtype)}
    if cfg.qk_norm:
        p["q_norm_scale"] = jnp.ones((hd,), cfg.pdtype)
        p["k_norm_scale"] = jnp.ones((hd,), cfg.pdtype)
    return p


def _project_qkv(p, x, cfg, positions):
    b, n, _ = x.shape
    hd, h, g = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = dense(p["q_w"], x, cfg.cdtype).reshape(b, n, h, hd)
    k = dense(p["k_w"], x, cfg.cdtype).reshape(b, n, g, hd)
    v = dense(p["v_w"], x, cfg.cdtype).reshape(b, n, g, hd)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm_scale"], q)
        k = rms_head_norm(p["k_norm_scale"], k)
    q = rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
    k = rope(k, positions, cfg.rope_theta, cfg.rotary_pct)
    q = constrain(q, "act_batch", "attn_seq", "heads", None)
    k = constrain(k, "act_batch", None, "kv_heads", None)
    v = constrain(v, "act_batch", None, "kv_heads", None)
    return q, k, v


def attn_apply(p, x, cfg, positions, *, causal: bool = True,
               kv: Optional[jnp.ndarray] = None,
               mask: Optional[jnp.ndarray] = None,
               prefix_len: int = 0) -> jnp.ndarray:
    """Full-sequence attention.  ``kv``: optional cross-attention memory
    (B, M, d) — used by the seamless decoder (always softmax for cross)."""
    b, n, _ = x.shape
    hd, h, g = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    if kv is None:
        q, k, v = _project_qkv(p, x, cfg, positions)
        out = ca.multi_head_attention(q, k, v, attn_cfg_of(cfg, causal),
                                      mask=mask, prefix_len=prefix_len)
    else:
        m = kv.shape[1]
        q = dense(p["q_w"], x, cfg.cdtype).reshape(b, n, h, hd)
        k = dense(p["k_w"], kv, cfg.cdtype).reshape(b, m, g, hd)
        v = dense(p["v_w"], kv, cfg.cdtype).reshape(b, m, g, hd)
        q = constrain(q, "act_batch", "attn_seq", "heads", None)
        k = constrain(k, "act_batch", None, "kv_heads", None)
        v = constrain(v, "act_batch", None, "kv_heads", None)
        out = ca.flash_softmax(q, k, v, causal=False,
                               chunk=min(cfg.softmax_chunk, m), mask=mask)
    out = out.reshape(b, n, h * hd)
    out = constrain(out, "act_batch", "attn_seq", None)
    return dense(p["o_w"], out, cfg.cdtype)


# ---------------------------------------------------------------------------
# Serving: the unified engine lifecycle (init_state -> prefill -> decode).
#
# One ``AttentionState`` pytree for every impl, per-row counters always
# (static lockstep batching is the degenerate case where all rows agree),
# diag tails at the G kv heads, backend dispatch (pallas / scan twin / jnp
# ref) owned by ``kernels/registry.py``.  The legacy seed path that
# ``use_serve_kernel=False`` selected is now ``backend='ref'``
# (``AttnSpec.from_cfg`` does that mapping) — used by
# ``benchmarks/bench_serve.py`` as the baseline.
# ---------------------------------------------------------------------------

def serve_state_init(cfg, batch: int, max_len: int):
    """Zeroed :class:`~repro.core.engine.AttentionState` for one layer.

    Always per-row: ``len``/``pos`` are (B,) and alpha/beta (B, H), so the
    same cache layout serves the static lockstep loop and the
    continuous-batching pool (each slot at its own depth with its own
    prompt calibration).
    """
    return attn_engine(cfg).init_state(batch, max_len)


def serve_prefill(p, x, cfg, positions, *, prefix_len: int = 0,
                  max_len: int = 0):
    """Forward over the prompt; returns ``(out, AttentionState)``.  The
    softmax KV cache is allocated at ``max_len`` (>= n) so decode appends
    in place; LLN emits the O(d^2) state from the same pass."""
    b, n, _ = x.shape
    hd, h = cfg.hd, cfg.n_heads
    q, k, v = _project_qkv(p, x, cfg, positions)
    eng = attn_engine(cfg)
    out, state = eng.prefill(q, k, v, max_len=max(max_len, n),
                             prefix_len=prefix_len)
    out = out.reshape(b, n, h * hd)
    return dense(p["o_w"], out, cfg.cdtype), state


def serve_decode(p, x, state, cfg, position, *, row_mask=None,
                 commit_len=None, return_residuals: bool = False,
                 defer_tail: bool = False):
    """Decode over T >= 1 new tokens.  x: (B, T, d).

    ``position``: absolute index of the first new token — a scalar (static
    batch: every row at the same depth; T=1 is the generation loop, T>1 the
    chunked multi-token / speculative-scoring path) or a per-row (B,)
    vector (continuous batching: every slot at its own depth).
    ``row_mask``: optional (B,) bool — rows where it is False write nothing
    (KV cache / LLN state / tails / positions all keep their old values);
    their outputs are garbage and must be discarded by the caller.
    ``commit_len``: optional per-row (B,) int32 in [0, T] — speculative
    partial commit: all T positions are scored, only the accepted prefix
    folds into the state (``AttentionEngine.verify``).
    ``return_residuals=True`` (requires ``commit_len``) returns a third
    element — the layer's ``{"k", "v"}`` post-RoPE commit residuals — so a
    ``commit_len=0`` score pass can be folded later by
    :func:`serve_commit` without a second full pass.
    ``defer_tail=True`` (T = 1, a state with diag tails) leaves the tails
    unchanged and returns the tail row the step writes as a third element
    (``AttentionEngine.decode``).
    """
    b, n, _ = x.shape
    hd, h, g = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = dense(p["q_w"], x, cfg.cdtype).reshape(b, n, h, hd)
    k = dense(p["k_w"], x, cfg.cdtype).reshape(b, n, g, hd)
    v = dense(p["v_w"], x, cfg.cdtype).reshape(b, n, g, hd)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm_scale"], q)
        k = rms_head_norm(p["k_norm_scale"], k)
    if jnp.ndim(position) == 0:
        pos = position + jnp.arange(n, dtype=jnp.int32)
    elif jnp.ndim(position) == 1:
        # Per-row bases: (B,) -> (B, T) absolute positions.
        pos = position[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    else:
        pos = position
    q = rope(q, pos, cfg.rope_theta, cfg.rotary_pct)
    k = rope(k, pos, cfg.rope_theta, cfg.rotary_pct)
    eng = attn_engine(cfg)
    if return_residuals:
        out, state, resid = eng.verify(state, q, k, v, row_mask=row_mask,
                                       commit_len=commit_len,
                                       return_residuals=True)
        out = out.reshape(b, n, h * hd)
        return dense(p["o_w"], out, cfg.cdtype), state, resid
    out, *rest = eng.decode(state, q, k, v, row_mask=row_mask,
                            commit_len=commit_len, defer_tail=defer_tail)
    out = out.reshape(b, n, h * hd)
    return (dense(p["o_w"], out, cfg.cdtype), *rest)


def serve_commit(state, residual, cfg, *, commit_len, row_mask=None):
    """Params-free commit of a scored chunk's accepted prefix.

    ``residual``: the ``{"k", "v"}`` dict :func:`serve_decode` returned
    under ``return_residuals=True`` (the projections and RoPE already
    happened in the score pass); ``state`` the state that pass ran against
    (bitwise unchanged by a ``commit_len=0`` score).  O(T d^2) per layer —
    :meth:`repro.core.engine.AttentionEngine.commit`.
    """
    return attn_engine(cfg).commit(state, residual, commit_len=commit_len,
                                   row_mask=row_mask)


# --- legacy entry points (deprecation shims over the engine) ---------------

@deprecated_shim("models.attention_block.attn_cache_init",
                 "attn_engine(cfg).init_state / serve_state_init")
def attn_cache_init(cfg, batch: int, max_len: int, per_row: bool = False):
    """Legacy cache initializer.  The engine state is always per-row now,
    so ``per_row`` is accepted and ignored (the scalar layout was the
    degenerate case and has been deleted)."""
    del per_row
    return serve_state_init(cfg, batch, max_len)


@deprecated_shim("models.attention_block.attn_prefill", "serve_prefill")
def attn_prefill(p, x, cfg, positions, *, prefix_len: int = 0,
                 max_len: int = 0):
    """Legacy prefill — delegates to :func:`serve_prefill`."""
    return serve_prefill(p, x, cfg, positions, prefix_len=prefix_len,
                         max_len=max_len)


@deprecated_shim("models.attention_block.attn_decode", "serve_decode")
def attn_decode(p, x, cache, cfg, position, *, row_mask=None):
    """Legacy decode — delegates to :func:`serve_decode`."""
    return serve_decode(p, x, cache, cfg, position, row_mask=row_mask)
