"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Queries/keys/values are compressed through low-rank latents:
  c_q  = RMSNorm(x W_dq)            (q_lora)
  q    = c_q W_uq                   -> per-head [nope | rope] parts
  c_kv = RMSNorm(x W_dkv)           (kv_lora = 512)
  k_nope, v = c_kv W_uk, c_kv W_uv  (decompressed per head)
  k_rope = RoPE(x W_kr)             (single shared rope key per position)

Decode caches only (c_kv, k_rope) — 576 floats/token — and uses the
*absorbed* formulation (W_uk folded into q, W_uv applied after the latent
context) so no per-step decompression of the whole cache is needed.

LLN applicability: the paper's technique applies to the assembled per-head
q/k (dim nope+rope); LLN decode then needs no token cache at all (O(d^2)
state) — the absorbed trick and the LLN state are two different routes to
the same memory goal, recorded separately in the roofline.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.engine import AttentionEngine, AttentionState
from repro.distributed.sharding import constrain
from .attention_block import batch_attention
from .layers import dense, dense_init, rope


def _dims(cfg):
    return (cfg.q_lora, cfg.kv_lora, cfg.nope_head_dim, cfg.rope_head_dim,
            cfg.v_head_dim, cfg.n_heads)


def mla_init(key, cfg):
    ql, kvl, nd, rd, vd, h = _dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    p = {"w_dkv": dense_init(ks[0], d, kvl, cfg.pdtype),
         "kv_norm_scale": jnp.ones((kvl,), cfg.pdtype),
         "w_uk": dense_init(ks[1], kvl, h * nd, cfg.pdtype),
         "w_uv": dense_init(ks[2], kvl, h * vd, cfg.pdtype),
         "w_kr": dense_init(ks[3], d, rd, cfg.pdtype),
         "o_w": dense_init(ks[4], h * vd, d, cfg.pdtype)}
    if ql:
        p["w_dq"] = dense_init(ks[5], d, ql, cfg.pdtype)
        p["q_norm_scale"] = jnp.ones((ql,), cfg.pdtype)
        p["w_uq"] = dense_init(ks[6], ql, h * (nd + rd), cfg.pdtype)
    else:
        p["w_q"] = dense_init(ks[7], d, h * (nd + rd), cfg.pdtype)
    return p


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * inv * scale.astype(jnp.float32)).astype(x.dtype)


def _q_proj(p, x, cfg, positions):
    ql, kvl, nd, rd, vd, h = _dims(cfg)
    b, n, _ = x.shape
    if ql:
        cq = _rms(dense(p["w_dq"], x, cfg.cdtype), p["q_norm_scale"])
        q = dense(p["w_uq"], cq, cfg.cdtype).reshape(b, n, h, nd + rd)
    else:
        q = dense(p["w_q"], x, cfg.cdtype).reshape(b, n, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _kv_latent(p, x, cfg, positions):
    ckv = _rms(dense(p["w_dkv"], x, cfg.cdtype), p["kv_norm_scale"])
    kr = dense(p["w_kr"], x, cfg.cdtype)[:, :, None, :]      # (B,N,1,rd)
    kr = rope(kr, positions, cfg.rope_theta)
    return ckv, kr


def _decompress(p, ckv, cfg):
    ql, kvl, nd, rd, vd, h = _dims(cfg)
    b, n, _ = ckv.shape
    k_nope = dense(p["w_uk"], ckv, cfg.cdtype).reshape(b, n, h, nd)
    v = dense(p["w_uv"], ckv, cfg.cdtype).reshape(b, n, h, vd)
    return k_nope, v


def _assemble(q_nope, q_rope, k_nope, kr):
    h = q_nope.shape[2]
    k_rope = jnp.broadcast_to(kr, kr.shape[:2] + (h, kr.shape[-1]))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, k_rope], -1)
    return q, k


def mla_apply(p, x, cfg, positions, *, causal: bool = True):
    """Full-sequence MLA (decompressed form), any attention impl."""
    b, n, _ = x.shape
    q_nope, q_rope = _q_proj(p, x, cfg, positions)
    ckv, kr = _kv_latent(p, x, cfg, positions)
    k_nope, v = _decompress(p, ckv, cfg)
    q, k = _assemble(q_nope, q_rope, k_nope, kr)
    q = constrain(q, "act_batch", "attn_seq", "heads", None)
    k = constrain(k, "act_batch", "attn_seq", "heads", None)
    v = constrain(v, "act_batch", "attn_seq", "heads", None)
    out = batch_attention(mla_engine(cfg, causal), q, k, v)
    out = out.reshape(b, n, -1)
    return dense(p["o_w"], out, cfg.cdtype)


# ---------------------------------------------------------------------------
# Serving — through the unified AttentionEngine.
#
# MLA's assembled per-head q/k (dim nope+rope, G == H) route through the
# same engine as standard attention, which is what gives MLA chunked
# multi-token decode and the kernelized LLN prefill/decode for free
# (ROADMAP "MLA serving parity").  Only the absorbed-form softmax decode
# stays MLA-specific: its state is the latent ``(ckv, kr)`` cache — carried
# in the same ``AttentionState`` pytree (``ckv``/``kr``/``len`` fields).
# ---------------------------------------------------------------------------

def mla_engine(cfg, causal: bool = True) -> AttentionEngine:
    """The engine for MLA's assembled q/k/v (full heads: G == H)."""
    ql, kvl, nd, rd, vd, h = _dims(cfg)
    return AttentionEngine.from_cfg(cfg, causal=causal, heads=h, kv_heads=h,
                                    head_dim=nd + rd, v_dim=vd)


def mla_state_init(cfg, batch: int, max_len: int) -> AttentionState:
    """Zeroed MLA decode state (per-row, like every engine state)."""
    ql, kvl, nd, rd, vd, h = _dims(cfg)
    if cfg.attn_impl == "softmax":
        return AttentionState(
            ckv=jnp.zeros((batch, max_len, kvl), cfg.cdtype),
            kr=jnp.zeros((batch, max_len, rd), cfg.cdtype),
            len=jnp.zeros((batch,), jnp.int32))
    return mla_engine(cfg).init_state(batch, max_len)


def mla_prefill(p, x, cfg, positions, *, max_len: int = 0):
    ql, kvl, nd, rd, vd, h = _dims(cfg)
    b, n, _ = x.shape
    q_nope, q_rope = _q_proj(p, x, cfg, positions)
    ckv, kr = _kv_latent(p, x, cfg, positions)
    k_nope, v = _decompress(p, ckv, cfg)
    q, k = _assemble(q_nope, q_rope, k_nope, kr)
    if cfg.attn_impl == "softmax":
        out = mla_engine(cfg).attention(q, k, v)
        ml = max(max_len, n)
        pad = ((0, 0), (0, ml - n), (0, 0))
        state = AttentionState(
            ckv=jnp.pad(ckv.astype(cfg.cdtype), pad),
            kr=jnp.pad(kr[:, :, 0].astype(cfg.cdtype), pad),
            len=jnp.full((b,), n, jnp.int32))
    else:
        out, state = mla_engine(cfg).prefill(q, k, v, max_len=max(max_len, n))
    out = out.reshape(b, n, -1)
    return dense(p["o_w"], out, cfg.cdtype), state


def _mla_absorbed_decode(p, cfg, state, q_nope, q_rope, ckv_new, kr_new):
    """Absorbed-form softmax decode over T >= 1 tokens: q is folded into
    the latent space (``W_uk``) so the whole cache is scored without
    per-step decompression; within-chunk causality comes from explicit
    absolute positions (``len + i``)."""
    ql, kvl, nd, rd, vd, h = _dims(cfg)
    b, t = q_nope.shape[:2]
    upd = lambda c, u, l: jax.lax.dynamic_update_slice_in_dim(c, u, l, 0)
    ckv = jax.vmap(upd)(state.ckv, ckv_new.astype(state.ckv.dtype),
                        state.len)
    krc = jax.vmap(upd)(state.kr, kr_new[:, :, 0].astype(state.kr.dtype),
                        state.len)
    ckv = constrain(ckv, "act_batch", "act_seq_cache", None)
    new_len = state.len + t
    # Absorbed: q' = q_nope @ W_uk (per head) lives in latent space.
    w_uk = p["w_uk"].reshape(kvl, h, nd)
    q_lat = jnp.einsum("bqhn,khn->bqhk", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    s = jnp.einsum("bqhk,bsk->bhqs", q_lat, ckv.astype(jnp.float32))
    s = s + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                       krc.astype(jnp.float32))
    s = s * ((nd + rd) ** -0.5)
    # Query i (absolute position len + i) sees keys j <= len + i.
    key_pos = jnp.arange(ckv.shape[1])
    q_pos = state.len[:, None] + jnp.arange(t)[None, :]           # (B, T)
    allowed = key_pos[None, None, None, :] <= q_pos[:, None, :, None]
    s = jnp.where(allowed, s, -1e30)
    attn = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqs,bsk->bqhk", attn, ckv.astype(jnp.float32))
    w_uv = p["w_uv"].reshape(kvl, h, vd)
    out = jnp.einsum("bqhk,khv->bqhv", ctx, w_uv.astype(jnp.float32))
    return (out.astype(cfg.cdtype),
            state.replace(ckv=ckv, kr=krc, len=new_len))


def mla_decode(p, x, state, cfg, position):
    """MLA decode over T >= 1 tokens (x: (B, T, d)) — the engine's chunked
    decode for LLN impls (``lln_decode_chunk`` with tails), the absorbed
    formulation for softmax.  ``position``: scalar or per-row (B,) index of
    the first new token."""
    ql, kvl, nd, rd, vd, h = _dims(cfg)
    b, n, _ = x.shape
    if jnp.ndim(position) == 0:
        pos = position + jnp.arange(n, dtype=jnp.int32)
    elif jnp.ndim(position) == 1:
        pos = position[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    else:
        pos = position
    q_nope, q_rope = _q_proj(p, x, cfg, pos)
    ckv_new, kr_new = _kv_latent(p, x, cfg, pos)

    if cfg.attn_impl == "softmax":
        out, state = _mla_absorbed_decode(p, cfg, state, q_nope, q_rope,
                                          ckv_new, kr_new)
    else:
        k_nope, v = _decompress(p, ckv_new, cfg)
        q, k = _assemble(q_nope, q_rope, k_nope, kr_new)
        out, state = mla_engine(cfg).decode(state, q, k, v)
    out = out.reshape(b, n, -1)
    return dense(p["o_w"], out, cfg.cdtype), state
