"""Encoder-decoder transformer (seamless-m4t-medium backbone).

The audio frontend is a STUB per the assignment: input_specs() provides
precomputed frame embeddings (B, M, frontend_dim); a linear adapter maps
them to d_model.  The encoder is bidirectional — the paper's exact published
setting for LLN attention (RoBERTa-style bidirectional encoder) — so
``attn_impl=lln_diag`` exercises eq. 8 in its native habitat.  Cross
attention stays softmax (N_q x M rectangle; LLN's state trick brings no
asymptotic win there and the paper does not linearize it).

Simplifications vs. the released m4t checkpoints (DESIGN.md): standard RoPE
instead of conformer relative-position machinery.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import attention as ca
from repro.distributed.sharding import constrain
from .attention_block import (attn_apply, attn_init, serve_decode,
                              serve_prefill, serve_state_init)
from .layers import (apply_mlp, apply_norm, dense, dense_init, embed_init,
                     embed_lookup, logits_from_hidden, mlp_init, norm_init,
                     trunc_normal)
from .transformer import _remat


def encdec_init(key, cfg):
    kf, ke, kd, kt, kh = jax.random.split(key, 5)
    p = {"frontend_proj": dense_init(kf, cfg.frontend_dim, cfg.d_model,
                                     cfg.pdtype),
         "embed": embed_init(kt, cfg.padded_vocab, cfg.d_model, cfg.pdtype),
         "enc_final_norm": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
         "final_norm": norm_init(cfg.d_model, cfg.norm, cfg.pdtype)}

    def enc_block(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
                "attn": attn_init(k1, cfg),
                "ln2": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
                "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.act,
                                cfg.pdtype)}

    def dec_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"ln1": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
                "attn": attn_init(k1, cfg),
                "ln_x": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
                "cross": attn_init(k2, cfg),
                "ln2": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
                "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.act,
                                cfg.pdtype)}

    p["enc_layers"] = jax.vmap(enc_block)(
        jax.random.split(ke, cfg.enc_layers))
    p["layers"] = jax.vmap(dec_block)(jax.random.split(kd, cfg.n_layers))
    p["lm_head"] = trunc_normal(kh, (cfg.d_model, cfg.padded_vocab),
                                cfg.d_model ** -0.5, cfg.pdtype)
    return p


def encode(p, src_embed, cfg):
    """src_embed: (B, M, frontend_dim) stub frame embeddings -> (B, M, D)."""
    x = dense(p["frontend_proj"], src_embed, cfg.cdtype)
    positions = jnp.arange(x.shape[1])

    def body(x, lp):
        h = apply_norm(lp["ln1"], x, cfg.norm)
        x = x + attn_apply(lp["attn"], h, cfg, positions,
                           causal=False).astype(x.dtype)
        h = apply_norm(lp["ln2"], x, cfg.norm)
        x = x + apply_mlp(lp["mlp"], h, cfg.act, cfg.cdtype).astype(x.dtype)
        return x, None

    x, _ = jax.lax.scan(_remat(body, cfg), x, p["enc_layers"],
                        unroll=bool(cfg.scan_unroll))
    return apply_norm(p["enc_final_norm"], x, cfg.norm)


def _dec_block(lp, x, enc_out, cfg, positions):
    h = apply_norm(lp["ln1"], x, cfg.norm)
    x = x + attn_apply(lp["attn"], h, cfg, positions,
                       causal=True).astype(x.dtype)
    h = apply_norm(lp["ln_x"], x, cfg.norm)
    x = x + attn_apply(lp["cross"], h, cfg, positions,
                       kv=enc_out).astype(x.dtype)
    h = apply_norm(lp["ln2"], x, cfg.norm)
    return x + apply_mlp(lp["mlp"], h, cfg.act, cfg.cdtype).astype(x.dtype)


def encdec_hidden(p, src_embed, tgt_tokens, cfg):
    enc_out = encode(p, src_embed, cfg)
    x = embed_lookup(p["embed"], tgt_tokens, cfg.cdtype, cfg.embed_scale)
    positions = jnp.arange(tgt_tokens.shape[1])

    def body(x, lp):
        return _dec_block(lp, x, enc_out, cfg, positions), None

    x, _ = jax.lax.scan(_remat(body, cfg), x, p["layers"],
                        unroll=bool(cfg.scan_unroll))
    x = apply_norm(p["final_norm"], x, cfg.norm)
    return x, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Serving.
# ---------------------------------------------------------------------------

def encdec_cache_init(p, cfg, batch: int, max_len: int, enc_len: int):
    one = serve_state_init(cfg, batch, max_len)
    g, hd = cfg.n_kv_heads, cfg.hd
    cross = {"ck": jnp.zeros((batch, enc_len, g, hd), cfg.cdtype),
             "cv": jnp.zeros((batch, enc_len, g, hd), cfg.cdtype)}
    return {"layers": jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape),
        {"self": one, **cross})}


def encdec_prefill(p, src_embed, tgt_tokens, cfg, max_len: int):
    """Encode source + prefill decoder over the target prefix."""
    enc_out = encode(p, src_embed, cfg)
    x = embed_lookup(p["embed"], tgt_tokens, cfg.cdtype, cfg.embed_scale)
    n = tgt_tokens.shape[1]
    positions = jnp.arange(n)
    b = x.shape[0]
    g, hd = cfg.n_kv_heads, cfg.hd

    def body(x, lp):
        h = apply_norm(lp["ln1"], x, cfg.norm)
        a, self_cache = serve_prefill(lp["attn"], h, cfg, positions,
                                      max_len=max_len)
        x = x + a.astype(x.dtype)
        h = apply_norm(lp["ln_x"], x, cfg.norm)
        m = enc_out.shape[1]
        ck = dense(lp["cross"]["k_w"], enc_out, cfg.cdtype).reshape(b, m, g, hd)
        cv = dense(lp["cross"]["v_w"], enc_out, cfg.cdtype).reshape(b, m, g, hd)
        q = dense(lp["cross"]["q_w"], h, cfg.cdtype).reshape(
            b, n, cfg.n_heads, hd)
        q = constrain(q, "act_batch", "attn_seq", "heads", None)
        ck = constrain(ck, "act_batch", None, "kv_heads", None)
        cv = constrain(cv, "act_batch", None, "kv_heads", None)
        xa = ca.flash_softmax(q, ck, cv, causal=False,
                              chunk=min(cfg.softmax_chunk, m),
                              constrain=constrain)
        xa = dense(lp["cross"]["o_w"], xa.reshape(b, n, -1), cfg.cdtype)
        x = x + xa.astype(x.dtype)
        h = apply_norm(lp["ln2"], x, cfg.norm)
        x = x + apply_mlp(lp["mlp"], h, cfg.act, cfg.cdtype).astype(x.dtype)
        return x, {"self": self_cache, "ck": ck, "cv": cv}

    x, caches = jax.lax.scan(body, x, p["layers"],
                             unroll=bool(cfg.scan_unroll))
    x = apply_norm(p["final_norm"], x, cfg.norm)
    logits = logits_from_hidden(p["lm_head"], x[:, -1:], cfg.cdtype,
                                cfg.logit_softcap)
    return logits, {"layers": caches}


def encdec_decode(p, caches, token, cfg, position):
    if token.ndim != 1:
        raise NotImplementedError(
            "chunked (B, T) decode is not wired for the encdec family")
    x = embed_lookup(p["embed"], token[:, None], cfg.cdtype, cfg.embed_scale)
    b = x.shape[0]

    def body(x, xs):
        lp, cache = xs
        h = apply_norm(lp["ln1"], x, cfg.norm)
        a, self_cache = serve_decode(lp["attn"], h, cache["self"], cfg,
                                     position)
        x = x + a.astype(x.dtype)
        h = apply_norm(lp["ln_x"], x, cfg.norm)
        q = dense(lp["cross"]["q_w"], h, cfg.cdtype).reshape(
            b, 1, cfg.n_heads, cfg.hd)
        xa = ca.flash_softmax(q, cache["ck"], cache["cv"], causal=False,
                              chunk=min(cfg.softmax_chunk,
                                        cache["ck"].shape[1]),
                              constrain=constrain)
        xa = dense(lp["cross"]["o_w"], xa.reshape(b, 1, -1), cfg.cdtype)
        x = x + xa.astype(x.dtype)
        h = apply_norm(lp["ln2"], x, cfg.norm)
        x = x + apply_mlp(lp["mlp"], h, cfg.act, cfg.cdtype).astype(x.dtype)
        return x, {"self": self_cache, "ck": cache["ck"], "cv": cache["cv"]}

    x, new_caches = jax.lax.scan(body, x, (p["layers"], caches["layers"]),
                                 unroll=bool(cfg.scan_unroll))
    x = apply_norm(p["final_norm"], x, cfg.norm)
    logits = logits_from_hidden(p["lm_head"], x, cfg.cdtype, cfg.logit_softcap)
    return logits[:, 0], {"layers": new_caches}
