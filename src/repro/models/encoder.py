"""Bidirectional encoder LM (RoBERTa-style) — the paper's §5 setting.

Token embeddings -> N bidirectional transformer blocks -> MLM head.
``attn_impl`` selects softmax / lln / lln_diag, reproducing the paper's
Table 1 comparison rows; with ``lln``/``lln_diag`` the encoder runs the
bidirectional LLN form (eq. 8) — the exact published configuration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention_block import attn_apply, attn_init
from .layers import (apply_mlp, apply_norm, embed_init, embed_lookup,
                     logits_from_hidden, mlp_init, norm_init, trunc_normal)
from .transformer import _remat


def encoder_init(key, cfg):
    ke, kl, kh = jax.random.split(key, 3)

    def block(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
                "attn": attn_init(k1, cfg),
                "ln2": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
                "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.act,
                                cfg.pdtype)}

    return {"embed": embed_init(ke, cfg.padded_vocab, cfg.d_model, cfg.pdtype),
            "layers": jax.vmap(block)(jax.random.split(kl, cfg.n_layers)),
            "final_norm": norm_init(cfg.d_model, cfg.norm, cfg.pdtype),
            "lm_head": trunc_normal(kh, (cfg.d_model, cfg.padded_vocab),
                                    cfg.d_model ** -0.5, cfg.pdtype)}


def encoder_hidden(p, tokens, cfg):
    with jax.named_scope("embed"):
        x = embed_lookup(p["embed"], tokens, cfg.cdtype, cfg.embed_scale)
    positions = jnp.arange(tokens.shape[1])

    def body(x, lp):
        with jax.named_scope("attn"):
            h = apply_norm(lp["ln1"], x, cfg.norm)
            x = x + attn_apply(lp["attn"], h, cfg, positions,
                               causal=False).astype(x.dtype)
        with jax.named_scope("mlp"):
            h = apply_norm(lp["ln2"], x, cfg.norm)
            x = x + apply_mlp(lp["mlp"], h, cfg.act,
                              cfg.cdtype).astype(x.dtype)
        return x, None

    x, _ = jax.lax.scan(_remat(body, cfg), x, p["layers"],
                        unroll=bool(cfg.scan_unroll))
    with jax.named_scope("mlm_head"):
        x = apply_norm(p["final_norm"], x, cfg.norm)
    return x, jnp.zeros((), jnp.float32)


def encoder_logits(p, tokens, cfg):
    h, aux = encoder_hidden(p, tokens, cfg)
    with jax.named_scope("mlm_head"):
        return logits_from_hidden(p["lm_head"], h, cfg.cdtype,
                                  cfg.logit_softcap), aux
