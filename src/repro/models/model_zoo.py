"""build_model(cfg) -> Model: a uniform functional interface per family.

Batch conventions (all int32 tokens in [0, vocab)):
  lm / moe / ssm / hybrid : {"inputs" (B,N), "targets" (B,N), "mask" (B,N)}
  encdec                  : + {"src" (B,M,frontend_dim) float}
  vlm                     : + {"patches" (B,P,frontend_dim) float}

``loss``  : params, batch -> scalar (chunked xent + router aux).
``prefill``: params, batch -> (last logits (B, Vpad), caches).
``decode`` : params, caches, token (B,) or (B, T), position -> (logits,
             caches).  Dense/MoE decoders additionally accept a per-row
             (B,) ``position`` plus ``row_mask`` against per-row caches
             (``cache_init(..., per_row=True)``) — the continuous-batching
             contract (masked rows advance nothing).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import encdec as ed
from . import hybrid as hy
from . import transformer as tr
from . import vlm as vl
from .layers import chunked_xent


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable
    loss: Callable
    hidden: Callable
    prefill: Callable
    decode: Callable
    cache_init: Callable
    param_count: Callable
    # Single-pass speculative verify (dense/moe decoders only; None
    # elsewhere): ``score`` = logits + per-layer (k, v) residuals without
    # advancing the caches; ``commit`` = params-free O(T d^2) fold of the
    # accepted prefix (transformer.py:lm_score / lm_commit).
    score: Optional[Callable] = None
    commit: Optional[Callable] = None


def _xent_loss(cfg, h, head, batch, scope: str = "lm_head"):
    with jax.named_scope(scope):
        return chunked_xent(h, head, batch["targets"], batch["mask"],
                            vocab=cfg.vocab, dtype=cfg.cdtype,
                            softcap=cfg.logit_softcap)


def _count(params) -> int:
    return int(sum(p.size for p in jax.tree_util.tree_leaves(params)))


def build_model(cfg: ArchConfig) -> Model:
    fam = cfg.family

    if fam in ("dense", "moe", "mla_moe"):
        def loss(params, batch):
            h, aux = tr.lm_hidden(params, batch["inputs"], cfg)
            return (_xent_loss(cfg, h, tr.lm_head_of(params), batch)
                    + cfg.router_aux_coef * aux)

        def hidden(params, batch):
            return tr.lm_hidden(params, batch["inputs"], cfg)

        def prefill(params, batch, max_len):
            return tr.lm_prefill(params, batch["inputs"], cfg, max_len)

        return Model(cfg=cfg, init=lambda key: tr.lm_init(key, cfg),
                     loss=loss, hidden=hidden, prefill=prefill,
                     decode=lambda p, c, t, pos, row_mask=None,
                     commit_len=None: tr.lm_decode(
                         p, c, t, cfg, pos, row_mask=row_mask,
                         commit_len=commit_len),
                     cache_init=lambda p, b, n, per_row=False:
                         tr.lm_cache_init(p, cfg, b, n, per_row=per_row),
                     param_count=_count,
                     score=(None if cfg.kv_lora > 0 else
                            lambda p, c, t, pos, row_mask=None:
                            tr.lm_score(p, c, t, cfg, pos,
                                        row_mask=row_mask)),
                     commit=(None if cfg.kv_lora > 0 else
                             lambda c, resid, commit_len, row_mask=None:
                             tr.lm_commit(c, resid, cfg, commit_len,
                                          row_mask=row_mask)))

    if fam in ("ssm", "hybrid"):
        def loss(params, batch):
            h, aux = hy.hybrid_hidden(params, batch["inputs"], cfg)
            head = params.get("lm_head", params["embed"]["table"].T)
            return _xent_loss(cfg, h, head, batch)

        def hidden(params, batch):
            return hy.hybrid_hidden(params, batch["inputs"], cfg)

        def prefill(params, batch, max_len):
            return hy.hybrid_prefill(params, batch["inputs"], cfg, max_len)

        return Model(cfg=cfg, init=lambda key: hy.hybrid_init(key, cfg),
                     loss=loss, hidden=hidden, prefill=prefill,
                     decode=lambda p, c, t, pos, row_mask=None,
                     commit_len=None: hy.hybrid_decode(
                         p, c, t, cfg, pos, row_mask=row_mask,
                         commit_len=commit_len),
                     cache_init=lambda p, b, n, per_row=False:
                         hy.hybrid_cache_init(p, cfg, b, n,
                                              per_row=per_row),
                     param_count=_count)

    if fam == "encdec":
        def loss(params, batch):
            h, aux = ed.encdec_hidden(params, batch["src"], batch["inputs"],
                                      cfg)
            return _xent_loss(cfg, h, params["lm_head"], batch)

        def hidden(params, batch):
            return ed.encdec_hidden(params, batch["src"], batch["inputs"], cfg)

        def prefill(params, batch, max_len):
            return ed.encdec_prefill(params, batch["src"], batch["inputs"],
                                     cfg, max_len)

        def cache_init(p, b, n):
            return ed.encdec_cache_init(p, cfg, b, n, enc_len=n)

        return Model(cfg=cfg, init=lambda key: ed.encdec_init(key, cfg),
                     loss=loss, hidden=hidden, prefill=prefill,
                     decode=lambda p, c, t, pos: ed.encdec_decode(p, c, t, cfg, pos),
                     cache_init=cache_init, param_count=_count)

    if fam == "encoder":
        from . import encoder as enc

        def loss(params, batch):
            h, aux = enc.encoder_hidden(params, batch["inputs"], cfg)
            return _xent_loss(cfg, h, params["lm_head"], batch,
                              scope="mlm_head")

        def hidden(params, batch):
            return enc.encoder_hidden(params, batch["inputs"], cfg)

        def no_serve(*a, **k):
            raise NotImplementedError("encoder-only models have no decode step")

        return Model(cfg=cfg, init=lambda key: enc.encoder_init(key, cfg),
                     loss=loss, hidden=hidden, prefill=no_serve,
                     decode=no_serve, cache_init=no_serve, param_count=_count)

    if fam == "vlm":
        def loss(params, batch):
            h, aux = vl.vlm_hidden(params, batch["patches"], batch["inputs"],
                                   cfg)
            return _xent_loss(cfg, h, tr.lm_head_of(params), batch)

        def hidden(params, batch):
            return vl.vlm_hidden(params, batch["patches"], batch["inputs"],
                                 cfg)

        def prefill(params, batch, max_len):
            return vl.vlm_prefill(params, batch["patches"], batch["inputs"],
                                  cfg, max_len)

        return Model(cfg=cfg, init=lambda key: vl.vlm_init(key, cfg),
                     loss=loss, hidden=hidden, prefill=prefill,
                     decode=lambda p, c, t, pos: vl.vlm_decode(p, c, t, cfg, pos),
                     cache_init=lambda p, b, n: vl.vlm_cache_init(p, cfg, b, n),
                     param_count=_count)

    raise ValueError(f"unknown family: {fam}")


# ---------------------------------------------------------------------------
# Speculative decoding: the tied first-k-layers draft model.
# ---------------------------------------------------------------------------

def draft_config(cfg: ArchConfig, draft_layers: int = 0) -> ArchConfig:
    """The draft model's config: the target truncated to its first
    ``draft_layers`` blocks (embedding, final norm and LM head shared) —
    the standard early-exit draft for draft-then-verify decoding.
    ``draft_layers`` defaults to ``cfg.draft_layers``; equal to
    ``cfg.n_layers`` it is the tied full model (acceptance -> 1, the
    machinery-proving configuration)."""
    k = draft_layers or cfg.draft_layers
    if not 1 <= k <= cfg.n_layers:
        raise ValueError(f"draft_layers must be in [1, {cfg.n_layers}], "
                         f"got {k}")
    if cfg.family not in ("dense", "moe") or cfg.first_dense_layers:
        raise NotImplementedError(
            "first-k-layers draft supports dense/moe decoders without "
            f"first_dense_layers (family={cfg.family})")
    return cfg.replace(name=f"{cfg.name}-draft{k}", n_layers=k)


def draft_params(params, cfg: ArchConfig, draft_layers: int = 0):
    """Slice the target's stacked layer params to the draft's first-k view.

    Zero-copy under jit (a static slice of the stacked (L, ...) leaves);
    everything else (embed / final_norm / lm_head) is shared by reference —
    the draft is TIED to the target, there are no extra weights to train
    or checkpoint."""
    k = draft_layers or cfg.draft_layers
    dcfg = draft_config(cfg, k)            # validates k and the family
    del dcfg
    out = {n: p for n, p in params.items() if n != "layers"}
    out["layers"] = jax.tree_util.tree_map(lambda a: a[:k],
                                           params["layers"])
    return out


def synthetic_batch(cfg: ArchConfig, batch: int, seq: int, key=None,
                    text_seq: Optional[int] = None) -> dict[str, Any]:
    """Deterministic synthetic batch with the family's input signature."""
    key = jax.random.PRNGKey(0) if key is None else key
    k1, k2, k3 = jax.random.split(key, 3)
    n = text_seq if text_seq is not None else seq
    if cfg.family == "vlm":
        n = max(seq - cfg.num_prefix_tokens, 8)
    toks = jax.random.randint(k1, (batch, n + 1), 0, cfg.vocab, jnp.int32)
    out = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
           "mask": jnp.ones((batch, n), jnp.float32)}
    if cfg.family == "encdec":
        out["src"] = jax.random.normal(k2, (batch, seq, cfg.frontend_dim),
                                       jnp.float32)
    if cfg.family == "vlm":
        out["patches"] = jax.random.normal(
            k3, (batch, cfg.num_prefix_tokens, cfg.frontend_dim), jnp.float32)
    return out
