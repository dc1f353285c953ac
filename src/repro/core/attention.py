"""Unified multi-head attention front-end.

One entry point — :func:`multi_head_attention` — dispatching on
``impl in {"softmax", "lln", "lln_diag"}``:

* ``softmax``  — arch-faithful baseline; flash-style (online-softmax, chunked
  over keys) so 32k-token prefill never materializes an N x N matrix.
* ``lln``      — the paper's Linear Log-Normal attention (eq. 8) with
  moment-matched (alpha, beta) (eq. 10), causal or bidirectional.
* ``lln_diag`` — the paper's §4.2 hybrid: average of LLN and block-diagonal
  softmax attention.

GQA/MQA: k/v may carry fewer heads (G) than q (H); G must divide H.
All inputs are (batch, seq, heads, head_dim).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp

from . import lln as lln_mod
from .numerics import einsum_f32
from .diag import block_diag_attn
from .lln import LLNState, lln_bidir, lln_causal
from .moment_matching import (constants_for_dim, length_gain,
                              solve_alpha_beta)

NEG_INF = -1e30

#: Logical axes of the arrays attention hands a kernel, by name, for
#: ``distributed/sharding.py:per_device``: q/k/v/out (B, N, H|G, D) and the
#: calibrations alpha/beta, heads last ((H|G,) or per-row (B, H|G)).  Query
#: and kv heads share the name "heads", so they split together or not at all.
ATTN_AXES = {
    "q": ("act_batch", None, "heads", None),
    "k": ("act_batch", None, "heads", None),
    "v": ("act_batch", None, "heads", None),
    "out": ("act_batch", None, "heads", None),
    "alpha": ("act_batch", "heads"),
    "beta": ("act_batch", "heads"),
}


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    impl: str = "softmax"          # softmax | lln | lln_diag | log_linear
    causal: bool = True
    diag_block: int = 256          # block size of the §4.2 diagonal component
    lln_chunk: int = 128           # chunk of the causal LLN scan (also the
                                   # log_linear bucket granule)
    softmax_chunk: int = 1024      # key-chunk of the flash softmax path
    use_kernel: bool = False       # route through Pallas kernels (kernels/ops)
    backend: Optional[str] = None  # explicit kernel backend (kernels/registry
                                   # auto|pallas|scan|ref); None -> "auto"
    # Moment-matching constants; None -> calibrated defaults for head_dim.
    mm_a: Optional[float] = None
    mm_b: Optional[float] = None
    # Fixed alpha=beta (paper §A.8.4 ablation); 0 = dynamic moment matching.
    fixed_ab: float = 0.0
    # log_linear only: Fenwick pyramid depth and per-level mix decay
    # (core/loglinear.py; num_scales=1 or scale_decay=1 reduce to lln).
    num_scales: int = 4
    scale_decay: float = 0.5


def _repeat_kv(t: jnp.ndarray, h: int) -> jnp.ndarray:
    """Expand (B, N, G, D) kv heads to H = G*R query heads."""
    g = t.shape[2]
    if g == h:
        return t
    return jnp.repeat(t, h // g, axis=2)


def batch_alpha_beta(q: jnp.ndarray, k: jnp.ndarray, cfg: AttnConfig,
                     per_row: bool = False, n: int | None = None
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Moment-matched (alpha, beta) from current-batch statistics.

    Mirrors the artifact: sigma_q/sigma_k are measured on the fly
    (stop-gradient) and eq. 10 is applied — this is what makes alpha/beta
    drift during training as in the paper's Fig. 9.

    GQA: statistics are pooled per kv *group* (the r query heads sharing one
    kv head), so alpha: (H,) and beta: (G,) stay consistent within a group.

    ``per_row=True`` measures each batch row ALONE (statistics over that
    row's sequence and feature dims only) and returns alpha: (B, H) and
    beta: (B, G).  This is the continuous-batching admission setting: a
    batched slot prefill then yields exactly the calibration each request
    would get prefilled solo, so grouped admission stays per-request exact
    even under dynamic moment matching.  ``cfg`` may be any object with
    ``fixed_ab`` / ``mm_a`` / ``mm_b`` attributes (``AttnConfig`` or
    ``kernels.registry.AttnSpec``).

    ``n`` (optional, static int) is the sequence length the calibration is
    for.  When the config carries a beta(n) schedule (``beta_n > 0``,
    ``AttnSpec`` from a config with ``lln_beta_n`` set) the (a, b)
    constants come from the length-aware grid (``constants_for_dim(d, n)``
    — the legacy fit at or below the calibration length, the nearest-N
    fit beyond it); with the schedule off (the default) ``n`` is ignored
    and the result is bit-identical to the legacy calibration.  The
    beta(n) *gain* itself is a use-time modifier applied by the engine
    (prefill at the prompt length, decode per row from ``state.pos``),
    never baked into the calibration this returns.
    """
    bsz, h, g = q.shape[0], q.shape[2], k.shape[2]
    length_aware = getattr(cfg, "beta_n", 0.0) > 0.0 and n is not None
    if cfg.fixed_ab:
        if per_row:
            return (jnp.full((bsz, h), cfg.fixed_ab, jnp.float32),
                    jnp.full((bsz, g), cfg.fixed_ab, jnp.float32))
        return (jnp.full((h,), cfg.fixed_ab, jnp.float32),
                jnp.full((g,), cfg.fixed_ab, jnp.float32))
    a, b = (cfg.mm_a, cfg.mm_b)
    if a is None or b is None:
        a, b = constants_for_dim(q.shape[-1], n=n if length_aware else None)
    r = h // g
    axes = (1, 3) if per_row else (0, 1, 3)   # row-local vs batch-pooled
    sq = jnp.sqrt(jnp.mean(jnp.square(q.astype(jnp.float32)), axis=axes))
    sq_g = jnp.mean(sq.reshape(sq.shape[:-1] + (g, r)), axis=-1)    # (..,G)
    sk_g = jnp.sqrt(jnp.mean(jnp.square(k.astype(jnp.float32)),
                             axis=axes))                            # (..,G)
    alpha_g, beta_g = solve_alpha_beta(sq_g, sk_g, a, b)
    # Per-query-head alpha re-solved against the group's sigma_tilde so each
    # q head is correctly normalized by its own sigma_q (eq. 10).
    sigma_sm_sq = jnp.square(sq_g) * jnp.square(sk_g)
    st = jnp.sqrt(jnp.maximum((sigma_sm_sq - b) / a, 1e-4))         # (..,G)
    alpha = jnp.repeat(st, r, axis=-1) / (jnp.sqrt(2.0)
                                          * jnp.maximum(sq, 1e-4))
    del alpha_g
    return alpha, beta_g


# ---------------------------------------------------------------------------
# Flash-style softmax attention (chunked over keys, online softmax).
# ---------------------------------------------------------------------------

def flash_softmax(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    chunk: int = 1024,
    mask: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    prefix_len: int = 0,
    q_start: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Flash-style (online-softmax) attention, chunked over keys.

    q: (B,Nq,H,D); k/v: (B,Nk,G,D[v]) — G kv heads with G | H (GQA/MQA;
    KV is repeated to H inside).  ``mask``: (B, Nk) key validity.
    Returns (B, Nq, H, Dv) in ``v.dtype``; accumulation is fp32.

    Online-softmax accumulation over key chunks; O(Nq * chunk) live scores.
    Assumes query i attends keys j <= i + (Nk - Nq) when causal (i.e. the
    queries are the *last* Nq positions — the decode/prefill convention).
    ``q_start`` overrides that convention with explicit absolute query
    positions ``q_start + i`` — the multi-token decode case, where queries
    sit mid-buffer in a max_len-sized cache.  It may be a traced scalar or,
    for continuous batching, a per-row ``(B,)`` vector (each batch row sits
    at its own depth in the cache).
    ``prefix_len``: prefix-LM — keys < prefix_len are visible to every query
    (PaliGemma-style bidirectional image prefix).
    """
    from repro.distributed.sharding import constrain

    b, nq, h, d = q.shape
    nk, g = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    # Flat heads throughout: a (G, R) head split would leave both factors
    # un-shardable by the model axis for GQA archs (e.g. 4 x 8 vs 16), which
    # makes the SPMD partitioner replicate heads and gather batch instead.
    # Repeating KV costs (N * H * D) bf16 transient; sharded it is tiny.
    if g != h:
        k = jnp.repeat(k, h // g, axis=2)
        v = jnp.repeat(v, h // g, axis=2)

    nkc = -(-nk // chunk)
    kpad = nkc * chunk - nk
    if mask is None:
        mask = jnp.ones((b, nk), jnp.bool_)
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, kpad)))

    qchunk = min(chunk, nq)
    nqc = -(-nq // qchunk)
    qpad = nqc * qchunk - nq
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0)))

    # Arrays stay in their input dtype (bf16 in models) — only the online-
    # softmax statistics and accumulators are fp32 (preferred_element_type
    # on the two matmuls).  Upcasting k/v here would materialize fp32
    # copies of the whole cache.  The stacked scan operands are explicitly
    # constrained (no-op outside a mesh) so the partitioner keeps batch on
    # the data axis and heads on the model axis.
    qg = (q.reshape(b, nqc, qchunk, h, d).transpose(1, 0, 2, 3, 4)
          * jnp.asarray(scale, q.dtype))                     # (nqc,B,Cq,H,D)
    kc = k.reshape(b, nkc, chunk, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nkc, chunk, h, dv).transpose(1, 0, 2, 3, 4)
    qg = constrain(qg, None, "act_batch", None, "heads", None)
    kc = constrain(kc, None, "act_batch", None, "heads", None)
    vc = constrain(vc, None, "act_batch", None, "heads", None)
    mc = mask.reshape(b, nkc, chunk).transpose(1, 0, 2)
    key_pos_all = jnp.arange(nkc * chunk).reshape(nkc, chunk)

    q_off = (nk - nq) if q_start is None else q_start
    per_row = q_start is not None and jnp.ndim(q_start) == 1

    def q_block(carry, xs):
        qq, qbase = xs                           # (B,Cq,H,D), scalar
        if per_row:                              # (B, Cq) absolute positions
            q_pos = (qbase + jnp.arange(qchunk))[None, :] + q_off[:, None]
        else:
            q_pos = qbase + jnp.arange(qchunk) + q_off

        def kv_step(inner, ys):
            m, l, acc = inner                    # (B,H,Cq), ..., (...,Dv)
            ck, cv, cm, key_pos = ys
            s = einsum_f32("bqhd,bjhd->bhqj", qq, ck)
            bias = jnp.where(cm[:, None, None, :], 0.0, NEG_INF)
            if causal and per_row:
                allowed = q_pos[:, :, None] >= key_pos[None, None, :]
                if prefix_len:
                    allowed = allowed | (key_pos[None, None, :] < prefix_len)
                bias = bias + jnp.where(allowed[:, None], 0.0, NEG_INF)
            elif causal:
                allowed = q_pos[:, None] >= key_pos[None, :]
                if prefix_len:
                    allowed = allowed | (key_pos[None, :] < prefix_len)
                bias = bias + jnp.where(allowed[None, None], 0.0, NEG_INF)
            s = s + bias
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + einsum_f32(
                "bhqj,bjhv->bhqv", p.astype(v.dtype), cv)
            return (m_new, l, acc), None

        m0 = jnp.full((b, h, qchunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, qchunk), jnp.float32)
        acc0 = jnp.zeros((b, h, qchunk, dv), jnp.float32)
        # remat: the VJP of the scan must recompute each block's p rather
        # than stash (Cq x chunk) probabilities per step (flash backward).
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step),
                                      (m0, l0, acc0),
                                      (kc, vc, mc, key_pos_all))
        out = acc / jnp.maximum(l[..., None], 1e-20)         # (B,H,Cq,Dv)
        return carry, out.astype(v.dtype)

    qbases = jnp.arange(nqc) * qchunk
    _, blocks = jax.lax.scan(q_block, 0, (qg, qbases))       # (nqc,B,H,Cq,Dv)
    out = blocks.transpose(1, 0, 3, 2, 4).reshape(b, nqc * qchunk, h, dv)
    return out[:, :nq].astype(v.dtype)


def naive_softmax(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    causal: bool = True, mask: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None, prefix_len: int = 0,
) -> jnp.ndarray:
    """Quadratic reference (small N / tests only)."""
    b, nq, h, d = q.shape
    nk = k.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("bqhd,bjhd->bhqj", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        s = s + jnp.where(mask[:, None, None, :], 0.0, NEG_INF)
    if causal:
        qp = jnp.arange(nq) + (nk - nq)
        allowed = qp[:, None] >= jnp.arange(nk)[None, :]
        if prefix_len:
            allowed = allowed | (jnp.arange(nk)[None, :] < prefix_len)
        s = s + jnp.where(allowed[None, None], 0.0, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqj,bjhv->bqhv", p, v.astype(jnp.float32)).astype(v.dtype)


# ---------------------------------------------------------------------------
# Unified entry point.
# ---------------------------------------------------------------------------

def multi_head_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: AttnConfig,
    *,
    mask: Optional[jnp.ndarray] = None,
    alpha: Optional[jnp.ndarray] = None,
    beta: Optional[jnp.ndarray] = None,
    prefix_len: int = 0,
) -> jnp.ndarray:
    """Full-sequence attention (training / prefill).  See module docstring."""
    h = q.shape[2]
    if cfg.impl == "softmax":
        return flash_softmax(q, k, v, causal=cfg.causal,
                             chunk=min(cfg.softmax_chunk, k.shape[1]),
                             mask=mask, prefix_len=prefix_len)
    g = k.shape[2]
    if alpha is None or beta is None:
        alpha, beta = batch_alpha_beta(q, k, cfg)
    alpha = jnp.asarray(alpha, jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)
    if alpha.ndim == 0:
        alpha = jnp.broadcast_to(alpha, (h,))
    if beta.ndim == 0:
        beta = jnp.broadcast_to(beta, (g,))
    # Heads live on the LAST axis ((H,) or per-row (B, H)) — pool a
    # per-q-head beta to the kv groups either way.
    if beta.shape[-1] == h and g != h:
        beta = beta.reshape(beta.shape[:-1] + (g, h // g)).mean(axis=-1)

    if cfg.use_kernel:
        # Kernels handle GQA via BlockSpec index maps — no KV repeat; the
        # backend registry owns the pallas/scan/ref dispatch.
        if mask is not None:
            raise ValueError("the attention kernels take no padding mask; "
                             "use the jnp path (use_kernel=False, or "
                             "backend 'ref') for masked attention")
        from repro.kernels import registry as kreg
        spec = kreg.AttnSpec(impl=cfg.impl, causal=cfg.causal, r=h // g,
                             backend=cfg.backend or "auto",
                             lln_chunk=cfg.lln_chunk,
                             diag_block=cfg.diag_block,
                             softmax_chunk=cfg.softmax_chunk,
                             fixed_ab=cfg.fixed_ab,
                             num_scales=cfg.num_scales,
                             scale_decay=cfg.scale_decay)
        from repro.distributed.sharding import per_device
        return per_device(
            lambda **a: {"out": kreg.attention(spec, **a)},
            ATTN_AXES.__getitem__, q=q, k=k, v=v, alpha=alpha,
            beta=beta)["out"]

    kv_k = _repeat_kv(k, h)
    kv_v = _repeat_kv(v, h)
    beta_h = jnp.repeat(beta, h // g, axis=-1) if g != h else beta
    if cfg.impl == "log_linear":
        if not cfg.causal:
            raise ValueError("log_linear attention is causal-only")
        from . import loglinear as _loglin
        out, _ = _loglin.prefill(q, kv_k, kv_v, alpha, beta_h,
                                 granule=cfg.lln_chunk,
                                 num_scales=cfg.num_scales,
                                 scale_decay=cfg.scale_decay)
        return out.astype(v.dtype)
    if cfg.causal:
        lln_out = lln_causal(q, kv_k, kv_v, alpha, beta_h, chunk=cfg.lln_chunk)
    else:
        lln_out = lln_bidir(q, kv_k, kv_v, alpha, beta_h, mask=mask)
    if cfg.impl == "lln":
        return lln_out
    if cfg.impl == "lln_diag":
        diag_out = block_diag_attn(q, kv_k, kv_v, block=cfg.diag_block,
                                   causal=cfg.causal, mask=mask)
        return (0.5 * (lln_out.astype(jnp.float32)
                       + diag_out.astype(jnp.float32))).astype(v.dtype)
    raise ValueError(f"unknown attention impl: {cfg.impl}")


# ---------------------------------------------------------------------------
# Decode-time state: softmax KV cache / LLN running state (+ diag tail).
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Ring-less softmax KV cache: k/v (B, S, G, D[v]) + filled length."""
    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray     # scalar int32

    @staticmethod
    def init(batch: int, max_len: int, g: int, d: int, dv: int,
             dtype=jnp.bfloat16) -> "KVCache":
        return KVCache(k=jnp.zeros((batch, max_len, g, d), dtype),
                       v=jnp.zeros((batch, max_len, g, dv), dtype),
                       length=jnp.zeros((), jnp.int32))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LLNDecodeState:
    """LLN decode state + rolling tail buffer for the diagonal component.

    The diag component of §4.2 only ever needs the current block's history,
    so decode keeps a (B, diag_block, G, D) tail instead of the full cache —
    this is what makes long_500k decode O(d^2 + block) per token.  Under GQA
    the tail carries the G kv heads (cache bytes / r); it is repeated to the
    H query heads only inside the tiny tail-softmax.  H-head tails (the seed
    layout, still produced by MLA and the ``use_serve_kernel=False`` path)
    are accepted too — the head count is read off the buffer shape.
    """
    lln: LLNState
    tail_k: jnp.ndarray     # (B, BLK, G, D)
    tail_v: jnp.ndarray     # (B, BLK, G, Dv)
    pos: jnp.ndarray        # absolute next position: scalar or per-row (B,)

    @staticmethod
    def init(batch: int, heads: int, d: int, dv: int, block: int,
             dtype=jnp.bfloat16,
             kv_heads: Optional[int] = None) -> "LLNDecodeState":
        g = kv_heads or heads
        return LLNDecodeState(
            lln=LLNState.init(batch, heads, d, dv),
            tail_k=jnp.zeros((batch, block, g, d), dtype),
            tail_v=jnp.zeros((batch, block, g, dv), dtype),
            pos=jnp.zeros((), jnp.int32))


def decode_softmax(cache: KVCache, q: jnp.ndarray, k_new: jnp.ndarray,
                   v_new: jnp.ndarray, *, scale: Optional[float] = None,
                   chunk: int = 1024,
                   row_mask: Optional[jnp.ndarray] = None,
                   commit_len: Optional[jnp.ndarray] = None
                   ) -> tuple[jnp.ndarray, KVCache]:
    """Softmax decode of T >= 1 tokens against a KV cache.

    q: (B,T,H,D); k/v_new: (B,T,G,D[v]) — new tokens are appended at
    ``cache.length`` and within-chunk causality comes from explicit
    absolute positions (``q_start``), so T > 1 scores a draft chunk in one
    call.  ``cache.length`` may be a scalar (static batch: all rows at the
    same depth) or a per-row ``(B,)`` vector (continuous batching; the
    append is then a vmapped per-row ``dynamic_update_slice``).
    ``row_mask``: optional (B,) bool — rows where it is False do not write
    the cache and do not advance ``length`` (their outputs are garbage and
    must be discarded by the caller); requires per-row ``length``.
    ``commit_len``: optional per-row (B,) int32 in [0, T] — speculative
    partial commit: all T tokens are scored (intra-chunk causality over
    the full draft), but ``length`` advances only by ``commit_len``.
    Keys past the accepted prefix stay in the buffer above ``length``,
    where they are invisible to scoring and overwritten by the next
    commit before ``length`` can ever reach them; ``commit_len=0`` rows
    restore their buffer bitwise (the masked-row contract).  Requires
    per-row ``length``.  Returns (out (B,T,H,Dv), new cache).
    """
    from repro.distributed.sharding import constrain

    per_row = jnp.ndim(cache.length) == 1
    if commit_len is not None and not per_row:
        raise ValueError("decode_softmax: commit_len requires a per-row "
                         "(B,) cache length")
    if per_row:
        upd = lambda c, u, l: jax.lax.dynamic_update_slice_in_dim(
            c, u, l, axis=0)
        kc = jax.vmap(upd)(cache.k, k_new.astype(cache.k.dtype),
                           cache.length)
        vc = jax.vmap(upd)(cache.v, v_new.astype(cache.v.dtype),
                           cache.length)
    else:
        kc = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k_new.astype(cache.k.dtype), cache.length, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v_new.astype(cache.v.dtype), cache.length, axis=1)
    t = q.shape[1]
    ret_k = ret_v = None
    if commit_len is not None:
        cl = lln_mod.commit_lengths(commit_len, row_mask, t)
        # Scoring sees ALL T draft keys on every row (a verify pass with
        # commit_len=0 is a pure score); only the RETURNED cache rolls
        # back — commit_len=0 rows restore their buffer bitwise.
        keep = (cl > 0)[:, None, None, None]
        ret_k = jnp.where(keep, kc, cache.k)
        ret_v = jnp.where(keep, vc, cache.v)
        new_len = cache.length + cl
        score_len = cache.length + t          # all T drafts visible to score
    elif row_mask is not None:
        keep = row_mask[:, None, None, None]
        kc = jnp.where(keep, kc, cache.k)
        vc = jnp.where(keep, vc, cache.v)
        new_len = cache.length + t * row_mask.astype(jnp.int32)
        score_len = new_len
    else:
        new_len = cache.length + t
        score_len = new_len
    kc = constrain(kc, "act_batch", "act_seq_cache", "kv_heads", None)
    vc = constrain(vc, "act_batch", "act_seq_cache", "kv_heads", None)
    lens = score_len if per_row else jnp.broadcast_to(score_len,
                                                      (q.shape[0],))
    valid = jnp.arange(kc.shape[1])[None, :] < lens[:, None]
    out = flash_softmax(q, kc, vc, causal=True,
                        chunk=min(chunk, kc.shape[1]),
                        mask=valid, scale=scale, q_start=cache.length)
    if ret_k is None:
        ret_k, ret_v = kc, vc
    return out, KVCache(k=ret_k, v=ret_v, length=new_len)


def _roll_tail(state: LLNDecodeState, k_t, v_t, posb, cl, idx):
    """The rolling diag-tail update over a chunk of T tokens: for each
    slot i the last *committed* chunk token writing it is
    j_i = j0 + block*((c-1-j0)//block), j0 = (i-pos)%blk, c the per-row
    committed length ``cl`` (= T for a plain decode).  Returns the new
    ``(tail_k, tail_v)``."""
    block = state.tail_k.shape[1]
    t = k_t.shape[1]
    j0 = jnp.mod(idx[None, :] - posb[:, None], block)             # (B, BLK)
    j_last = jnp.clip(j0 + block * ((cl[:, None] - 1 - j0) // block),
                      0, t - 1)
    wrote = (j0 < cl[:, None])[:, :, None, None]
    gather = j_last[:, :, None, None]
    tail_k = jnp.where(wrote, jnp.take_along_axis(k_t, gather, axis=1
                                                  ).astype(state.tail_k.dtype),
                       state.tail_k)
    tail_v = jnp.where(wrote, jnp.take_along_axis(v_t, gather, axis=1
                                                  ).astype(state.tail_v.dtype),
                       state.tail_v)
    return tail_k, tail_v


def _committed(b, t, row_mask, commit_len):
    """Per-row (B,) count of the chunk's T tokens that fold into the
    state: ``commit_len`` under partial commit, T or 0 by ``row_mask``."""
    if commit_len is not None:
        return lln_mod.commit_lengths(commit_len, row_mask, t)
    if row_mask is not None:
        return t * row_mask.astype(jnp.int32)
    return jnp.full((b,), t, jnp.int32)


def tail_row(state, k_new, v_new, *, row_mask=None, commit_len=None):
    """The diag-tail row a single-token decode writes, for a caller that
    defers the write (:func:`write_tail_rows`).  k/v_new: (B,1,G,D[v]).

    Returns ``{"k", "v"}`` (B, G, D[v]) in the tail dtype, ``slot`` (B,)
    = ``pos % block`` and ``write`` (B,) bool (False where the row
    commits nothing) — exactly what :func:`_roll_tail` writes for T = 1.
    """
    b, block = k_new.shape[0], state.tail_k.shape[1]
    posb = jnp.broadcast_to(jnp.asarray(state.pos, jnp.int32), (b,))
    return {"k": k_new[:, 0].astype(state.tail_k.dtype),
            "v": v_new[:, 0].astype(state.tail_v.dtype),
            "slot": posb % block,
            "write": _committed(b, 1, row_mask, commit_len) > 0}


@jax.named_scope("diag_tail")
def write_tail_rows(tail, rows, slot, write):
    """Write one row per (layer, batch row) into stacked tails, in place.

    tail: (L, B, BLK, G, D); rows: (L, B, G, D); slot, write: (L, B) as
    :func:`tail_row` gives them, stacked over layers.  One scatter; rows
    with ``write`` False are dropped, so their tails stay bitwise equal.
    """
    n_layers, b, block = tail.shape[:3]
    idx = jnp.where(write, slot, block)          # past the end: dropped
    return tail.at[jnp.arange(n_layers)[:, None], jnp.arange(b)[None, :],
                   idx].set(rows.astype(tail.dtype), mode="drop")


def _tail_visibility(posb, t, block):
    """Which tail slots each query of a T-token chunk at per-row positions
    ``posb`` (B,) sees, (B, T, BLK), and each query's block start, (B, T).

    Absolute position of tail slot i: entries from the previous block get
    positions < the current block start and are masked; never-written
    slots get negative positions."""
    idx = jnp.arange(block)
    cur_base = (posb // block) * block                            # (B,)
    abs_idx = cur_base[:, None] + idx[None, :]                    # (B, BLK)
    tail_pos = jnp.where(idx[None, :] < (posb - cur_base)[:, None],
                         abs_idx, abs_idx - block)
    q_pos = posb[:, None] + jnp.arange(t)[None, :]                # (B, T)
    q_base = (q_pos // block) * block                 # block start per query
    m_tail = (tail_pos[:, None, :] >= q_base[:, :, None]) \
        & (tail_pos[:, None, :] >= 0)                             # (B, T, BLK)
    return m_tail, q_base


def _diag_chunk(q, k_new, v_new, tail_k, tail_v, posb):
    """The diag component of a T-token chunk: one masked softmax over
    [tail ∪ chunk] keys with per-token block-diagonal visibility.
    q: (B,T,H,D); k/v_new: (B,T,G,D[v]); tails (B,BLK,G,D[v]); posb (B,).
    Returns (B,T,H,Dv) f32."""
    b, t, h, d = q.shape
    m_tail, q_base = _tail_visibility(posb, t, tail_k.shape[1])
    m_chunk = (jnp.arange(t)[None, None, :] <= jnp.arange(t)[None, :, None]) \
        & (q_base[:, None, :] == q_base[:, :, None])  # (B,T,T): j<=i, same blk
    allowed = jnp.concatenate([m_tail, m_chunk], axis=2)

    keys = jnp.concatenate([tail_k, k_new.astype(tail_k.dtype)], axis=1)
    vals = jnp.concatenate([tail_v, v_new.astype(tail_v.dtype)], axis=1)
    # GQA repeat only here, on the (BLK+T)-key tail-softmax operands.
    kf = _repeat_kv(keys, h).astype(jnp.float32)
    vf = _repeat_kv(vals, h).astype(jnp.float32)
    s = jnp.einsum("bihd,bjhd->bhij", q.astype(jnp.float32), kf) * (d ** -0.5)
    s = jnp.where(allowed[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhij,bjhv->bihv", p, vf)


def _diag_single(q, k_new, v_new, tail_k, tail_v, posb):
    """:func:`_diag_chunk` for one token (T = 1), read from the tails as
    stored.

    The concatenated softmax in two parts: the tail's scores and the
    token's own (always visible) score share one max and one denominator.
    Query heads are grouped by kv head, (B,G,r,D), so the tails are neither
    repeated to H heads, concatenated nor cast: q·k takes the stored
    operands with f32 accumulation (products of bf16 values are exact),
    and p·v keeps the f32 probabilities unrounded (``HIGHEST``).  Only the
    order of the f32 sums differs."""
    b, _, h, d = q.shape
    g = tail_k.shape[2]
    m_tail = _tail_visibility(posb, 1, tail_k.shape[1])[0][:, 0]  # (B, BLK)
    qg = q.reshape(b, g, h // g, d)
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    k_self = k_new[:, 0].astype(tail_k.dtype).astype(f32)         # (B,G,D)
    v_self = v_new[:, 0].astype(tail_v.dtype).astype(f32)
    scale = d ** -0.5
    s_tail = jnp.einsum("bgrd,bjgd->bgrj", qg, tail_k, precision=hi,
                        preferred_element_type=f32) * scale
    s_tail = jnp.where(m_tail[:, None, None, :], s_tail, NEG_INF)
    s_self = jnp.sum(qg.astype(f32) * k_self[:, :, None, :], -1) * scale
    m = jnp.maximum(jnp.max(s_tail, -1), s_self)                  # (B,G,r)
    e_tail = jnp.exp(s_tail - m[..., None])
    e_self = jnp.exp(s_self - m)
    den = jnp.sum(e_tail, -1) + e_self
    pv = jnp.einsum("bgrj,bjgv->bgrv", e_tail / den[..., None], tail_v,
                    precision=hi, preferred_element_type=f32)
    out = pv + (e_self / den)[..., None] * v_self[:, :, None, :]
    return out.reshape(b, 1, h, -1)


def decode_lln_chunk(state: LLNDecodeState, q: jnp.ndarray,
                     k_new: jnp.ndarray, v_new: jnp.ndarray,
                     alpha: jnp.ndarray, beta: jnp.ndarray,
                     *, impl: str = "lln_diag",
                     use_kernel: bool = True,
                     row_mask: Optional[jnp.ndarray] = None,
                     backend: Optional[str] = None,
                     commit_len: Optional[jnp.ndarray] = None,
                     renorm: Optional[float] = None,
                     defer_tail: bool = False
                     ) -> tuple[jnp.ndarray, LLNDecodeState]:
    """LLN(+Diag) decode of T >= 1 tokens.  q: (B,T,H,D); k/v_new: (B,T,G,D[v]).

    The LLN state advance is vectorized over the chunk (one rescale, one
    intra-chunk causal quadratic — kernels/ops.py:lln_decode_chunk when
    ``use_kernel``; the jnp ``core.lln.decode_chunk`` otherwise).  The diag
    component runs one masked softmax over [tail block ∪ chunk keys] with
    per-token block-diagonal visibility derived from absolute positions, so
    a chunk may straddle a diag-block boundary and still match T sequential
    single-token steps exactly.  A single token (T = 1) takes the same
    softmax in two parts, read from the tails as stored (:func:`_diag_single`).

    ``state.pos`` may be a scalar (static batch) or a per-row ``(B,)``
    vector (continuous batching: every row sits at its own absolute
    position; the tail slot rotation and the block-diagonal visibility are
    evaluated per row).  ``alpha``/``beta`` may be (H,)/(B, H) —
    per-row calibration for pooled requests prefillled separately.
    ``row_mask``: optional (B,) bool; rows where it is False advance
    NOTHING — lln state, tails and ``pos`` keep their old values (their
    outputs are garbage and must be discarded).  Requires per-row ``pos``.
    ``backend``: explicit registry backend (``auto``/``pallas`` route
    through ``kernels/ops.py``; ``scan``/``ref`` run the jnp twin below);
    None derives it from the legacy ``use_kernel`` flag.
    ``commit_len``: optional per-row (B,) int32 in [0, T] — speculative
    partial commit: all T positions are scored, but only the accepted
    prefix folds into the LLN state, the diag tail and ``pos``
    (``commit_len=0`` ≡ ``row_mask=False``; ``commit_len=T`` ≡ a plain
    decode).  Requires per-row ``pos``.
    ``renorm``: optional drift-renormalization threshold on the carried
    ``z`` magnitude (``core.lln.decode_chunk``); semantics-preserving,
    applied uniformly by every backend.
    ``defer_tail``: return the old tails unchanged; the caller writes the
    step's row itself (:func:`tail_row`, :func:`write_tail_rows`).  Exact
    for T = 1: the slot the token overwrites holds a previous-block entry
    that the block mask already hides, so nothing here reads it.
    """
    b, t, h, _ = q.shape
    if backend is None:
        backend = "auto" if use_kernel else "ref"
    if backend not in ("scan", "ref"):
        from repro.kernels import ops as kops
        lln_out, lln_state = kops.lln_decode_chunk(state.lln, q, k_new,
                                                   v_new, alpha, beta,
                                                   row_mask=row_mask,
                                                   backend=backend,
                                                   commit_len=commit_len,
                                                   renorm=renorm)
    else:
        beta_h = jnp.asarray(beta, jnp.float32)
        g = k_new.shape[2]
        if beta_h.ndim and beta_h.shape[-1] == g and g != h:
            beta_h = jnp.repeat(beta_h, h // g, axis=-1)
        lln_out, lln_state = lln_mod.decode_chunk(
            state.lln, q, _repeat_kv(k_new, h), _repeat_kv(v_new, h),
            alpha, beta_h, row_mask=row_mask, commit_len=commit_len,
            renorm=renorm)

    # --- rolling tail update, vectorized (``_roll_tail``).
    block = state.tail_k.shape[1]
    gt = state.tail_k.shape[2]          # tail head count (G, or H for seed)
    k_t = _repeat_kv(k_new, gt) if k_new.shape[2] != gt else k_new
    v_t = _repeat_kv(v_new, gt) if v_new.shape[2] != gt else v_new
    pos = state.pos
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))    # (B,)
    cl = _committed(b, t, row_mask, commit_len)
    idx = jnp.arange(block)
    if defer_tail:
        tail_k, tail_v = state.tail_k, state.tail_v
    else:
        with jax.named_scope("diag_tail"):
            tail_k, tail_v = _roll_tail(state, k_t, v_t, posb, cl, idx)
    if commit_len is not None:
        new_pos = posb + cl         # always per-row under partial commit
    elif row_mask is not None:
        new_pos = pos + t * row_mask.astype(jnp.int32)
    else:
        new_pos = pos + t           # scalar pos stays scalar
    new_state = LLNDecodeState(lln=lln_state, tail_k=tail_k, tail_v=tail_v,
                               pos=new_pos)
    if impl == "lln":
        return lln_out, new_state

    # --- diagonal component: a softmax over [tail ∪ chunk] keys.
    if t == 1:
        with jax.named_scope("diag_tail"):
            diag_out = _diag_single(q, k_t, v_t, state.tail_k, state.tail_v,
                                    posb)
    else:
        diag_out = _diag_chunk(q, k_t, v_t, state.tail_k, state.tail_v, posb)
    out = 0.5 * (lln_out.astype(jnp.float32) + diag_out)
    return out.astype(v_new.dtype), new_state


def commit_softmax(cache: KVCache, k_new: jnp.ndarray, v_new: jnp.ndarray,
                   *, commit_len: jnp.ndarray,
                   row_mask: Optional[jnp.ndarray] = None) -> KVCache:
    """Commit half of :func:`decode_softmax` — append the accepted prefix
    of a previously *scored* chunk, no scoring.

    Single-pass speculative verify: a ``commit_len=0`` verify pass scores
    the draft and rolls the cache back bitwise; this re-appends the
    chunk's (k, v) residuals and advances ``length`` by the final
    ``commit_len``, identical to re-running :func:`decode_softmax` with
    it.  Requires per-row ``length``.
    """
    if jnp.ndim(cache.length) != 1:
        raise ValueError("commit_softmax requires a per-row (B,) cache "
                         "length")
    t = k_new.shape[1]
    upd = lambda c, u, l: jax.lax.dynamic_update_slice_in_dim(
        c, u, l, axis=0)
    kc = jax.vmap(upd)(cache.k, k_new.astype(cache.k.dtype), cache.length)
    vc = jax.vmap(upd)(cache.v, v_new.astype(cache.v.dtype), cache.length)
    cl = lln_mod.commit_lengths(commit_len, row_mask, t)
    keep = (cl > 0)[:, None, None, None]
    return KVCache(k=jnp.where(keep, kc, cache.k),
                   v=jnp.where(keep, vc, cache.v),
                   length=cache.length + cl)


def commit_lln_chunk(state: LLNDecodeState, k_new: jnp.ndarray,
                     v_new: jnp.ndarray, beta: jnp.ndarray,
                     *, impl: str = "lln_diag",
                     commit_len: jnp.ndarray,
                     row_mask: Optional[jnp.ndarray] = None,
                     backend: Optional[str] = None,
                     renorm: Optional[float] = None) -> LLNDecodeState:
    """Commit half of :func:`decode_lln_chunk` — fold the accepted prefix
    of a previously scored chunk into the LLN state, the diag tail and
    ``pos``, without scoring.

    k/v_new: (B,T,G,D[v]) — the post-RoPE residuals the verify pass
    returned.  Bit-identical per backend to re-running
    :func:`decode_lln_chunk` with the final ``commit_len`` (the state
    advance of the two paths shares the same per-backend fold).  Requires
    per-row ``pos``.
    """
    b, t = k_new.shape[0], k_new.shape[1]
    if backend is None:
        backend = "auto"
    if backend not in ("scan", "ref"):
        from repro.kernels import ops as kops
        lln_state = kops.lln_commit_chunk(state.lln, k_new, v_new, beta,
                                          row_mask=row_mask,
                                          backend=backend,
                                          commit_len=commit_len,
                                          renorm=renorm)
    else:
        h = state.lln.s.shape[1]
        g = k_new.shape[2]
        beta_h = jnp.asarray(beta, jnp.float32)
        if beta_h.ndim and beta_h.shape[-1] == g and g != h:
            beta_h = jnp.repeat(beta_h, h // g, axis=-1)
        lln_state = lln_mod.commit_chunk(
            state.lln, _repeat_kv(k_new, h), _repeat_kv(v_new, h), beta_h,
            row_mask=row_mask, commit_len=commit_len, renorm=renorm)

    # Rolling diag-tail update — same per-slot last-committed-writer gather
    # as decode_lln_chunk.
    block = state.tail_k.shape[1]
    gt = state.tail_k.shape[2]
    k_t = _repeat_kv(k_new, gt) if k_new.shape[2] != gt else k_new
    v_t = _repeat_kv(v_new, gt) if v_new.shape[2] != gt else v_new
    posb = jnp.broadcast_to(jnp.asarray(state.pos, jnp.int32), (b,))
    cl = lln_mod.commit_lengths(commit_len, row_mask, t)
    with jax.named_scope("diag_tail"):
        tail_k, tail_v = _roll_tail(state, k_t, v_t, posb, cl,
                                    jnp.arange(block))
    return LLNDecodeState(lln=lln_state, tail_k=tail_k, tail_v=tail_v,
                          pos=posb + cl)


def decode_lln(state: LLNDecodeState, q: jnp.ndarray, k_new: jnp.ndarray,
               v_new: jnp.ndarray, alpha: jnp.ndarray, beta: jnp.ndarray,
               *, impl: str = "lln_diag") -> tuple[jnp.ndarray, LLNDecodeState]:
    """One-token LLN(+Diag) decode (T=1 :func:`decode_lln_chunk`).

    .. deprecated:: use :meth:`repro.core.engine.AttentionEngine.decode`
       (or :func:`decode_lln_chunk` directly) — chunked decode subsumes the
       single-token case.
    """
    from repro.kernels.registry import warn_deprecated
    warn_deprecated("repro.core.attention.decode_lln",
                    "AttentionEngine.decode / decode_lln_chunk")
    return decode_lln_chunk(state, q, k_new, v_new, alpha, beta, impl=impl,
                            use_kernel=False)
