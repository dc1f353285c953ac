"""Pure-jnp oracles for every Pallas kernel in this package.

Kernel-native layout: q/k: (BH, N, D) (already alpha/beta-scaled and
stabilized for the LLN kernels), v: (BH, N, DV).  GQA is expressed by
``r = H // G``: k/v carry (B*G, N, D) and query row ``bh`` reads kv row
``bh // r``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6
NEG_INF = -1e30


def _expand_kv(t: jnp.ndarray, r: int) -> jnp.ndarray:
    return t if r == 1 else jnp.repeat(t, r, axis=0)


def lln_bidir_ref(qs: jnp.ndarray, ks: jnp.ndarray, v: jnp.ndarray,
                  r: int = 1) -> jnp.ndarray:
    """Bidirectional LLN: out_i = e^{qs_i} S / (e^{qs_i} . z)."""
    fq = jnp.exp(qs.astype(jnp.float32))
    fk = jnp.exp(ks.astype(jnp.float32))
    vf = v.astype(jnp.float32)
    s = jnp.einsum("gnd,gnv->gdv", fk, vf)
    z = jnp.sum(fk, axis=1)
    s = _expand_kv(s, r)
    z = _expand_kv(z, r)
    num = jnp.einsum("hnd,hdv->hnv", fq, s)
    den = jnp.einsum("hnd,hd->hn", fq, z)
    return (num / (den[..., None] + EPS)).astype(v.dtype)


def lln_causal_ref(qs: jnp.ndarray, ks: jnp.ndarray, v: jnp.ndarray,
                   r: int = 1) -> jnp.ndarray:
    """Causal LLN, quadratic-form oracle: P = tril(e^{qs} e^{ks}^T) row-norm."""
    fq = jnp.exp(qs.astype(jnp.float32))
    fk = jnp.exp(_expand_kv(ks, r).astype(jnp.float32))
    vf = _expand_kv(v, r).astype(jnp.float32)
    n = qs.shape[1]
    scores = jnp.einsum("hid,hjd->hij", fq, fk)
    scores = scores * jnp.tril(jnp.ones((n, n), jnp.float32))
    out = jnp.einsum("hij,hjv->hiv", scores, vf)
    den = jnp.sum(scores, axis=-1)
    return (out / (den[..., None] + EPS)).astype(v.dtype)


def block_diag_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   block: int, causal: bool, r: int = 1,
                   scale: float | None = None) -> jnp.ndarray:
    """Block-diagonal softmax attention oracle (N divisible by block)."""
    k = _expand_kv(k, r)
    v = _expand_kv(v, r)
    bh, n, d = q.shape
    dv = v.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    nb = n // block
    qb = q.reshape(bh, nb, block, d).astype(jnp.float32) * scale
    kb = k.reshape(bh, nb, block, d).astype(jnp.float32)
    vb = v.reshape(bh, nb, block, dv).astype(jnp.float32)
    s = jnp.einsum("hgid,hgjd->hgij", qb, kb)
    if causal:
        tri = jnp.tril(jnp.ones((block, block), jnp.bool_))
        s = jnp.where(tri[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hgij,hgjv->hgiv", p, vb)
    return out.reshape(bh, n, dv).astype(v.dtype)


def lln_prefill_state_ref(qs: jnp.ndarray, ks: jnp.ndarray, v: jnp.ndarray,
                          r: int = 1):
    """Oracle for the state-emitting causal kernel: (out, s, z) with the
    final running state s = sum_j Phi(k_j) v_j^T (BH, D, DV) and
    z = sum_j Phi(k_j) (BH, 1, D), per query-head row (GQA rows repeat the
    group state, matching the H-head decode cache)."""
    out = lln_causal_ref(qs, ks, v, r)
    fk = jnp.exp(_expand_kv(ks, r).astype(jnp.float32))
    vf = _expand_kv(v, r).astype(jnp.float32)
    s = jnp.einsum("hnd,hnv->hdv", fk, vf)
    z = jnp.sum(fk, axis=1, keepdims=True)
    return out, s, z


def _segsum_kv(t: jnp.ndarray, r: int) -> jnp.ndarray:
    """Sum a per-query-head gradient over the r heads sharing each KV row."""
    if r == 1:
        return t
    bh = t.shape[0]
    return t.reshape(bh // r, r, *t.shape[1:]).sum(axis=1)


def lln_bwd_ref(qs: jnp.ndarray, ks: jnp.ndarray, v: jnp.ndarray,
                g: jnp.ndarray, o: jnp.ndarray, den: jnp.ndarray,
                causal: bool, r: int = 1):
    """Analytic LLN backward oracle (quadratic form), kernel layout.

    ``den`` is the forward's fp32 normalizer in kernel layout (BH, 1, N).
    Mirrors the normalizer-aware decomposition used by the Pallas backward:
    u = g/den, w = (g.o)/den, G_ij = (u_i.v_j - w_i) * mask, then
    dqs = fq * (G @ fk), dks = fk * (G^T @ fq), dv = scores^T @ u, with
    dks/dv segment-summed over the r repeated query heads.
    """
    fq = jnp.exp(qs.astype(jnp.float32))
    fk = jnp.exp(_expand_kv(ks, r).astype(jnp.float32))
    vf = _expand_kv(v, r).astype(jnp.float32)
    gf = g.astype(jnp.float32)
    of = o.astype(jnp.float32)
    den = den[:, 0]
    u = gf / den[..., None]
    w = jnp.sum(gf * of, axis=-1) / den
    mask = jnp.tril(jnp.ones((qs.shape[1], qs.shape[1]), jnp.float32)) \
        if causal else jnp.ones((qs.shape[1], qs.shape[1]), jnp.float32)
    scores = jnp.einsum("hid,hjd->hij", fq, fk) * mask
    gmat = (jnp.einsum("hiv,hjv->hij", u, vf) - w[..., None]) * mask
    dqs = fq * jnp.einsum("hij,hjd->hid", gmat, fk)
    dks = fk * jnp.einsum("hij,hid->hjd", gmat, fq)
    dv = jnp.einsum("hij,hiv->hjv", scores, u)
    return dqs, _segsum_kv(dks, r), _segsum_kv(dv, r)


def block_diag_bwd_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       g: jnp.ndarray, *, block: int, causal: bool,
                       r: int = 1, scale: float | None = None):
    """Block-diagonal softmax backward oracle via jax.vjp (kernel layout)."""
    kf = _expand_kv(k, r)
    vf = _expand_kv(v, r)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: block_diag_ref(
            q_.astype(jnp.float32), k_.astype(jnp.float32),
            v_.astype(jnp.float32), block=block, causal=causal, r=1,
            scale=scale), q, kf, vf)
    dq, dk, dv = vjp(g.astype(jnp.float32))
    return dq, _segsum_kv(dk, r), _segsum_kv(dv, r)


def lln_diag_fused_bwd_ref(qs, ks, q, k, v, g, o, den, *, block: int,
                           r: int = 1, scale: float | None = None):
    """Backward oracle for the fused causal LLN + diag kernel.

    The LLN cotangent w needs the LLN component of the averaged output,
    reconstructed exactly like the kernel does: 2*o - diag_out.
    """
    diag_out = block_diag_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), block=block,
                              causal=True, r=r, scale=scale)
    lln_out = 2.0 * o.astype(jnp.float32) - diag_out
    gh = 0.5 * g.astype(jnp.float32)
    dqs, dks, dv_lln = lln_bwd_ref(qs, ks, v, gh, lln_out, den,
                                   causal=True, r=r)
    dqd, dkd, dv_diag = block_diag_bwd_ref(q, k, v, gh, block=block,
                                           causal=True, r=r, scale=scale)
    return dqs, dqd, dks, dkd, dv_lln + dv_diag


def lln_diag_fused_ref(qs: jnp.ndarray, ks: jnp.ndarray, q: jnp.ndarray,
                       k: jnp.ndarray, v: jnp.ndarray, *, block: int,
                       causal: bool, r: int = 1,
                       scale: float | None = None) -> jnp.ndarray:
    """Oracle for the fused LLN+Diag kernel: 0.5*(LLN + block-diag softmax).

    qs/ks are the stabilized LLN-scaled tensors; q/k the raw ones for the
    softmax diagonal.
    """
    lln = (lln_causal_ref(qs, ks, v, r) if causal
           else lln_bidir_ref(qs, ks, v, r))
    diag = block_diag_ref(q, k, v, block=block, causal=causal, r=r,
                          scale=scale)
    return (0.5 * (lln.astype(jnp.float32) + diag.astype(jnp.float32))
            ).astype(v.dtype)
