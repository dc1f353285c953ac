"""Pallas TPU backward kernels for Linear Log-Normal attention.

The forward computes ``out_i = num_i / den_i`` with ``num_i = Phi(q_i) S_i``,
``den_i = Phi(q_i) . z_i + EPS`` over prefix (causal) or full-sequence
(bidir) summaries ``S_i = sum_j Phi(k_j) v_j^T``, ``z_i = sum_j Phi(k_j)``.
The quotient rule is applied analytically from the saved normalizer instead
of via ``jax.vjp``: with the cotangent ``g_i`` and the saved forward output,

    u_i = g_i / den_i                    (value-space cotangent, Dv)
    w_i = (g_i . out_i) / den_i          (normalizer cotangent, scalar)

the three input gradients factor through the same linear-attention summaries
as the forward (cf. the normalizer-aware decomposition in "The Devil in
Linear Transformer", Qin et al. 2022):

    dPhi(q)_i = sum_{j<=i} (u_i . v_j - w_i) Phi(k)_j = S_i u_i - w_i z_i
    dPhi(k)_j = sum_{i>=j} (u_i . v_j - w_i) Phi(q)_i = dS_j v_j - dz_j
    dv_j      = sum_{i>=j} (Phi(q)_i . Phi(k)_j) u_i  = dS_j^T Phi(k)_j

with the *reverse* running state ``dS_j = sum_{i>=j} Phi(q)_i u_i^T`` and
``dz_j = sum_{i>=j} w_i Phi(q)_i`` (the mirror of the forward scan, cf. the
chunked backward of "Log-Linear Attention", Guo et al. 2025).  Since the
feature map is exp(.), ``d qs = Phi(q) * dPhi(q)`` elementwise.

Kernel structure:

* ``lln_causal_bwd_pallas`` — two kernels.  dQ runs a forward-order scan
  re-building the running ``(S, z)`` prefix state in VMEM scratch (same
  recurrence as the forward); dK/dV runs a reverse-order scan with the
  gradient state ``(dS, dz)`` in VMEM scratch.
* GQA (r = H // G > 1): the dK/dV grid is (BG, num_blocks, r) with the
  query-head repeat innermost — dk/dv output blocks are revisited
  consecutively and accumulated in place (a segment-sum over the ``h // r``
  index map), so repeated K/V is never materialized; the reverse state
  ``dS``/``dz`` is kept per repeated head in an (r, D, Dv) scratch.
* ``lln_bidir_bwd_pallas`` — reduce/apply structure mirroring the forward:
  dQ applies the saved forward summaries ``(S, z)``; a reduce pass
  accumulates the full-sequence ``(dS, dz)`` per KV head; an apply pass
  produces dK/dV.
* ``lln_diag_fused_bwd_pallas`` — backward of the §4.2 hybrid.  Shares the
  q/k/v block loads between the LLN gradient and the block-diagonal-softmax
  gradient exactly like the forward fusion; the softmax probabilities are
  recomputed in-kernel (they are block-local), which also reconstructs the
  LLN component of the saved averaged output as ``2*out - diag_out`` so the
  forward only stores the LLN normalizer.

All gradients are emitted in fp32 (ops.py applies the alpha/beta chain rule
and casts back to the model dtypes).

Each kernel has a chunked ``lax.scan`` twin (``*_bwd_scan``) implementing
the identical recurrences in plain jnp.  ops.py dispatches to the scan twin
when the kernels would run in interpret mode (the CPU container): interpret
mode pays a full block copy per grid step, so it is a correctness tool, not
a perf path — while the scan twin keeps the structural wins (saved
residuals instead of forward recompute, no ``jax.checkpoint`` remat, GQA
segment-sum instead of repeated KV) and measurably beats the legacy
``jax.vjp``-through-the-reference fallback on CPU too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _contract(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _load_uw(g_ref, o_ref, den_ref):
    """Cotangents u = g/den (blk, Dv) and w = (g.o)/den (blk,)."""
    gg = g_ref[0].astype(jnp.float32)
    oo = o_ref[0].astype(jnp.float32)
    den = den_ref[0][0]                                  # (blk,)
    return gg / den[:, None], jnp.sum(gg * oo, axis=-1) / den


def _tril(blk):
    row = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    return row >= col


# ---------------------------------------------------------------------------
# Causal LLN backward.
# ---------------------------------------------------------------------------

def _causal_dq_kernel(qs_ref, ks_ref, v_ref, g_ref, o_ref, den_ref,
                      dqs_ref, s_acc, z_acc, *, blk):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        z_acc[...] = jnp.zeros_like(z_acc)

    fq = jnp.exp(qs_ref[0].astype(jnp.float32))          # (blk, d)
    fk = jnp.exp(ks_ref[0].astype(jnp.float32))          # (blk, d)
    vv = v_ref[0].astype(jnp.float32)                    # (blk, dv)
    u, w = _load_uw(g_ref, o_ref, den_ref)

    mask = _tril(blk).astype(jnp.float32)
    # G_ij = (u_i . v_j - w_i) for j <= i within the block.
    gmat = (_contract(u, vv, ((1,), (1,))) - w[:, None]) * mask
    # intra (j <= i, same block) + inter (all earlier blocks via S, z).
    dfq = _contract(gmat, fk, ((1,), (0,)))
    dfq += _contract(u, s_acc[...], ((1,), (1,)))
    dfq -= w[:, None] * z_acc[...]
    dqs_ref[0] = fq * dfq

    s_acc[...] += _contract(fk, vv, ((0,), (0,)))
    z_acc[...] += jnp.sum(fk, axis=0, keepdims=True)


def _causal_dkv_kernel(qs_ref, ks_ref, v_ref, g_ref, o_ref, den_ref,
                       dks_ref, dv_ref, ds_acc, dz_acc, *, blk, r):
    j = pl.program_id(1)
    rr = pl.program_id(2)

    # New reverse scan for this repeated query head starts at the last block.
    @pl.when(j == 0)
    def _init_state():
        ds_acc[pl.ds(rr, 1)] = jnp.zeros((1,) + ds_acc.shape[1:], jnp.float32)
        dz_acc[pl.ds(rr, 1)] = jnp.zeros((1,) + dz_acc.shape[1:], jnp.float32)

    # dk/dv output blocks accumulate across the r repeated query heads.
    @pl.when(rr == 0)
    def _init_out():
        dks_ref[...] = jnp.zeros_like(dks_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    fq = jnp.exp(qs_ref[0].astype(jnp.float32))
    fk = jnp.exp(ks_ref[0].astype(jnp.float32))
    vv = v_ref[0].astype(jnp.float32)
    u, w = _load_uw(g_ref, o_ref, den_ref)
    ds = ds_acc[pl.ds(rr, 1)][0]                         # (d, dv), later blks
    dz = dz_acc[pl.ds(rr, 1)][0]                         # (1, d)

    mask = _tril(blk).astype(jnp.float32)
    scores = _contract(fq, fk, ((1,), (1,))) * mask      # (blk_i, blk_j)
    gmat = (_contract(u, vv, ((1,), (1,))) - w[:, None]) * mask

    dv_ref[0] += _contract(scores, u, ((0,), (0,))) \
        + _contract(fk, ds, ((1,), (0,)))
    dfk = _contract(gmat, fq, ((0,), (0,))) \
        + _contract(vv, ds, ((1,), (1,))) - dz
    dks_ref[0] += fk * dfk

    ds_acc[pl.ds(rr, 1)] = (ds + _contract(fq, u, ((0,), (0,))))[None]
    dz_acc[pl.ds(rr, 1)] = (dz + jnp.sum(fq * w[:, None], axis=0,
                                         keepdims=True))[None]


def lln_causal_bwd_pallas(qs, ks, v, g, o, den, *, r: int = 1,
                          blk: int = 256, interpret: bool = False):
    """Backward of the causal LLN kernel.

    qs/g/o: (BH, N, .) query-side tensors, den (BH, 1, N) the forward's
    fp32 normalizer; ks/v: (BG, N, .) with r = H // G.  Returns fp32
    (dqs, dks, dv) in kernel layout, with dks/dv already segment-summed
    over the repeated query heads.
    """
    bh, n, d = qs.shape
    bg = ks.shape[0]
    dv = v.shape[-1]
    nb = n // blk
    dqs = pl.pallas_call(
        functools.partial(_causal_dq_kernel, blk=blk),
        name="lln_causal_dq",
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, blk, d), lambda h, j, r=r: (h // r, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda h, j, r=r: (h // r, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, 1, blk), lambda h, j: (h, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, blk, d), lambda h, j: (h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, dv), jnp.float32),
                        pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(qs, ks, v, g, o, den)

    # Reverse-order scan: grid index j walks blocks last-to-first; the
    # innermost r axis accumulates the GQA segment-sum in the output block.
    dks, dvv = pl.pallas_call(
        functools.partial(_causal_dkv_kernel, blk=blk, r=r),
        name="lln_causal_dkv",
        grid=(bg, nb, r),
        in_specs=[
            pl.BlockSpec((1, blk, d),
                         lambda gi, j, rr, r=r, nb=nb:
                         (gi * r + rr, nb - 1 - j, 0)),
            pl.BlockSpec((1, blk, d),
                         lambda gi, j, rr, nb=nb: (gi, nb - 1 - j, 0)),
            pl.BlockSpec((1, blk, dv),
                         lambda gi, j, rr, nb=nb: (gi, nb - 1 - j, 0)),
            pl.BlockSpec((1, blk, dv),
                         lambda gi, j, rr, r=r, nb=nb:
                         (gi * r + rr, nb - 1 - j, 0)),
            pl.BlockSpec((1, blk, dv),
                         lambda gi, j, rr, r=r, nb=nb:
                         (gi * r + rr, nb - 1 - j, 0)),
            pl.BlockSpec((1, 1, blk),
                         lambda gi, j, rr, r=r, nb=nb:
                         (gi * r + rr, 0, nb - 1 - j)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, d),
                         lambda gi, j, rr, nb=nb: (gi, nb - 1 - j, 0)),
            pl.BlockSpec((1, blk, dv),
                         lambda gi, j, rr, nb=nb: (gi, nb - 1 - j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bg, n, d), jnp.float32),
                   jax.ShapeDtypeStruct((bg, n, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((r, d, dv), jnp.float32),
                        pltpu.VMEM((r, 1, d), jnp.float32)],
        interpret=interpret,
    )(qs, ks, v, g, o, den)
    return dqs, dks, dvv


# ---------------------------------------------------------------------------
# Bidirectional LLN backward: dQ apply + (dS, dz) reduce + dK/dV apply.
# ---------------------------------------------------------------------------

def _bidir_dq_kernel(qs_ref, g_ref, o_ref, den_ref, s_ref, z_ref, dqs_ref):
    fq = jnp.exp(qs_ref[0].astype(jnp.float32))
    u, w = _load_uw(g_ref, o_ref, den_ref)
    dfq = _contract(u, s_ref[0], ((1,), (1,))) - w[:, None] * z_ref[0]
    dqs_ref[0] = fq * dfq


def _bidir_reduce_kernel(qs_ref, g_ref, o_ref, den_ref, ds_ref, dz_ref):
    first = (pl.program_id(1) == 0) & (pl.program_id(2) == 0)

    @pl.when(first)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dz_ref[...] = jnp.zeros_like(dz_ref)

    fq = jnp.exp(qs_ref[0].astype(jnp.float32))
    u, w = _load_uw(g_ref, o_ref, den_ref)
    ds_ref[0] += _contract(fq, u, ((0,), (0,)))
    dz_ref[0] += jnp.sum(fq * w[:, None], axis=0, keepdims=True)


def _bidir_dkv_kernel(ks_ref, v_ref, ds_ref, dz_ref, dks_ref, dv_ref):
    fk = jnp.exp(ks_ref[0].astype(jnp.float32))
    vv = v_ref[0].astype(jnp.float32)
    ds = ds_ref[0]
    dv_ref[0] = _contract(fk, ds, ((1,), (0,)))
    dks_ref[0] = fk * (_contract(vv, ds, ((1,), (1,))) - dz_ref[0])


def lln_bidir_bwd_pallas(qs, ks, v, g, o, den, s, z, *, r: int = 1,
                         blk: int = 256, interpret: bool = False):
    """Backward of the bidirectional LLN kernel.

    s/z are the forward's reduced summaries (BG, D, DV)/(BG, 1, D), saved as
    residuals.  Returns fp32 (dqs, dks, dv) in kernel layout.
    """
    bh, n, d = qs.shape
    bg = ks.shape[0]
    dv = v.shape[-1]
    nb = n // blk
    dqs = pl.pallas_call(
        _bidir_dq_kernel,
        name="lln_bidir_dq",
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, 1, blk), lambda h, j: (h, 0, j)),
            pl.BlockSpec((1, d, dv), lambda h, j, r=r: (h // r, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda h, j, r=r: (h // r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, d), lambda h, j: (h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, d), jnp.float32),
        interpret=interpret,
    )(qs, g, o, den, s, z)

    # Full-sequence gradient summaries, segment-summed over repeated heads:
    # for a fixed KV head every (rr, j) iteration lands on the same output
    # block, so the accumulation stays in VMEM until the head changes.
    dsg, dzg = pl.pallas_call(
        _bidir_reduce_kernel,
        name="lln_bidir_reduce",
        grid=(bg, r, nb),
        in_specs=[
            pl.BlockSpec((1, blk, d),
                         lambda gi, rr, j, r=r: (gi * r + rr, j, 0)),
            pl.BlockSpec((1, blk, dv),
                         lambda gi, rr, j, r=r: (gi * r + rr, j, 0)),
            pl.BlockSpec((1, blk, dv),
                         lambda gi, rr, j, r=r: (gi * r + rr, j, 0)),
            pl.BlockSpec((1, 1, blk),
                         lambda gi, rr, j, r=r: (gi * r + rr, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, d, dv), lambda gi, rr, j: (gi, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda gi, rr, j: (gi, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bg, d, dv), jnp.float32),
                   jax.ShapeDtypeStruct((bg, 1, d), jnp.float32)],
        interpret=interpret,
    )(qs, g, o, den)

    dks, dvv = pl.pallas_call(
        _bidir_dkv_kernel,
        name="lln_bidir_dkv",
        grid=(bg, nb),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda gi, j: (gi, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda gi, j: (gi, j, 0)),
            pl.BlockSpec((1, d, dv), lambda gi, j: (gi, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda gi, j: (gi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, d), lambda gi, j: (gi, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda gi, j: (gi, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bg, n, d), jnp.float32),
                   jax.ShapeDtypeStruct((bg, n, dv), jnp.float32)],
        interpret=interpret,
    )(ks, v, dsg, dzg)
    return dqs, dks, dvv


# ---------------------------------------------------------------------------
# Fused LLN + block-diagonal softmax backward (§4.2 hybrid).
# ---------------------------------------------------------------------------

def _diag_recompute(q_ref, k_ref, vv, *, blk, scale, causal):
    """Block softmax probabilities p and diag output (shared-load recompute)."""
    qq = q_ref[0].astype(jnp.float32) * scale
    kk = k_ref[0].astype(jnp.float32)
    s = _contract(qq, kk, ((1,), (1,)))
    if causal:
        s = jnp.where(_tril(blk), s, NEG_INF)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return qq, kk, p, jnp.dot(p, vv, preferred_element_type=jnp.float32)


def _fused_uw(g_ref, o_ref, den_ref, diag_out):
    """LLN cotangents for the averaged output: the LLN component is
    reconstructed as 2*out - diag_out, and the 0.5 averaging weight is
    folded into u/w via g/2."""
    gh = 0.5 * g_ref[0].astype(jnp.float32)
    den = den_ref[0][0]                                  # (blk,)
    lln_out = 2.0 * o_ref[0].astype(jnp.float32) - diag_out
    u = gh / den[:, None]
    w = jnp.sum(gh * lln_out, axis=-1) / den
    return gh, u, w


def _dsoftmax(p, dp):
    return p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))


def _fused_dq_kernel(qs_ref, ks_ref, q_ref, k_ref, v_ref, g_ref, o_ref,
                     den_ref, dqs_ref, dqd_ref, s_acc, z_acc, *, blk, scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        z_acc[...] = jnp.zeros_like(z_acc)

    fq = jnp.exp(qs_ref[0].astype(jnp.float32))
    fk = jnp.exp(ks_ref[0].astype(jnp.float32))
    vv = v_ref[0].astype(jnp.float32)
    qq, kk, p, diag_out = _diag_recompute(q_ref, k_ref, vv, blk=blk,
                                          scale=scale, causal=True)
    gh, u, w = _fused_uw(g_ref, o_ref, den_ref, diag_out)

    mask = _tril(blk).astype(jnp.float32)
    gmat = (_contract(u, vv, ((1,), (1,))) - w[:, None]) * mask
    dfq = _contract(gmat, fk, ((1,), (0,)))
    dfq += _contract(u, s_acc[...], ((1,), (1,)))
    dfq -= w[:, None] * z_acc[...]
    dqs_ref[0] = fq * dfq

    dp = _contract(gh, vv, ((1,), (1,)))
    dqd_ref[0] = _contract(_dsoftmax(p, dp), kk, ((1,), (0,))) * scale

    s_acc[...] += _contract(fk, vv, ((0,), (0,)))
    z_acc[...] += jnp.sum(fk, axis=0, keepdims=True)


def _fused_dkv_kernel(qs_ref, ks_ref, q_ref, k_ref, v_ref, g_ref, o_ref,
                      den_ref, dks_ref, dkd_ref, dv_ref, ds_acc, dz_acc,
                      *, blk, scale, r):
    j = pl.program_id(1)
    rr = pl.program_id(2)

    @pl.when(j == 0)
    def _init_state():
        ds_acc[pl.ds(rr, 1)] = jnp.zeros((1,) + ds_acc.shape[1:], jnp.float32)
        dz_acc[pl.ds(rr, 1)] = jnp.zeros((1,) + dz_acc.shape[1:], jnp.float32)

    @pl.when(rr == 0)
    def _init_out():
        dks_ref[...] = jnp.zeros_like(dks_ref)
        dkd_ref[...] = jnp.zeros_like(dkd_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    fq = jnp.exp(qs_ref[0].astype(jnp.float32))
    fk = jnp.exp(ks_ref[0].astype(jnp.float32))
    vv = v_ref[0].astype(jnp.float32)
    qq, _, p, diag_out = _diag_recompute(q_ref, k_ref, vv, blk=blk,
                                         scale=scale, causal=True)
    gh, u, w = _fused_uw(g_ref, o_ref, den_ref, diag_out)
    ds = ds_acc[pl.ds(rr, 1)][0]
    dz = dz_acc[pl.ds(rr, 1)][0]

    mask = _tril(blk).astype(jnp.float32)
    scores = _contract(fq, fk, ((1,), (1,))) * mask
    gmat = (_contract(u, vv, ((1,), (1,))) - w[:, None]) * mask

    dp = _contract(gh, vv, ((1,), (1,)))
    dsm = _dsoftmax(p, dp)
    dv_ref[0] += _contract(scores, u, ((0,), (0,))) \
        + _contract(fk, ds, ((1,), (0,))) \
        + _contract(p, gh, ((0,), (0,)))
    dfk = _contract(gmat, fq, ((0,), (0,))) \
        + _contract(vv, ds, ((1,), (1,))) - dz
    dks_ref[0] += fk * dfk
    dkd_ref[0] += _contract(dsm, qq, ((0,), (0,)))

    ds_acc[pl.ds(rr, 1)] = (ds + _contract(fq, u, ((0,), (0,))))[None]
    dz_acc[pl.ds(rr, 1)] = (dz + jnp.sum(fq * w[:, None], axis=0,
                                         keepdims=True))[None]


def lln_diag_fused_bwd_pallas(qs, ks, q, k, v, g, o, den, *, r: int = 1,
                              blk: int = 256, scale: float | None = None,
                              interpret: bool = False):
    """Backward of the fused causal LLN + block-diag softmax kernel.

    Returns fp32 (dqs, dq_diag, dks, dk_diag, dv): dqs/dks feed the LLN
    alpha/beta chain rule, dq_diag/dk_diag are the raw-q/k softmax grads,
    dv carries both components.  dks/dk_diag/dv are segment-summed over the
    r repeated query heads.
    """
    bh, n, d = qs.shape
    bg = ks.shape[0]
    dvd = v.shape[-1]
    nb = n // blk
    scale = (d ** -0.5) if scale is None else scale

    def q_spec(shape):
        return pl.BlockSpec(shape, lambda h, j: (h, j, 0))

    def kv_spec(shape):
        return pl.BlockSpec(shape, lambda h, j, r=r: (h // r, j, 0))

    dqs, dqd = pl.pallas_call(
        functools.partial(_fused_dq_kernel, blk=blk, scale=scale),
        name="lln_diag_fused_dq",
        grid=(bh, nb),
        in_specs=[
            q_spec((1, blk, d)),
            kv_spec((1, blk, d)),
            q_spec((1, blk, d)),
            kv_spec((1, blk, d)),
            kv_spec((1, blk, dvd)),
            q_spec((1, blk, dvd)),
            q_spec((1, blk, dvd)),
            pl.BlockSpec((1, 1, blk), lambda h, j: (h, 0, j)),
        ],
        out_specs=[q_spec((1, blk, d)), q_spec((1, blk, d))],
        out_shape=[jax.ShapeDtypeStruct((bh, n, d), jnp.float32),
                   jax.ShapeDtypeStruct((bh, n, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, dvd), jnp.float32),
                        pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(qs, ks, q, k, v, g, o, den)

    def qr_spec(shape):
        return pl.BlockSpec(shape,
                            lambda gi, j, rr, r=r, nb=nb:
                            (gi * r + rr, nb - 1 - j, 0))

    def kvr_spec(shape):
        return pl.BlockSpec(shape,
                            lambda gi, j, rr, nb=nb: (gi, nb - 1 - j, 0))

    dks, dkd, dvv = pl.pallas_call(
        functools.partial(_fused_dkv_kernel, blk=blk, scale=scale, r=r),
        name="lln_diag_fused_dkv",
        grid=(bg, nb, r),
        in_specs=[
            qr_spec((1, blk, d)),
            kvr_spec((1, blk, d)),
            qr_spec((1, blk, d)),
            kvr_spec((1, blk, d)),
            kvr_spec((1, blk, dvd)),
            qr_spec((1, blk, dvd)),
            qr_spec((1, blk, dvd)),
            pl.BlockSpec((1, 1, blk),
                         lambda gi, j, rr, r=r, nb=nb:
                         (gi * r + rr, 0, nb - 1 - j)),
        ],
        out_specs=[kvr_spec((1, blk, d)), kvr_spec((1, blk, d)),
                   kvr_spec((1, blk, dvd))],
        out_shape=[jax.ShapeDtypeStruct((bg, n, d), jnp.float32),
                   jax.ShapeDtypeStruct((bg, n, d), jnp.float32),
                   jax.ShapeDtypeStruct((bg, n, dvd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((r, d, dvd), jnp.float32),
                        pltpu.VMEM((r, 1, d), jnp.float32)],
        interpret=interpret,
    )(qs, ks, q, k, v, g, o, den)
    return dqs, dqd, dks, dkd, dvv


# ---------------------------------------------------------------------------
# Chunked lax.scan twins (interpret-mode / CPU dispatch; identical math).
# ---------------------------------------------------------------------------

def _uw_full(g, o, den):
    den = den[:, 0]                                      # (BH, 1, N) -> (BH, N)
    gf = g.astype(jnp.float32)
    u = gf / den[..., None]
    w = jnp.sum(gf * o.astype(jnp.float32), axis=-1) / den
    return u, w


def _chunked_q(t, bg, r, nc, blk):
    """(BG*r, N, D) -> (nc, BG, r, blk, D) chunk-major for lax.scan."""
    d = t.shape[-1]
    return t.reshape(bg, r, nc, blk, d).transpose(2, 0, 1, 3, 4)


def _chunked_kv(t, nc, blk):
    """(BG, N, D) -> (nc, BG, blk, D)."""
    bg, _, d = t.shape
    return t.reshape(bg, nc, blk, d).transpose(1, 0, 2, 3)


def _unchunk_q(t, bh):
    nc, bg, r, blk, d = t.shape
    return t.transpose(1, 2, 0, 3, 4).reshape(bh, nc * blk, d)


def _unchunk_kv(t):
    nc, bg, blk, d = t.shape
    return t.transpose(1, 0, 2, 3).reshape(bg, nc * blk, d)


def lln_causal_bwd_scan(qs, ks, v, g, o, den, *, r: int = 1,
                        blk: int = 256):
    """jnp twin of :func:`lln_causal_bwd_pallas` (same residuals, same
    two-pass scan structure, chunk-parallel over heads)."""
    bh, n, d = qs.shape
    bg = ks.shape[0]
    dv = v.shape[-1]
    nc = n // blk
    fq = _chunked_q(jnp.exp(qs.astype(jnp.float32)), bg, r, nc, blk)
    fk = _chunked_kv(jnp.exp(ks.astype(jnp.float32)), nc, blk)
    vf = _chunked_kv(v.astype(jnp.float32), nc, blk)
    u, w = _uw_full(g, o, den)
    u = _chunked_q(u, bg, r, nc, blk)
    w = _chunked_q(w[..., None], bg, r, nc, blk)[..., 0]
    mask = jnp.tril(jnp.ones((blk, blk), jnp.float32))

    def dq_step(carry, xs):
        s, z = carry                                 # (BG,D,Dv), (BG,D)
        fq_c, fk_c, v_c, u_c, w_c = xs
        gmat = (jnp.einsum("brie,bje->brij", u_c, v_c)
                - w_c[..., None]) * mask
        dfq = jnp.einsum("brij,bjd->brid", gmat, fk_c)
        dfq += jnp.einsum("brie,bde->brid", u_c, s)
        dfq -= w_c[..., None] * z[:, None, None, :]
        s = s + jnp.einsum("bjd,bje->bde", fk_c, v_c)
        z = z + jnp.sum(fk_c, axis=1)
        return (s, z), fq_c * dfq

    s0 = jnp.zeros((bg, d, dv), jnp.float32)
    z0 = jnp.zeros((bg, d), jnp.float32)
    _, dqs = jax.lax.scan(dq_step, (s0, z0), (fq, fk, vf, u, w))

    def dkv_step(carry, xs):
        ds, dz = carry                               # (BG,D,Dv), (BG,D)
        fq_c, fk_c, v_c, u_c, w_c = xs
        scores = jnp.einsum("brid,bjd->brij", fq_c, fk_c) * mask
        gmat = (jnp.einsum("brie,bje->brij", u_c, v_c)
                - w_c[..., None]) * mask
        dv_c = jnp.einsum("brij,brie->bje", scores, u_c)
        dv_c += jnp.einsum("bjd,bde->bje", fk_c, ds)
        dfk = jnp.einsum("brij,brid->bjd", gmat, fq_c)
        dfk += jnp.einsum("bje,bde->bjd", v_c, ds) - dz[:, None, :]
        ds = ds + jnp.einsum("brid,brie->bde", fq_c, u_c)
        dz = dz + jnp.sum(fq_c * w_c[..., None], axis=(1, 2))
        return (ds, dz), (fk_c * dfk, dv_c)

    _, (dks, dvv) = jax.lax.scan(dkv_step, (s0, z0), (fq, fk, vf, u, w),
                                 reverse=True)
    return _unchunk_q(dqs, bh), _unchunk_kv(dks), _unchunk_kv(dvv)


def lln_bidir_bwd_scan(qs, ks, v, g, o, den, s, z, *, r: int = 1,
                       blk: int = 256):
    """jnp twin of :func:`lln_bidir_bwd_pallas` (full-sequence einsums)."""
    bh, n, d = qs.shape
    bg = ks.shape[0]
    fq = jnp.exp(qs.astype(jnp.float32)).reshape(bg, r, n, d)
    fk = jnp.exp(ks.astype(jnp.float32))
    vf = v.astype(jnp.float32)
    u, w = _uw_full(g, o, den)
    u = u.reshape(bg, r, n, -1)
    w = w.reshape(bg, r, n)
    dfq = jnp.einsum("brne,bde->brnd", u, s) \
        - w[..., None] * z[:, 0][:, None, None, :]
    dqs = (fq * dfq).reshape(bh, n, d)
    ds = jnp.einsum("brnd,brne->bde", fq, u)
    dz = jnp.sum(fq * w[..., None], axis=(1, 2))
    dvv = jnp.einsum("bnd,bde->bne", fk, ds)
    dks = fk * (jnp.einsum("bne,bde->bnd", vf, ds) - dz[:, None, :])
    return dqs, dks, dvv


def block_diag_bwd_scan(q, k, v, g, *, r: int = 1, blk: int = 256,
                        causal: bool = False, scale: float | None = None):
    """jnp twin of :func:`block_diag.block_diag_bwd_pallas`."""
    bh, n, d = q.shape
    bg = k.shape[0]
    dv = v.shape[-1]
    nb = n // blk
    scale = (d ** -0.5) if scale is None else scale
    qq = q.astype(jnp.float32).reshape(bg, r, nb, blk, d) * scale
    kk = k.astype(jnp.float32).reshape(bg, nb, blk, d)
    vf = v.astype(jnp.float32).reshape(bg, nb, blk, dv)
    gf = g.astype(jnp.float32).reshape(bg, r, nb, blk, dv)
    s = jnp.einsum("brcid,bcjd->brcij", qq, kk)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((blk, blk), jnp.bool_)), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    dp = jnp.einsum("brcie,bcje->brcij", gf, vf)
    dsm = _dsoftmax(p, dp)
    dq = jnp.einsum("brcij,bcjd->brcid", dsm, kk) * scale
    dk = jnp.einsum("brcij,brcid->bcjd", dsm, qq)
    dvv = jnp.einsum("brcij,brcie->bcje", p, gf)
    return (dq.reshape(bh, n, d), dk.reshape(bg, n, d),
            dvv.reshape(bg, n, dv))


def lln_diag_fused_bwd_scan(qs, ks, q, k, v, g, o, den, *, r: int = 1,
                            blk: int = 256, scale: float | None = None):
    """jnp twin of :func:`lln_diag_fused_bwd_pallas`: LLN scan backward on
    g/2 plus the block-softmax backward, with the LLN output reconstructed
    as 2*o - diag_out exactly like the kernel."""
    bg = ks.shape[0]
    dv = v.shape[-1]
    n = qs.shape[1]
    nb = n // blk
    scale = (qs.shape[-1] ** -0.5) if scale is None else scale
    qq = q.astype(jnp.float32).reshape(bg, r, nb, blk, -1) * scale
    kk = k.astype(jnp.float32).reshape(bg, nb, blk, -1)
    vf = v.astype(jnp.float32).reshape(bg, nb, blk, dv)
    s = jnp.einsum("brcid,bcjd->brcij", qq, kk)
    s = jnp.where(jnp.tril(jnp.ones((blk, blk), jnp.bool_)), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    diag_out = jnp.einsum("brcij,bcje->brcie", p, vf).reshape(*g.shape)
    gh = 0.5 * g.astype(jnp.float32)
    lln_out = 2.0 * o.astype(jnp.float32) - diag_out
    dqs, dks, dv_lln = lln_causal_bwd_scan(qs, ks, v, gh, lln_out, den,
                                           r=r, blk=blk)
    # Diag softmax backward reusing the probabilities computed above (the
    # kernel shares the same recompute between components).
    ghb = gh.reshape(bg, r, nb, blk, dv)
    dp = jnp.einsum("brcie,bcje->brcij", ghb, vf)
    dsm = _dsoftmax(p, dp)
    dqd = (jnp.einsum("brcij,bcjd->brcid", dsm, kk) * scale
           ).reshape(qs.shape[0], n, -1)
    dkd = jnp.einsum("brcij,brcid->bcjd", dsm, qq).reshape(bg, n, -1)
    dv_diag = jnp.einsum("brcij,brcie->bcje", p, ghb).reshape(bg, n, dv)
    return dqs, dqd, dks, dkd, dv_lln + dv_diag
