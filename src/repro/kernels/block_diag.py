"""Pallas TPU kernel for block-diagonal softmax attention (paper §4.2).

Each sequence block attends only within itself: scores, softmax and the
weighted sum all live in VMEM — no N x N HBM round-trip.  Grid is
(batch*heads, num_blocks); blocks are MXU-aligned (default 256 x head_dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _block_diag_kernel(q_ref, k_ref, v_ref, o_ref, *, blk, scale, causal):
    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        s = jnp.where(row >= col, s, -1e30)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o_ref[0] = jnp.dot(p, v, preferred_element_type=jnp.float32
                       ).astype(o_ref.dtype)


def _block_diag_bwd_kernel(q_ref, k_ref, v_ref, g_ref, dq_ref, dk_ref,
                           dv_ref, *, blk, scale, causal):
    rr = pl.program_id(2)

    # dk/dv output blocks accumulate the GQA segment-sum over the r
    # repeated query heads (innermost grid axis -> consecutive revisits).
    @pl.when(rr == 0)
    def _init_out():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    qq = q_ref[0].astype(jnp.float32) * scale
    kk = k_ref[0].astype(jnp.float32)
    vv = v_ref[0].astype(jnp.float32)
    gg = g_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(qq, kk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        s = jnp.where(row >= col, s, -1e30)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s)
    p = p / jnp.sum(p, axis=-1, keepdims=True)

    dp = jax.lax.dot_general(gg, vv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dsm = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq_ref[0] = jnp.dot(dsm, kk, preferred_element_type=jnp.float32) * scale
    dk_ref[0] += jax.lax.dot_general(dsm, qq, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dv_ref[0] += jax.lax.dot_general(p, gg, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)


def block_diag_bwd_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          g: jnp.ndarray, *, r: int = 1, blk: int = 256,
                          causal: bool = False, scale: float | None = None,
                          interpret: bool = False):
    """Backward of the block-diagonal softmax kernel.

    Needs no forward residuals: the block probabilities are recomputed
    in-kernel.  Returns fp32 (dq, dk, dv); dk/dv are segment-summed over
    the r = H // G repeated query heads.
    """
    bh, n, d = q.shape
    bg = k.shape[0]
    dv = v.shape[-1]
    nb = n // blk
    scale = (d ** -0.5) if scale is None else scale
    return pl.pallas_call(
        functools.partial(_block_diag_bwd_kernel, blk=blk, scale=scale,
                          causal=causal),
        name="block_diag_bwd",
        grid=(bg, nb, r),
        in_specs=[
            pl.BlockSpec((1, blk, d),
                         lambda gi, j, rr, r=r: (gi * r + rr, j, 0)),
            pl.BlockSpec((1, blk, d), lambda gi, j, rr: (gi, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda gi, j, rr: (gi, j, 0)),
            pl.BlockSpec((1, blk, dv),
                         lambda gi, j, rr, r=r: (gi * r + rr, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, d),
                         lambda gi, j, rr, r=r: (gi * r + rr, j, 0)),
            pl.BlockSpec((1, blk, d), lambda gi, j, rr: (gi, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda gi, j, rr: (gi, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, n, d), jnp.float32),
                   jax.ShapeDtypeStruct((bg, n, d), jnp.float32),
                   jax.ShapeDtypeStruct((bg, n, dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g)


def block_diag_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      r: int = 1, blk: int = 256, causal: bool = False,
                      scale: float | None = None,
                      interpret: bool = False) -> jnp.ndarray:
    """q: (BH, N, D); k/v: (BG, N, D[v]); N % blk == 0."""
    bh, n, d = q.shape
    dv = v.shape[-1]
    nb = n // blk
    scale = (d ** -0.5) if scale is None else scale
    return pl.pallas_call(
        functools.partial(_block_diag_kernel, blk=blk, scale=scale,
                          causal=causal),
        name="block_diag",
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, blk, d), lambda h, j, r=r: (h // r, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda h, j, r=r: (h // r, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, dv), lambda h, j: (h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, dv), v.dtype),
        interpret=interpret,
    )(q, k, v)
