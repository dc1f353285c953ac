"""Pallas TPU kernel for the Mamba2 SSD chunked scan (arXiv:2405.21060).

Structurally the same kernel family as the causal LLN scan
(kernels/lln_attention.py): an intra-chunk quadratic form plus a VMEM-
resident state pass — with per-step exponential decay folded in log-space.
One grid step processes one (batch*head, chunk) tile:

    lcum_i   = cumsum(log a)_i                      (within chunk)
    scores   = (C B^T) * exp(lcum_i - lcum_j) * tril
    y        = scores xbar + (C * exp(lcum)) state
    state   <- exp(lcum_last) state + (B * exp(lcum_last - lcum))^T xbar

B/C group sharing (ssm_groups < heads) is expressed with BlockSpec index
maps, like GQA in the attention kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(la_ref, xb_ref, b_ref, c_ref, o_ref, state, *, blk):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    la = la_ref[0].astype(jnp.float32)                   # (1, blk) lane row
    xb = xb_ref[0].astype(jnp.float32)                   # (blk, P)
    bb = b_ref[0].astype(jnp.float32)                    # (blk, S)
    cc = c_ref[0].astype(jnp.float32)                    # (blk, S)

    row = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    tril = row >= col
    # Prefix sum as a masked row reduction (Mosaic has no cumsum), as a
    # (blk, 1) column and its (1, blk) transpose.
    lcum = jnp.sum(jnp.where(tril, la, 0.0), axis=1, keepdims=True)
    dec = jnp.exp(jnp.clip(lcum - lcum.reshape(1, blk), -60.0, 0.0))

    dot = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    scores = dot * dec * tril.astype(jnp.float32)
    y_intra = jnp.dot(scores, xb, preferred_element_type=jnp.float32)

    ein = jnp.exp(jnp.clip(lcum, -60.0, 0.0))
    y_inter = jnp.dot(cc * ein, state[...],
                      preferred_element_type=jnp.float32)
    o_ref[0] = (y_intra + y_inter).astype(o_ref.dtype)

    l_last = lcum[blk - 1:, :]                           # (1, 1)
    carry = jnp.exp(jnp.clip(l_last - lcum, -60.0, 0.0))
    state[...] = state[...] * jnp.exp(jnp.clip(l_last, -60.0, 0.0)) + \
        jax.lax.dot_general(bb * carry, xb, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)


def ssd_pallas(log_a: jnp.ndarray, xbar: jnp.ndarray, b_in: jnp.ndarray,
               c_in: jnp.ndarray, *, r: int = 1, blk: int = 256,
               interpret: bool = False) -> jnp.ndarray:
    """log_a: (BH, 1, N) (a lane row per head, the layout the TPU compiler
    tiles); xbar: (BH, N, P); b_in/c_in: (BG, N, S); N % blk == 0.
    Head bh reads group row bh // r.  Returns y: (BH, N, P)."""
    bh, n, p = xbar.shape
    s = b_in.shape[-1]
    nb = n // blk
    return pl.pallas_call(
        functools.partial(_ssd_kernel, blk=blk),
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, 1, blk), lambda h, j: (h, 0, j)),
            pl.BlockSpec((1, blk, p), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, blk, s), lambda h, j, r=r: (h // r, j, 0)),
            pl.BlockSpec((1, blk, s), lambda h, j, r=r: (h // r, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, p), lambda h, j: (h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, p), xbar.dtype),
        scratch_shapes=[pltpu.VMEM((s, p), jnp.float32)],
        interpret=interpret,
    )(log_a, xbar, b_in, c_in)
