"""Pallas TPU kernel for log-linear (Fenwick multi-scale) LLN attention.

One pass over the sequence in ``granule``-sized blocks (grid minor
dimension is sequential on TPU) maintaining the full bucket pyramid in
VMEM scratch: level ``l`` holds the LLN ``(S, z)`` summary of a dyadic
span of ``2^l`` closed granules.  Queries in block ``j`` read the
pyramid-of-``j`` aggregate (per-level static weights ``decay**l``) plus
a causal intra-block term at weight 1 — exactly the sequential decode
semantics of ``core/loglinear.py``.

Because ops.py pre-stabilizes ``ks = beta*k - c_k`` with ONE global
per-(batch,head) constant, every bucket shares the same reference: the
Fenwick carry-merge degenerates to pure adds and merged-out levels are
simply zeroed, so unoccupied levels contribute nothing to the aggregate
and no per-bucket max/exp bookkeeping is needed in-kernel.  The carry
path at block ``j`` is the binary increment ``j -> j+1``: the carry
propagates through level ``l`` iff bits ``0..l`` of ``j`` are all set,
and the top level saturates (pure add).

GQA without materializing repeated KV: query row ``bh`` reads kv row
``bh // r`` via BlockSpec index maps, same idiom as lln_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-6


def _loglin_causal_kernel(qs_ref, ks_ref, v_ref, o_ref, *rest, blk,
                          num_scales, weights, with_state):
    # rest = (*state outputs if with_state, sl_scr, zl_scr)
    sl_out = rest[0] if with_state else None
    zl_out = rest[1] if with_state else None
    sl_scr, zl_scr = rest[-2:]
    ls = num_scales
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sl_scr[...] = jnp.zeros_like(sl_scr)
        zl_scr[...] = jnp.zeros_like(zl_scr)

    fq = jnp.exp(qs_ref[0].astype(jnp.float32))          # (blk, d)
    fk = jnp.exp(ks_ref[0].astype(jnp.float32))          # (blk, d)
    vv = v_ref[0].astype(jnp.float32)                    # (blk, dv)

    row = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    causal = (row >= col).astype(jnp.float32)

    scores = jax.lax.dot_general(fq, fk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * causal
    intra = jnp.dot(scores, vv, preferred_element_type=jnp.float32)
    intra_z = jnp.sum(scores, axis=-1)

    # Pyramid-of-j aggregate.  Merged-out / never-filled levels hold
    # zeros, so the static per-level weight is all that is needed.
    s_eff = weights[0] * sl_scr[0]
    z_eff = weights[0] * zl_scr[0]
    for l in range(1, ls):
        s_eff = s_eff + weights[l] * sl_scr[l]
        z_eff = z_eff + weights[l] * zl_scr[l]
    inter = jnp.dot(fq, s_eff, preferred_element_type=jnp.float32)
    inter_z = jnp.dot(fq, z_eff.reshape(-1, 1),
                      preferred_element_type=jnp.float32)[:, 0]

    den = intra_z + inter_z + EPS
    o_ref[0] = ((intra + inter) / den[:, None]).astype(o_ref.dtype)

    # Fenwick carry-merge of the (now closed) block j: binary increment
    # j -> j+1 with pure adds (shared reference).
    g_s = jax.lax.dot_general(fk, vv, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    g_z = jnp.sum(fk, axis=0, keepdims=True)
    carry_s, carry_z = g_s, g_z
    for l in range(ls - 1):
        reach = (j & ((1 << l) - 1)) == ((1 << l) - 1)   # carry reaches l
        bit = ((j >> l) & 1) == 1
        mrg = jnp.logical_and(reach, bit)
        take = jnp.logical_and(reach, jnp.logical_not(bit))
        old_s = sl_scr[l]
        old_z = zl_scr[l]
        sl_scr[l] = jnp.where(take, carry_s,
                              jnp.where(mrg, jnp.zeros_like(old_s), old_s))
        zl_scr[l] = jnp.where(take, carry_z,
                              jnp.where(mrg, jnp.zeros_like(old_z), old_z))
        carry_s = jnp.where(mrg, carry_s + old_s, carry_s)
        carry_z = jnp.where(mrg, carry_z + old_z, carry_z)
    top = ls - 1
    if top > 0:
        reach_top = (j & ((1 << top) - 1)) == ((1 << top) - 1)
        sl_scr[top] += jnp.where(reach_top, carry_s, jnp.zeros_like(carry_s))
        zl_scr[top] += jnp.where(reach_top, carry_z, jnp.zeros_like(carry_z))
    else:
        sl_scr[0] += carry_s
        zl_scr[0] += carry_z

    if with_state:
        # The (h, 0, 0, 0)-mapped output blocks are revisited every j;
        # the value committed after the last grid step is the final carry.
        sl_out[0] = sl_scr[...]
        zl_out[0] = zl_scr[...]


def loglin_causal_pallas(qs: jnp.ndarray, ks: jnp.ndarray, v: jnp.ndarray, *,
                         num_scales: int, scale_decay: float, r: int = 1,
                         blk: int = 256, interpret: bool = False,
                         return_state: bool = False):
    """qs: (BH, N, D) pre-scaled alpha*q - c_q; ks/v: (BG, N, D[v])
    pre-scaled beta*k - c_k with a single global reference; N % blk == 0
    and ``blk`` is the bucket granule.

    With ``return_state`` also emits the final bucket pyramid
    ``sl`` (BH, L, D, DV) and ``zl`` (BH, L, 1, D) fp32 — all levels at
    the shared global reference (ops broadcasts ``c_k`` into ``cl``).
    """
    bh, n, d = qs.shape
    dv = v.shape[-1]
    nb = n // blk
    ls = num_scales
    weights = tuple(float(scale_decay) ** l for l in range(ls))
    grid = (bh, nb)
    out_specs = [pl.BlockSpec((1, blk, dv), lambda h, j: (h, j, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, n, dv), v.dtype)]
    if return_state:
        out_specs.append(
            pl.BlockSpec((1, ls, d, dv), lambda h, j: (h, 0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, ls, d, dv), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, ls, 1, d), lambda h, j: (h, 0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, ls, 1, d), jnp.float32))
    res = pl.pallas_call(
        functools.partial(_loglin_causal_kernel, blk=blk,
                          num_scales=ls, weights=weights,
                          with_state=return_state),
        name="loglin_causal",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, blk, d), lambda h, j, r=r: (h // r, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda h, j, r=r: (h // r, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((ls, d, dv), jnp.float32),
                        pltpu.VMEM((ls, 1, d), jnp.float32)],
        interpret=interpret,
    )(qs, ks, v)
    return tuple(res) if return_state else res[0]
