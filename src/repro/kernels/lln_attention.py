"""Pallas TPU kernels for Linear Log-Normal attention (paper eq. 8).

TPU adaptation (vs. the paper's PyTorch einsum implementation):
* the feature map exp(.) is fused into the matmul pipeline — Phi(Q), Phi(K)
  (each N x D in HBM) are never materialized;
* the running state S (D x DV) and normalizer z (1 x D) live in fp32 VMEM
  scratch across sequence blocks (grid minor dimension is sequential on TPU);
* block sizes are MXU-aligned (multiples of 128 on the lane dim; D = head_dim
  is 64/128 for all assigned archs);
* GQA without materializing repeated KV: query row ``bh`` reads kv row
  ``bh // r`` via BlockSpec index maps.

Inputs are pre-scaled and pre-stabilized by ops.py:  qs = alpha*q - c_q,
ks = beta*k - c_k  with per-(batch,head) global constants that cancel exactly
in the normalized form (see core/lln.py docstring).

Training residuals
------------------
Every forward entry point accepts ``return_res=True`` to additionally emit
the per-row normalizer ``den_i = Phi(q_i) . (z_prefix + sum_block Phi(k))``
(fp32, shape (BH, 1, N): the TPU compiler tiles the last two block dims
by (8, 128), so a per-row vector travels as a (1, blk) lane row) — and,
for the bidirectional variant, the reduced ``(S, z)`` summary state.  ops.py saves these (together with the already
pre-scaled ``qs``/``ks``) as custom_vjp residuals so the backward kernels in
``lln_backward.py`` never recompute the stabilization constants or the
forward normalizers: the quotient rule through ``out = num / den`` is applied
analytically from the saved ``den`` and the forward output.  The fused
LLN+diag kernel saves only the LLN ``den`` — its backward reconstructs the
LLN component as ``2*out - diag_out`` from an in-kernel softmax recompute
that it needs anyway for the softmax gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-6


# ---------------------------------------------------------------------------
# Causal LLN: chunked scan with VMEM-resident state.
# ---------------------------------------------------------------------------

def _lln_causal_kernel(qs_ref, ks_ref, v_ref, o_ref, *rest, blk, with_res,
                       with_state):
    # rest = (*extra outputs, s_acc, z_acc): den if with_res, then the final
    # (s, z) state outputs if with_state.
    den_ref = rest[0] if with_res else None
    s_out = rest[int(with_res)] if with_state else None
    z_out = rest[int(with_res) + 1] if with_state else None
    s_acc, z_acc = rest[-2:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        z_acc[...] = jnp.zeros_like(z_acc)

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

    inter = jnp.dot(fq, s_acc[...], preferred_element_type=jnp.float32)
    inter_z = jnp.dot(fq, z_acc[...].reshape(-1, 1),
                      preferred_element_type=jnp.float32)[:, 0]

    den = intra_z + inter_z + EPS
    o_ref[0] = ((intra + inter) / den[:, None]).astype(o_ref.dtype)
    if with_res:
        den_ref[0] = den[None, :]

    s_acc[...] += jax.lax.dot_general(fk, vv, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    z_acc[...] += jnp.sum(fk, axis=0, keepdims=True)
    if with_state:
        # The (h, 0, 0)-mapped output blocks are revisited every j; the
        # value committed after the last grid step is the final carry.
        s_out[0] = s_acc[...]
        z_out[0] = z_acc[...]


def lln_causal_pallas(qs: jnp.ndarray, ks: jnp.ndarray, v: jnp.ndarray, *,
                      r: int = 1, blk: int = 256, interpret: bool = False,
                      return_res: bool = False, return_state: bool = False):
    """qs: (BH, N, D) pre-scaled; ks/v: (BG, N, D[v]); N % blk == 0.

    With ``return_res`` also emits the fp32 normalizer ``den`` (BH, 1, N) used
    by the custom backward (see module docstring).  With ``return_state``
    also emits the final running state ``s`` (BH, D, DV) and ``z`` (BH, 1, D)
    — the O(d^2) decode state, produced by the same pass that computes the
    prefill outputs (serving path; see ops.lln_prefill).
    """
    bh, n, d = qs.shape
    dv = v.shape[-1]
    nb = n // blk
    grid = (bh, nb)
    out_specs = [pl.BlockSpec((1, blk, dv), lambda h, j: (h, j, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, n, dv), v.dtype)]
    if return_res:
        out_specs.append(pl.BlockSpec((1, 1, blk), lambda h, j: (h, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, n), jnp.float32))
    if return_state:
        out_specs.append(pl.BlockSpec((1, d, dv), lambda h, j: (h, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, d, dv), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, d), lambda h, j: (h, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, d), jnp.float32))
    res = pl.pallas_call(
        functools.partial(_lln_causal_kernel, blk=blk, with_res=return_res,
                          with_state=return_state),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, blk, d), lambda h, j, r=r: (h // r, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda h, j, r=r: (h // r, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d, dv), jnp.float32),
                        pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(qs, ks, v)
    return tuple(res) if (return_res or return_state) else res[0]


# ---------------------------------------------------------------------------
# Bidirectional LLN: reduce pass (S, z) + apply pass.
# ---------------------------------------------------------------------------

def _lln_reduce_kernel(ks_ref, v_ref, s_ref, z_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    fk = jnp.exp(ks_ref[0].astype(jnp.float32))
    vv = v_ref[0].astype(jnp.float32)
    s_ref[0] += jax.lax.dot_general(fk, vv, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    z_ref[0] += jnp.sum(fk, axis=0, keepdims=True)


def _lln_apply_kernel(qs_ref, s_ref, z_ref, o_ref, *rest, with_res):
    fq = jnp.exp(qs_ref[0].astype(jnp.float32))
    num = jnp.dot(fq, s_ref[0], preferred_element_type=jnp.float32)
    den = jnp.dot(fq, z_ref[0].reshape(-1, 1),
                  preferred_element_type=jnp.float32)[:, 0] + EPS
    o_ref[0] = (num / den[:, None]).astype(o_ref.dtype)
    if with_res:
        rest[0][0] = den[None, :]


def lln_bidir_pallas(qs: jnp.ndarray, ks: jnp.ndarray, v: jnp.ndarray, *,
                     r: int = 1, blk: int = 256, interpret: bool = False,
                     return_res: bool = False):
    """qs: (BH, N, D); ks/v: (BG, N, D[v]); N % blk == 0.

    With ``return_res`` returns ``(out, s, z, den)``: the reduced summary
    state (BG, D, DV)/(BG, 1, D) and the fp32 normalizer (BH, 1, N), reused by
    the backward pass.
    """
    bh, n, d = qs.shape
    bg = ks.shape[0]
    dv = v.shape[-1]
    nb = n // blk
    s, z = pl.pallas_call(
        _lln_reduce_kernel,
        grid=(bg, nb),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda g, j: (g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, d, dv), lambda g, j: (g, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda g, j: (g, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bg, d, dv), jnp.float32),
                   jax.ShapeDtypeStruct((bg, 1, d), jnp.float32)],
        interpret=interpret,
    )(ks, v)
    out_specs = [pl.BlockSpec((1, blk, dv), lambda h, j: (h, j, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, n, dv), v.dtype)]
    if return_res:
        out_specs.append(pl.BlockSpec((1, 1, blk), lambda h, j: (h, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, n), jnp.float32))
    res = pl.pallas_call(
        functools.partial(_lln_apply_kernel, with_res=return_res),
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, d, dv), lambda h, j, r=r: (h // r, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda h, j, r=r: (h // r, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(qs, s, z)
    if return_res:
        return res[0], s, z, res[1]
    return res[0]


# ---------------------------------------------------------------------------
# Fused LLN + block-diagonal softmax (the §4.2 hybrid in a single pass).
# Beyond-paper optimization: shares the v (and q/k) block loads between the
# two components and writes the averaged output once.
# ---------------------------------------------------------------------------

def _lln_diag_fused_kernel(qs_ref, ks_ref, q_ref, k_ref, v_ref, o_ref,
                           *rest, blk, scale, causal, with_res):
    den_ref = rest[0] if with_res else None
    s_acc, z_acc = rest[-2:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        z_acc[...] = jnp.zeros_like(z_acc)

    fq = jnp.exp(qs_ref[0].astype(jnp.float32))
    fk = jnp.exp(ks_ref[0].astype(jnp.float32))
    vv = v_ref[0].astype(jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    tril = row >= col

    # --- LLN component (causal chunked or full-block bidir handled by ops) --
    scores = jax.lax.dot_general(fq, fk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    if causal:
        scores = scores * tril.astype(jnp.float32)
    intra = jnp.dot(scores, vv, preferred_element_type=jnp.float32)
    intra_z = jnp.sum(scores, axis=-1)
    inter = jnp.dot(fq, s_acc[...], preferred_element_type=jnp.float32)
    inter_z = jnp.dot(fq, z_acc[...].reshape(-1, 1),
                      preferred_element_type=jnp.float32)[:, 0]
    den = intra_z + inter_z + EPS
    lln_out = (intra + inter) / den[:, None]
    if with_res:
        den_ref[0] = den[None, :]
    s_acc[...] += jax.lax.dot_general(fk, vv, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    z_acc[...] += jnp.sum(fk, axis=0, keepdims=True)

    # --- block-diagonal softmax component ----------------------------------
    qq = q_ref[0].astype(jnp.float32) * scale
    kk = k_ref[0].astype(jnp.float32)
    ds = jax.lax.dot_general(qq, kk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if causal:
        ds = jnp.where(tril, ds, -1e30)
    ds = ds - jnp.max(ds, axis=-1, keepdims=True)
    p = jnp.exp(ds)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    diag_out = jnp.dot(p, vv, preferred_element_type=jnp.float32)

    o_ref[0] = (0.5 * (lln_out + diag_out)).astype(o_ref.dtype)


def lln_diag_fused_pallas(qs, ks, q, k, v, *, r: int = 1, blk: int = 256,
                          causal: bool = True, scale: float | None = None,
                          interpret: bool = False, return_res: bool = False):
    """Fused §4.2 hybrid.  Diag block size == LLN chunk size == blk.

    Causal only: the bidirectional LLN needs the full-sequence state, which
    the single-pass fusion cannot provide (use lln_bidir_pallas + block_diag).
    With ``return_res`` also emits the LLN normalizer ``den`` (BH, 1, N, fp32);
    the diag softmax needs no residual — its backward recomputes the block
    probabilities from the shared q/k loads.
    """
    if not causal:
        raise ValueError("fused lln+diag kernel is causal-only")
    bh, n, d = qs.shape
    dv = v.shape[-1]
    nb = n // blk
    scale = (d ** -0.5) if scale is None else scale
    out_specs = [pl.BlockSpec((1, blk, dv), lambda h, j: (h, j, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, n, dv), v.dtype)]
    if return_res:
        out_specs.append(pl.BlockSpec((1, 1, blk), lambda h, j: (h, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, n), jnp.float32))
    res = pl.pallas_call(
        functools.partial(_lln_diag_fused_kernel, blk=blk, scale=scale,
                          causal=causal, with_res=return_res),
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, blk, d), lambda h, j, r=r: (h // r, j, 0)),
            pl.BlockSpec((1, blk, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, blk, d), lambda h, j, r=r: (h // r, j, 0)),
            pl.BlockSpec((1, blk, dv), lambda h, j, r=r: (h // r, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d, dv), jnp.float32),
                        pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(qs, ks, q, k, v)
    return tuple(res) if return_res else res[0]


# ---------------------------------------------------------------------------
# Chunked multi-token decode: advance the (S, z) state over T new tokens in
# one grid step per (batch, head) — the serving-path building block for
# speculative/multi-token decode (ops.lln_decode_chunk).
# ---------------------------------------------------------------------------

def _lln_decode_kernel(qs_ref, ks_ref, v_ref, s0_ref, z0_ref,
                       o_ref, s1_ref, z1_ref, *, t):
    fq = jnp.exp(qs_ref[0].astype(jnp.float32))          # (t, d)
    fk = jnp.exp(ks_ref[0].astype(jnp.float32))          # (t, d)
    vv = v_ref[0].astype(jnp.float32)                    # (t, dv)
    s0 = s0_ref[0]                                       # (d, dv) fp32
    z0 = z0_ref[0]                                       # (1, d) fp32

    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    causal = (row >= col).astype(jnp.float32)

    scores = jax.lax.dot_general(fq, fk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * causal
    intra = jnp.dot(scores, vv, preferred_element_type=jnp.float32)
    intra_z = jnp.sum(scores, axis=-1)
    inter = jnp.dot(fq, s0, preferred_element_type=jnp.float32)
    inter_z = jnp.dot(fq, z0.reshape(-1, 1),
                      preferred_element_type=jnp.float32)[:, 0]
    den = intra_z + inter_z + EPS
    o_ref[0] = ((intra + inter) / den[:, None]).astype(o_ref.dtype)
    s1_ref[0] = s0 + jax.lax.dot_general(fk, vv, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
    z1_ref[0] = z0 + jnp.sum(fk, axis=0, keepdims=True)


def lln_decode_pallas(qs: jnp.ndarray, ks: jnp.ndarray, v: jnp.ndarray,
                      s0: jnp.ndarray, z0: jnp.ndarray, *, r: int = 1,
                      interpret: bool = False):
    """qs: (BH, T, D) pre-scaled; ks/v: (BG, T, D[v]); s0: (BH, D, DV) and
    z0: (BH, 1, D) pre-rescaled to the chunk's reference constant (fp32).

    Returns (out (BH, T, DV), s1, z1).  T should be padded by the caller to
    a sublane multiple with ks rows at NEG_INF (=> Phi(k) = 0, no state
    contribution) and qs/v rows at 0 (output rows sliced off).
    """
    bh, t, d = qs.shape
    dv = v.shape[-1]
    return pl.pallas_call(
        functools.partial(_lln_decode_kernel, t=t),
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda h: (h, 0, 0)),
            pl.BlockSpec((1, t, d), lambda h, r=r: (h // r, 0, 0)),
            pl.BlockSpec((1, t, dv), lambda h, r=r: (h // r, 0, 0)),
            pl.BlockSpec((1, d, dv), lambda h: (h, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda h: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, t, dv), lambda h: (h, 0, 0)),
            pl.BlockSpec((1, d, dv), lambda h: (h, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda h: (h, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, t, dv), v.dtype),
                   jax.ShapeDtypeStruct((bh, d, dv), jnp.float32),
                   jax.ShapeDtypeStruct((bh, 1, d), jnp.float32)],
        interpret=interpret,
    )(qs, ks, v, s0, z0)
